"""The benchmark's self-tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a cell made of new files only, which the harness proper does not ship
NEW_CELL = HERE / "data" / "new_cell"

