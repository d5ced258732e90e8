"""Finds a part of the benchmark by the name that ``BENCHMARK.json``, a
configuration or a mix gives it: the file ``<folder>/<name>.py`` beside this
one. Nothing here lists the parts, so a later cell brings its own as new
files:

* ``metrics/<metric>.py``  ``read(ctx)``, one per metric (``run.py``);
* ``zones/<dist>.py``      what a configuration's zone holds (``deploy.py``);
* ``programs/<kind>.py``   what a mix's program means: the system's
  ``Program`` and the plain reference it is held to (``loadgen.py``,
  ``check.py``). A program spec without ``"kind"`` is a ``filter``.
"""
from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

__all__ = ["load", "zone_kind", "program_kind"]

HERE = Path(__file__).resolve().parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@functools.cache
def load(folder: str, name: str, root: Path = HERE):
    """The module ``<folder>/<name>.py`` under ``root`` (the benchmark's own
    directory unless given); ``LookupError`` naming both where there is no
    such file."""
    path = root / folder / f"{name}.py"
    if not isinstance(name, str) or not _NAME.match(name) \
            or not path.is_file():
        raise LookupError(f"no {folder} file for {name!r}: "
                          f"{folder}/{name}.py is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{folder}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def zone_kind(spec: dict):
    """The generator of a configuration's ``zones`` entry."""
    return load("zones", spec["dist"])


def program_kind(spec: dict):
    """The kind of a mix's program spec."""
    return load("programs", spec.get("kind", "filter"))
