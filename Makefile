# CI entry points. `make ci` is lint plus the FAST pytest tier: everything
# not marked `slow` (the emulation-sleep and big-model compile tests; run
# the complete suite with `make test-all`). Speed is measured on the chip
# only, by `chipbench/run.py` over the cells in BENCHMARK.json.
PYTHONPATH := src:$(PYTHONPATH)
export PYTHONPATH

.PHONY: test test-all ci lint

test:
	python -m pytest -x -q -m "not slow"

# the complete suite, slow tier included (coverage identical to the
# pre-split `make test`)
test-all:
	python -m pytest -x -q

# static checks when the linter is available; the container image does not
# guarantee ruff, so its absence skips (loudly) rather than failing CI
lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check src tests; \
	else \
		echo "ruff not installed; skipping lint"; \
	fi

ci: lint test
