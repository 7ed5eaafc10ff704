# Top-level developer/CI entry points.
#
#   make native   - build the preload shim + native test programs
#   make test     - full pytest suite (CPU JAX, 8 virtual devices)
#   make ci       - the full gate: native build, tests, multichip dry run,
#                   and the 1k-host twice-run determinism check
#
# The benchmark is benchmark/run.py (BENCHMARK.json), run on a TPU host.

.PHONY: native test ci clean

native:
	$(MAKE) -C native

test: native
	python -m pytest tests/ -q

ci: native
	bash tools/ci.sh

clean:
	$(MAKE) -C native clean
	rm -rf shadow.data
