PY := python
export PYTHONPATH := src

.PHONY: analyze check check-all test test-all smoke smoke-sweep \
        smoke-sweep-closedloop smoke-sweep-executor smoke-dispatch \
        golden bench bench-smoke bench-compiled

# Static determinism & cache-integrity analysis (DESIGN.md Sections
# 9+11): the repro.analysis passes — fingerprint/determinism/protocol
# plus the engine-verification trio (conformance/translate/layout) —
# then ruff (pyflakes/pycodestyle-errors/isort per pyproject.toml).
# Ruff is a dev extra — skipped with a notice where it is not installed
# (CI installs it and enforces both).
analyze:
	$(PY) -m repro.analysis
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
		$(PY) -m ruff check src tests benchmarks; \
	else \
		echo "ruff not installed (pip install -r requirements-dev.txt); skipping lint gate"; \
	fi

# Fast tier (default): deselects @pytest.mark.slow (golden-trace sweep
# regression, full Table-5 cells, 8-device distributed run).
test:
	$(PY) -m pytest -x -q -m "not slow"

# Everything, including the slow markers.
test-all:
	$(PY) -m pytest -x -q

# Tiny-config end-to-end smokes: the DES benchmarks that need no JAX
# compilation, plus the async serving path (real jitted steps, reduced
# configs).
smoke:
	$(PY) -m benchmarks.run fig01 fig04 table5 --jobs 2
	$(PY) -m repro.launch.serve --jobs yi-6b:4,minicpm3-4b:2 \
	    --policy srtf --compare-fifo \
	    --tokens-per-block 4 --prompt-len 8 --batch 1

# Sweep-runner smoke on a cheap subset: exercises the multiprocess fan-out
# and the on-disk cache without the full 56-pair grid.
smoke-sweep:
	$(PY) -m benchmarks.run fig01 table5 scenarios --jobs 2 --subset 4 \
	    --no-cache

# Executor-machine sweep smoke: the real-JAX lane executor driven through
# SweepSpec/run_sweep (tiny grid, spawn-pool fan-out, measured cells).
# Fan-out needs JAX held to the CPU: a chip belongs to one process.
smoke-sweep-executor:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.run --machine executor --jobs 2 \
	    --subset 1 --no-cache

# Closed-loop sweep smoke: completion-driven M/G/k + think-time cells
# (arrival processes fed by the DES feedback edge) through the same
# runner — small spec, multiprocess fan-out.
smoke-sweep-closedloop:
	$(PY) -m benchmarks.run closedloop --jobs 2 --subset 1 --no-cache

# Distributed-dispatch smoke: the same cheap subset served to a 2-worker
# localhost queue farm (DESIGN.md Section 12) — exercises the wire
# protocol, chunked in-worker runner, packfile ingest, and the
# byte-identical record path end to end.
smoke-dispatch:
	$(PY) -m benchmarks.run table5 --jobs 2 --subset 2 --no-cache \
	    --dispatch queue --workers 2

# Persistent DES perf lane: blocks/sec + cold/warm sweep wall time on
# standardized workloads, written to BENCH_des.json at the repo root
# (benchmarks/perf.py; every perf PR reports against this file).
bench:
	$(PY) -m benchmarks.perf

# Reduced perf lane for CI: same row shape, small workloads; the JSON is
# uploaded as a per-commit artifact so the trajectory accumulates.
bench-smoke:
	$(PY) -m benchmarks.perf --smoke --jobs 2 --repeat 1

# Compiled-engine slice of the perf lane (skips the slow python-engine
# throughput rows; same JSON shape — the lane to iterate on while working
# on the engine.  DESIGN.md Section 10).
bench-compiled:
	$(PY) -m benchmarks.perf --engine compiled

check: test smoke

check-all: test-all smoke smoke-sweep smoke-sweep-closedloop \
	smoke-sweep-executor

# Regenerate the golden-trace fixture (ONLY when a schedule change is
# intended and reviewed; tests/test_golden_traces.py pins the current one).
golden:
	$(PY) tests/make_golden_traces.py
