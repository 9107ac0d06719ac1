# Repro toolchain entry points.
#
#   make test          — tier-1 verify (full pytest suite, 8 forced devices)
#   make bench-smoke   — quick benchmark pass: engine executor suite
#   make bench-engine  — full Sim-vs-Mesh executor benchmark -> BENCH_engine.json
#   make bench-elastic — elastic resize-event cost benchmark -> BENCH_elastic.json
#   make bench-serve   — serving suite (lookup/service/hot-swap) -> BENCH_serve.json
#   make bench-comm    — scheme x transport wall + measured wire bytes -> BENCH_comm.json
#   make bench-hier    — flat vs hierarchical (2x4) wall + per-tier wire bytes -> BENCH_hier.json
#   make bench-obs     — instrumented-vs-bare tracing overhead + traced 2-host
#                        run -> BENCH_obs.json (the <=1.03x obs gate input)
#   make bench-chaos   — seeded fault-injection run (kills + straggler +
#                        partition) vs the fault-free oracle -> BENCH_chaos.json
#   make bench-profile — roofline-attributed profiling: per-window cost
#                        attribution of the three schemes on the 8-device
#                        mesh -> BENCH_profile.json (the check_profile input)
#   make bench-adapt   — adaptive-communication suite: {fixed,dynamic} merge x
#                        {dense,bf16,int8} wire + the fixed-tau frontier legs
#                        -> BENCH_adapt.json (the check_adapt gate input; runs
#                        non-quick so the exact wire pins match the baseline)
#   make perf-report   — render every committed BENCH_*.json baseline plus
#                        attribution into a self-contained perf_report.html
#   make serve-smoke   — quantization service end to end: live elastic trainer
#                        hot-swapping codebooks under open-loop load
#   make trace-smoke   — 2-host traced + metered train run, then the trace
#                        invariant checker (repro.obs.check) on the export
#   make ci-local      — mirror the full CI matrix locally (lint, tier-1 under
#                        1 AND 8 forced devices, fresh engine + serve benches +
#                        the regression gates, the obs overhead gate, the
#                        chaos fault-injection gate, and the trace-invariant
#                        smoke) so CI failures reproduce without pushing
#   make example-mesh  — the 8-device mesh demo against the sim oracles
#   make example-elastic — the 8->4->8 elastic resharding demo
#   make example-serve — the train-while-serve demo (examples/serve_vq.py)

PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export XLA_FLAGS ?= --xla_force_host_platform_device_count=8

.PHONY: test lint bench-smoke bench-engine bench-elastic bench-serve \
        bench-comm bench-hier bench-obs bench-chaos bench-profile \
        bench-adapt perf-report serve-smoke trace-smoke ci-local \
        example-mesh example-elastic example-serve

test:
	$(PY) -m pytest -x -q

lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check src tests benchmarks examples; \
	else \
		echo "ruff not installed; skipping (CI runs it)"; \
	fi
	$(PY) -m compileall -q src tests benchmarks examples

bench-smoke:
	$(PY) -m benchmarks.run --suite engine --quick

bench-engine:
	$(PY) -m benchmarks.run --suite engine

bench-elastic:
	$(PY) -m benchmarks.run --suite elastic

bench-serve:
	$(PY) -m benchmarks.run --suite serve

bench-comm:
	$(PY) -m benchmarks.run --suite comm --quick

bench-hier:
	$(PY) -m benchmarks.run --suite hier --quick

bench-obs:
	$(PY) -m benchmarks.run --suite obs --quick

bench-chaos:
	$(PY) -m benchmarks.run --suite chaos --quick

bench-profile:
	$(PY) -m benchmarks.run --suite profile --quick

bench-adapt:
	$(PY) -m benchmarks.run --suite adapt

perf-report:
	$(PY) -m repro.obs.report --out perf_report.html

serve-smoke:
	$(PY) -m repro.launch.serve --mode vq --smoke --train-publish

# the checker's runpy RuntimeWarning ('repro.obs.check found in
# sys.modules') is harmless: the package __init__ imports the submodule
# before -m re-executes it as __main__
trace-smoke:
	$(PY) -m repro.launch.train --mode vq --executor mesh --scheme delta \
		--workers 8 --hosts 2 --points 400 \
		--trace ci.trace.json --metrics ci.metrics.jsonl
	$(PY) -m repro.obs.check ci.trace.json --expect-merge-tiers 0,1 \
		--expect-counter codebook_divergence --expect-counter distortion
	$(PY) -m repro.launch.train --mode vq --executor mesh --scheme delta \
		--workers 8 --points 400 --merge dynamic --divergence-thresh 1e-3 \
		--wire-quant int8 --trace ci.adapt.trace.json
	$(PY) -m repro.obs.check ci.adapt.trace.json \
		--expect-counter divergence_trigger

ci-local: lint
	XLA_FLAGS=--xla_force_host_platform_device_count=1 $(PY) -m pytest -q
	XLA_FLAGS=--xla_force_host_platform_device_count=8 $(PY) -m pytest -q
	XLA_FLAGS=--xla_force_host_platform_device_count=1 \
		$(PY) -m repro.launch.serve --mode vq --smoke --train-publish
	$(PY) -m repro.launch.serve --mode vq --smoke --train-publish
	$(PY) -m benchmarks.run --suite engine --quick --out BENCH_engine.fresh.json
	$(PY) -m benchmarks.check_regression \
		--baseline BENCH_engine.json --fresh BENCH_engine.fresh.json
	$(PY) -m benchmarks.run --suite serve --quick --out BENCH_serve.fresh.json
	$(PY) -m benchmarks.check_regression \
		--baseline BENCH_serve.json --fresh BENCH_serve.fresh.json
	$(PY) -m benchmarks.run --suite comm --quick --out BENCH_comm.fresh.json
	$(PY) -m benchmarks.check_regression \
		--baseline BENCH_comm.json --fresh BENCH_comm.fresh.json
	$(PY) -m benchmarks.run --suite hier --quick --out BENCH_hier.fresh.json
	$(PY) -m benchmarks.check_regression \
		--baseline BENCH_hier.json --fresh BENCH_hier.fresh.json
	$(PY) -m benchmarks.run --suite obs --quick --out BENCH_obs.fresh.json
	$(PY) -m benchmarks.check_regression \
		--baseline BENCH_obs.json --fresh BENCH_obs.fresh.json
	$(PY) -m benchmarks.run --suite chaos --quick --out BENCH_chaos.fresh.json
	$(PY) -m benchmarks.check_regression \
		--baseline BENCH_chaos.json --fresh BENCH_chaos.fresh.json
	$(PY) -m benchmarks.run --suite profile --quick --out BENCH_profile.fresh.json
	$(PY) -m benchmarks.check_regression \
		--baseline BENCH_profile.json --fresh BENCH_profile.fresh.json
	$(PY) -m benchmarks.run --suite adapt --out BENCH_adapt.fresh.json
	$(PY) -m benchmarks.check_regression \
		--baseline BENCH_adapt.json --fresh BENCH_adapt.fresh.json
	$(PY) -m repro.obs.report --out perf_report.html
	$(MAKE) trace-smoke
	$(PY) -m benchmarks.run --suite elastic --quick --out BENCH_elastic.fresh.json

example-mesh:
	$(PY) examples/mesh_vq.py

example-elastic:
	$(PY) examples/elastic_vq.py

example-serve:
	$(PY) examples/serve_vq.py
