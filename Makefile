# Tier-1 verification + common dev entry points.

PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: verify test coverage lint bench-mixing bench-wire bench-rounds bench-lm-rounds bench-serve bench quickstart install sweep-smoke sweep-paper sweep-churn-smoke sweep-lm-smoke

verify:  ## tier-1 test suite (the CI gate); CPU only, kernels in interpret mode
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q

lint:  ## ruff baseline (when installed) + repro.lint repo rules
	@if $(PY) -c "import ruff" >/dev/null 2>&1; then \
	    $(PY) -m ruff check .; \
	else \
	    echo "ruff not installed; skipping the ruff baseline"; \
	fi
	$(PY) -m repro.lint src

coverage:  ## tier-1 with line coverage gated on the mixing core + kernels
	$(PY) -m pytest -q --cov=repro.core --cov=repro.kernels \
	    --cov-report=term-missing --cov-fail-under=85

sweep-smoke:  ## 3-family smoke sweep (minutes, CPU) -> results/ + BENCH_sweep.json
	$(PY) -m repro.experiments.sweep --preset smoke \
	    --store results/sweep_smoke.jsonl --bench-out BENCH_sweep.json

sweep-large-n-smoke:  ## tiny-N large_n stand-in: fused sparse_sharded end to end
	$(PY) -m repro.experiments.sweep --preset large_n_smoke \
	    --store results/sweep_large_n_smoke.jsonl \
	    --bench-out BENCH_large_n_smoke.json

sweep-churn-smoke:  ## hub-kill vs leaf-kill churn gate (faults subsystem)
	$(PY) -m repro.experiments.sweep --preset churn_smoke \
	    --store results/sweep_churn_smoke.jsonl \
	    --bench-out BENCH_churn_smoke.json

sweep-lm-smoke:  ## LLM-cohort gate: ring/star gossip beats isolation on g2_token_spread
	$(PY) -m repro.experiments.sweep --preset lm_smoke \
	    --store results/sweep_lm_smoke.jsonl \
	    --bench-out BENCH_lm_smoke.json

sweep-paper:  ## the paper's N=100 matrix (ER/BA/SBM x splits x 3 seeds)
	$(PY) -m repro.experiments.sweep --preset paper \
	    --store results/sweep_paper.jsonl --bench-out BENCH_sweep.json

test: verify

install:  ## editable install with test extras (hypothesis, networkx)
	$(PY) -m pip install -e ".[test]"

bench-mixing:  ## dense vs sparse gossip sweep + halo wire volumes -> BENCH_mixing.json
	$(PY) benchmarks/bench_mixing.py

bench-wire:  ## wire-volume model only (allgather vs ring halo, S=8, fast)
	$(PY) benchmarks/bench_mixing.py --sizes "" --out BENCH_mixing_wire.json

bench-rounds:  ## fused (one lax.scan) vs Python-loop rounds/s -> BENCH_rounds.json
	$(PY) benchmarks/bench_rounds.py

bench-lm-rounds:  ## fused vs loop LM cohort rounds/s -> BENCH_lm_rounds.json
	$(PY) benchmarks/bench_lm_rounds.py

bench-serve:  ## chunked prefill + engine identity + routing delta -> BENCH_serve.json
	$(PY) benchmarks/bench_serve.py

bench:  ## quick paper-figure benchmark harness
	$(PY) benchmarks/run.py

quickstart:
	$(PY) examples/quickstart.py
