# Developer entry points (tests name the CPU fake-chip platform through
# tests/conftest.py; bench and chip-smoke run on the TPU or fail).

# one cell of BENCHMARK.json, as the driver runs it
CELL ?= qwen2-7b.chat-steady

.PHONY: test test-fast native bench gateway-bench chip-smoke chaos docs dist clean lint

# aigw-check (ISSUE 15): the invariant lint suite — jit-surface
# registry, engine-thread discipline, async-blocking, determinism, and
# gauge/state drift — over the whole package. Exit 1 on any
# unsuppressed finding; tests/test_staticcheck.py runs the same gate
# in tier-1. See docs/development.md for the rule catalog.
lint:
	env JAX_PLATFORMS=cpu python tools/staticcheck.py

test: native
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q

test-fast: native
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -x --ignore=tests/test_llama_model.py \
	  --ignore=tests/test_parallel.py --ignore=tests/test_mixtral.py \
	  --ignore=tests/test_ring_attention.py --ignore=tests/test_pipeline.py

native:
	$(MAKE) -C native

# BENCHMARK.json's command for CELL=<name>: the last stdout line is
# the result. Exits 3 where there is no TPU.
bench:
	python3 cellbench/run.py --workload $(CELL) --seed 2147493001 --seconds 50 --trace 0

gateway-bench:
	python benchmarks/gateway_overhead.py

# The quickest proof that the system still starts on the chip:
# qwen2-7b W8A16 through `aigw run` -> picker -> `tpuserve` on one TPU
# chip, every Pallas kernel against its XLA twin, last stdout line one
# JSON verdict. Fails without a chip (CPU dry run: `python chip_smoke.py
# --platform cpu --model tiny-random`).
chip-smoke:
	python chip_smoke.py

# Fleet control plane chaos smoke (ISSUE 14): the non-slow half of the
# chaos matrix — controller predicates/hysteresis, drain routing,
# breaker unification, pre-first-byte failover — against stub replicas.
# The kill -9 / drain-retire rigs over real engines are the slow tier.
# AIGW_TSAN=1: the engine-thread sanitizer is asserted on under churn —
# a thread-discipline violation fails the chaos run loudly instead of
# corrupting streams silently (ISSUE 15).
chaos:
	env JAX_PLATFORMS=cpu AIGW_TSAN=1 python -m pytest tests/test_fleet_controller.py -q -m 'not slow' -p no:cacheprovider

docs:
	python docs/build_site.py

codegen:
	python -m aigw_tpu.config.clientgen

clean:
	$(MAKE) -C native clean

dist:
	pip wheel --no-deps --no-build-isolation -w dist/ .
	@ls -la dist/
