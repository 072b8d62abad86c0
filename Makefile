# Local enforcement targets — reference `make safety` parity (Makefile:216:
# clippy + kani + dylint there; arch lint + fuzz + sanitizers + contract
# gates here). CI (.github/workflows/ci.yml) runs the same gates.

PY ?= python
export JAX_PLATFORMS ?= cpu

.PHONY: safety lint lock-graph lock-graph-check shard-graph shard-graph-check modelcheck fuzz sanitizers contracts test native aot-tpu chaos trace-tests doctor ragged-tests pipeline-tests spec-tests tp-tests pd-tests federation-tests fleetobs-tests lifecycle-tests cancellation-tests tenancy-tests

safety: lint lock-graph-check shard-graph-check modelcheck fuzz sanitizers contracts aot-tpu chaos trace-tests doctor ragged-tests pipeline-tests spec-tests tp-tests pd-tests federation-tests fleetobs-tests lifecycle-tests cancellation-tests tenancy-tests  ## the full local gate

LINT_SARIF ?= build/fabric_lint.sarif
#: wall-clock budget for the whole-repo analyzer run (all three passes) —
#: the CI guard that keeps interprocedural passes from silently blowing up
#: the lint gate (exit 3 on overrun)
LINT_BUDGET ?= 120

lint:  ## fabric-lint (AS/JP/LK/RC/SH/AK interprocedural + migrated DE/EC families, SARIF artifact, wall-clock budget) + pytest driver + concurrency stress + license audit (deny.toml parity)
	@mkdir -p $(dir $(LINT_SARIF))
	$(PY) -m cyberfabric_core_tpu.apps.fabric_lint cyberfabric_core_tpu \
		--format sarif --output $(LINT_SARIF) --max-seconds $(LINT_BUDGET)
	$(PY) -m pytest tests/test_arch_lint.py tests/test_fabric_lint.py \
		tests/test_concurrency_stress.py \
		tests/test_license_audit.py -q -m "not slow"

lock-graph:  ## regenerate the checked lock-hierarchy artifact (docs/lock_graph.json) from the code
	$(PY) -m cyberfabric_core_tpu.apps.fabric_lint cyberfabric_core_tpu \
		--lock-graph json --output docs/lock_graph.json

lock-graph-check:  ## drift check: the committed hierarchy doc matches the regenerated graph (and stays acyclic)
	@$(PY) -m cyberfabric_core_tpu.apps.fabric_lint cyberfabric_core_tpu \
		--lock-graph json --output build/lock_graph.regen.json
	@diff -u docs/lock_graph.json build/lock_graph.regen.json \
		|| { echo "docs/lock_graph.json is stale — run 'make lock-graph' and commit"; exit 1; }

shard-graph:  ## regenerate the checked SPMD-world artifact (docs/shard_graph.json: mesh inventory, dispatch map, provenance, AOT key coverage) from the code
	$(PY) -m cyberfabric_core_tpu.apps.fabric_lint cyberfabric_core_tpu \
		--shard-graph json --output docs/shard_graph.json

shard-graph-check:  ## drift check: the committed SPMD doc matches the regenerated graph (and the AOT key stays complete)
	@$(PY) -m cyberfabric_core_tpu.apps.fabric_lint cyberfabric_core_tpu \
		--shard-graph json --output build/shard_graph.regen.json
	@diff -u docs/shard_graph.json build/shard_graph.regen.json \
		|| { echo "docs/shard_graph.json is stale — run 'make shard-graph' and commit"; exit 1; }

modelcheck:  ## kani parity: exhaustive pool-protocol model check + scheduler admission invariant walks
	$(PY) -m pytest tests/test_model_check_pool.py tests/test_model_check_scheduler.py -q

fuzz:  ## parser fuzzing: property layer + coverage-guided mutation w/ corpus
	FUZZ_EXAMPLES=2000 $(PY) -m pytest tests/test_odata_fuzz.py -q
	$(PY) -m fuzz.fuzz_odata --target all --time $${FUZZ_TIME:-20}

sanitizers:  ## TSAN/ASAN exercise of the native allocator + radix tree
	$(MAKE) -C native/fabric_host tsan asan

contracts:  ## OpenAPI golden gate + GTS docs validation (oasdiff equivalent)
	$(PY) -m pytest tests/test_openapi_contract.py -q
	$(PY) -m cyberfabric_core_tpu.apps.gts_docs_validator docs config README.md --vendor x

aot-tpu:  ## TPU lowering gate: serving set compiles for v5e via topology AOT
	$(PY) -m pytest tests/test_aot_tpu.py tests/test_feasibility.py -q

chaos:  ## faultlab: deterministic seeded chaos-scenario suite (every failpoint exercised, invariants green, repeat-stable)
	$(PY) -m pytest tests/test_faultlab.py -q
	$(PY) -m cyberfabric_core_tpu.apps.faultlab --repeat 2 > /dev/null

trace-tests:  ## request observability: flight-recorder + telemetry tests
	$(PY) -m pytest tests/test_flight_recorder.py tests/test_telemetry_export.py -q

doctor:  ## fabric-doctor: SLO engine/watchdog/state-machine tests + the burn-rate and stall chaos scenarios
	$(PY) -m pytest tests/test_doctor.py -q
	$(PY) -m cyberfabric_core_tpu.apps.doctor --scenarios > /dev/null

ragged-tests:  ## ragged mixed-batch kernel/scheduler tests
	$(PY) -m pytest tests/test_ragged_attention.py tests/test_mixed_batch.py -q

pipeline-tests:  ## deep-lookahead pipeline tests (streams byte-identical at any ring depth)
	$(PY) -m pytest tests/test_scheduler_pipeline.py -q

spec-tests:  ## batched speculative decoding tests (greedy streams byte-identical to k=0)
	$(PY) -m pytest tests/test_scheduler_spec.py -q

tp-tests:  ## tensor-parallel engine tests (tp=8 streams bit-identical to tp=1)
	$(PY) -m pytest tests/test_tp_engine.py tests/test_parallel.py -q

pd-tests:  ## prefill/decode disaggregation tests (PD-split streams bit-identical to unified)
	$(PY) -m pytest tests/test_pd_disaggregation.py -q

federation-tests:  ## federation tests (registry/routing/failover + multi-process e2e)
	$(PY) -m pytest tests/test_federation.py tests/test_federation_e2e.py -q

fleetobs-tests:  ## fleet observability tests
	$(PY) -m pytest tests/test_fleetscope.py -q

lifecycle-tests:  ## replica lifecycle tests
	$(PY) -m pytest tests/test_lifecycle.py tests/test_replicas.py -q

cancellation-tests:  ## end-to-end cancellation/deadline tests
	$(PY) -m pytest tests/test_cancellation.py -q

tenancy-tests:  ## tenant isolation tests
	$(PY) -m pytest tests/test_tenancy.py -q

test:  ## full suite
	$(PY) -m pytest tests/ -q

native:  ## build the native host library + PJRT AOT consumer
	$(MAKE) -C native/fabric_host
	$(MAKE) -C native/pjrt_host
