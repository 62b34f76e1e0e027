# Developer entry points.  Every target runs on the CPU
# (JAX_PLATFORMS=cpu, which jax honours).  How fast the system is comes
# from the chip alone: `python3 chipbench/run.py` (BENCHMARK.json,
# recorded by the driver in PERF_LEDGER.jsonl) and `python chip_smoke.py`
# exit non-zero without a TPU, so run them through the chip tool.

PY ?= python

.PHONY: smoke test test-fast verify-fast lint-graph obs-check \
	health-check aot-check cluster-check chaos-check \
	durability-check sp-check

# <3 min sanity gate: import + one eager op, one jitted llama forward
# step (the driver's entry()), and a 2-virtual-device multichip train
# step with numeric parity asserted.  Run this before ANY snapshot
# commit; it catches the classic "HEAD doesn't even import" breakage
# (round 5 shipped one) in seconds.
smoke:
	JAX_PLATFORMS=cpu $(PY) -c "\
	import numpy as np; \
	import paddle_tpu as paddle; \
	x = paddle.to_tensor(np.ones((2, 3), np.float32)); \
	y = paddle.to_tensor(np.ones((3, 4), np.float32)); \
	assert list(paddle.matmul(x, y).shape) == [2, 4]; \
	print('smoke: eager op OK'); \
	import __graft_entry__ as ge; \
	fn, args = ge.entry(); \
	import jax; \
	loss = float(jax.jit(fn)(*args)); \
	assert loss == loss, 'NaN loss'; \
	print(f'smoke: jitted llama step OK (loss {loss:.3f})'); \
	ge.dryrun_multichip(2); \
	print('smoke: multichip(2) OK')"
	JAX_PLATFORMS=cpu $(PY) -m pytest -q -p no:cacheprovider \
		tests/test_checkpoint_faults.py \
		tests/test_checkpoint_shardwise.py \
		tests/test_ckpt_checksum.py \
		tests/test_guardian.py \
		tests/test_watchdog.py \
		tests/test_dataloader_hardening.py \
		tests/test_grouped_gemm.py \
		tests/test_graph_lint.py \
		tests/test_infermeta.py \
		tests/test_moe_ep.py \
		tests/test_serving_scheduler.py \
		tests/test_load_harness.py \
		tests/test_prefix_cache.py \
		tests/test_spec_decode.py \
		tests/test_async_exec.py \
		tests/test_obs.py \
		tests/test_perf.py \
		tests/test_health.py \
		tests/test_aot.py \
		tests/test_quant.py \
		tests/test_cluster.py \
		tests/test_chaos.py \
		tests/test_durability.py
	$(MAKE) obs-check
	$(MAKE) health-check
	$(MAKE) aot-check
	$(MAKE) cluster-check
	$(MAKE) chaos-check
	$(MAKE) durability-check
	$(MAKE) sp-check

# Fast lane — must be green before any snapshot commit (see README).
test-fast:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m "not slow" \
		--continue-on-collection-errors -p no:cacheprovider

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
		--continue-on-collection-errors -p no:cacheprovider

# Graph-contract linter (paddle_tpu/analysis): traces every registered
# hot program (train step, five serving programs, fused-MoE body) on
# CPU and enforces its contract — dense-materialization ceiling,
# host-sync ban, donation coverage, dtype-upcast floor, collective
# inventory — plus the lowered-HLO host-sync scan.
lint-graph:
	JAX_PLATFORMS=cpu $(PY) tools/lint_graph.py

# Telemetry end-to-end smoke: guarded train step + seeded serving load
# under PT_OBS=on, then schema checks over the Prometheus exposition,
# the Chrome trace (trace IDs across a preemption) and a flight dump.
obs-check:
	JAX_PLATFORMS=cpu $(PY) tools/obs_dump.py

# Health-plane end-to-end smoke: seeded load against a deliberately
# violated TTFT SLO must fire a PAGE burn-rate alert, journal it,
# surface it in a live /statusz scrape, and resolve on recovery; plus
# the endpoint contract and event-journal schema/query checks.
health-check:
	JAX_PLATFORMS=cpu $(PY) tools/health_check.py

# AOT-plane end-to-end smoke: warm every (program x shape-rung) pair
# into a fresh compile cache, then prove a second engine re-warms
# entirely from disk with zero compiles and zero traces.
aot-check:
	JAX_PLATFORMS=cpu $(PY) tools/aot_warmup.py

# Fleet end-to-end smoke: 2-replica cluster under PT_OBS, seeded burst
# through the affinity router, drain one replica mid-load + join a
# fresh one — asserts zero request loss, journaled route/drain events,
# replica-labelled gauges and the /statusz cluster provider.
cluster-check:
	JAX_PLATFORMS=cpu $(PY) tools/cluster_check.py

# Survivability end-to-end smoke: 3-replica fleet takes an injected
# crash mid-load (failover + auto-restart), a seeded PT_CHAOS schedule
# over every fault point, and saturating submits against a bounded
# queue — asserts zero loss with bit-identical streams, REJECTED-with-
# retry-after shedding, and the fail/restart/shed telemetry contract.
chaos-check:
	JAX_PLATFORMS=cpu $(PY) tools/chaos_check.py

# Durable-serving end-to-end smoke: WAL journal roundtrip, a real
# subprocess SIGKILLed mid-load and recovered zero-loss/bit-identical,
# hung-replica KV-page salvage, and the durability telemetry contract.
durability-check:
	JAX_PLATFORMS=cpu $(PY) tools/durability_check.py

# Long-context end-to-end smoke: sequence-parallel chunked prefill on
# a forced-CPU mesh — streams bit-identical to single-device, the
# PT_SP_PREFILL=off gate bit-exact, the serve.prefill_sp contract
# (ring collective inventory + host-sync ban) linted, sp telemetry in
# Prometheus and /statusz.
sp-check:
	JAX_PLATFORMS=cpu $(PY) tools/sp_prefill_check.py

# Fast lane + regression gate: fails ONLY on failures not recorded in
# tools/fastlane_baseline.txt, so a dirty-but-known lane never blocks
# unrelated work while any NEW breakage does.
verify-fast: lint-graph
	$(PY) tools/check_fastlane.py
