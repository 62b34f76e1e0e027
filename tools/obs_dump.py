"""make obs-check — end-to-end telemetry smoke on CPU.

Runs a guarded train step and a seeded serving load with PT_OBS on
(logical clock), then validates the three export surfaces the README
promises:

1. Prometheus exposition — serving SLO, guardian, and compile/retrace
   families present with sane values;
2. Chrome trace — a preempted request's trace ID threads
   submit -> admit -> prefill -> preempt -> re-admit -> finish;
3. flight recorder — a dump carries the preemption and retrace events
   in seq order.

Exits non-zero naming every violated check — wired into ``make smoke``.
"""
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the sequence-parallel plane (section 11) needs a real device mesh
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "0")

import numpy as np  # noqa: E402

FAILURES = []


def check(ok, what):
    print(f"  {'ok' if ok else 'FAIL'}: {what}")
    if not ok:
        FAILURES.append(what)


def main():
    import paddle_tpu as paddle
    from paddle_tpu import obs
    from paddle_tpu.inference.server import RequestState, ServingEngine
    from paddle_tpu.models import (
        CompiledTrainStep, LlamaConfig, LlamaForCausalLM)

    h = obs.configure(mode="on", clock=obs.LogicalClock())

    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)

    # -- a couple of train steps (train.* spans + step metrics) ---------
    step = CompiledTrainStep(model, lr=1e-3)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 16)).astype(np.int64)
    for _ in range(2):
        step.step(ids, ids)

    # -- seeded serving load with a forced preemption -------------------
    rng = np.random.RandomState(1)
    eng = ServingEngine(model, max_seqs=2, page_size=4, max_len=64,
                        num_pages=8)
    handles = [eng.submit(rng.randint(1, 256, (n,)).astype(np.int32),
                          max_new_tokens=8) for n in (7, 13, 21)]
    stats = eng.run()

    print("== run ==")
    check(all(hd.state is RequestState.FINISHED for hd in handles),
          "all requests finished")
    check(stats["preemptions"] >= 1, "page pressure forced a preemption")

    # -- 1. Prometheus exposition ---------------------------------------
    print("== prometheus exposition ==")
    prom = h.registry.prometheus_text()
    for fam in ("serve_requests_submitted_total",
                "serve_requests_total",
                "serve_preemptions_total",
                "serve_ttft_steps_bucket",
                "serve_queue_wait_steps_bucket",
                "train_steps_total",
                "train_step_wall_s_count",
                "jit_traces_total",
                "jit_dispatches_total"):
        check(fam in prom, f"family {fam}")
    check("serve_requests_submitted_total 3" in prom,
          "submitted counter == 3")
    check("train_steps_total 2" in prom, "train step counter == 2")

    # -- 1b. perf plane: HBM watermark gauges, no host-clock roofline ----
    print("== perf plane ==")
    for fam in ("hbm_peak_bytes", "hbm_bytes_in_use", "hbm_bytes_limit"):
        check(fam in prom, f"family {fam}")
    check("program_mfu" not in prom and "roofline_bound" not in prom,
          "no rate from a dispatch's host wall time")
    check("roofline" not in stats, "serving stats carry no roofline")

    # -- 2. Chrome trace with trace IDs across a preemption -------------
    print("== chrome trace ==")
    victim = next(hd for hd in handles if hd.num_preemptions >= 1)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.json")
        h.tracer.export_chrome(path)
        doc = json.loads(open(path).read())
    evs = doc.get("traceEvents", [])
    check(bool(evs) and evs[0].get("ph") == "M", "meta event present")
    names = [e["name"] for e in evs
             if e.get("args", {}).get("trace_id") == victim.rid]
    check(names[:1] == ["req.submit"], f"{victim.rid} starts at submit")
    check(names[-1:] == ["req.finish"], f"{victim.rid} ends at finish")
    want = ["req.submit", "req.admit", "req.prefill", "req.preempt",
            "req.admit", "req.finish"]
    it = iter(names)
    check(all(any(n == w for n in it) for w in want),
          f"{victim.rid} lifecycle order {want}")
    check(any(e["name"] == "train.step" for e in evs),
          "train.step spans exported")
    check(any(e.get("ph") == "C" and e["name"].startswith("perf.")
              for e in evs), "perf counter tracks exported")
    check(any(e.get("ph") == "M" and e["name"] == "thread_name"
              for e in evs), "thread_name metadata exported")

    # -- 3. flight recorder dump ----------------------------------------
    print("== flight recorder ==")
    text = obs.dump(reason="obs-check")
    lines = text.splitlines()
    head = json.loads(lines[0])["flight_recorder"]
    check(head["reason"] == "obs-check", "dump header reason")
    events = [json.loads(ln) for ln in lines[1:]]
    kinds = [e["kind"] for e in events]
    check("serve.preempt" in kinds, "preemption journaled")
    check("jit.trace" in kinds, "retraces journaled")
    seqs = [e["seq"] for e in events]
    check(seqs == sorted(seqs), "events in seq order")

    # -- 4. health plane: /statusz JSON + event-log schema --------------
    print("== health plane ==")
    from paddle_tpu.obs import events as ev_mod
    from paddle_tpu.obs import health
    sz = health.statusz_payload(h)
    check(json.loads(json.dumps(sz, default=str)) is not None,
          "/statusz payload is JSON-serializable")
    for key in ("build", "now", "heartbeats", "slos", "providers",
                "event_log"):
        check(key in sz, f"/statusz key {key}")
    check(sz["build"].get("project") == "paddle_tpu",
          "/statusz build info names the project")
    rows = {r["slo"]: r for r in sz["slos"]}
    check({"serve_ttft", "serve_errors"} <= set(rows),
          "/statusz carries the stock serving SLOs")
    for r in sz["slos"]:
        check({"slo", "source", "target", "state", "burn",
               "budget_remaining"} <= set(r),
              f"SLO row schema for {r.get('slo')}")
    check(rows.get("serve_errors", {}).get("state") == "ok",
          "no failed requests: error SLO ok")
    check(sz["providers"].get("serving", {}).get("pool", {})
          .get("num_pages", 0) > 0,
          "/statusz serving provider exposes the page pool")
    check("serving" in sz["heartbeats"], "serving heartbeat recorded")
    tail = h.events.events()
    check(bool(tail), "event log has a tail")
    check(all(all(k in e for k in ev_mod.SCHEMA_KEYS) for e in tail),
          "event-log schema (seq/ts/kind on every event)")
    echo = [e["seq"] for e in tail]
    check(echo == sorted(echo), "event log in seq order")
    ev_kinds = {e["kind"] for e in tail}
    check({"req.admit", "req.finish", "serve.preempt"} <= ev_kinds,
          "lifecycle events journaled (admit/finish/preempt)")
    check(len(ev_mod.query(tail, kind="req.finish")) == 3,
          "query by kind finds the three finishes")

    # -- 5. async executor: overlap ratio + phase telemetry -------------
    # a second engine (PT_ASYNC_EXEC on) takes over the /statusz
    # serving provider, so this section runs after the sync checks
    print("== async executor ==")
    eng2 = ServingEngine(model, max_seqs=2, page_size=4, max_len=64,
                         async_exec=True, slos=[])
    h2 = [eng2.submit(rng.randint(1, 256, (n,)).astype(np.int32),
                      max_new_tokens=12) for n in (6, 9)]
    eng2.run()
    check(all(hd.state is RequestState.FINISHED for hd in h2),
          "async engine drained")
    prom = h.registry.prometheus_text()
    check("serving_host_overlap_ratio" in prom,
          "host_overlap_ratio gauge exported")
    check('step_phase_seconds{phase="overlap",program='
          '"serve.step_async"}' in prom,
          "serve.step_async phase gauges exported")
    check(any(s.name == "perf.host_overlap"
              for s in h.tracer.spans), "host-overlap counter track")
    sz = health.statusz_payload(h)
    az = sz["providers"].get("serving", {}).get("async", {})
    check(az.get("mode") == "on", "/statusz async mode on")
    check(isinstance(az.get("replans"), int), "/statusz replan counter")
    check(az.get("host_overlap_ratio", -1) > 0,
          "/statusz host_overlap_ratio > 0")
    check(set(az.get("step_phase_seconds", {})) <= {
        "plan", "dispatch", "overlap", "fence", "commit"}
        and az.get("step_phase_seconds"),
        "/statusz per-step phase seconds")
    check("phase_seconds_total" in az, "/statusz cumulative phases")

    # -- 6. AOT compile cache: gauges + /statusz provider ----------------
    print("== aot compile cache ==")
    with tempfile.TemporaryDirectory() as d:
        eng3 = ServingEngine(model, max_seqs=2, page_size=4, max_len=64,
                             prefill_chunk=8, aot="warm",
                             compile_cache=d, slos=[])
        rep = eng3._aot_report
        check(rep is not None and rep["entries"] > 0,
              "warmup report covers entries")
        check(rep["compile"] == rep["entries"] and not rep["failed"],
              "cold warmup compiled every (program x rung) pair")
        # a second engine against the same cache dir must come off disk
        eng4 = ServingEngine(model, max_seqs=2, page_size=4,
                             max_len=64, prefill_chunk=8, aot="warm",
                             compile_cache=d, slos=[])
        rep2 = eng4._aot_report
        check(rep2["disk"] == rep2["entries"] and rep2["compile"] == 0,
              "re-warm resolves every entry from the persistent cache")
        prom = h.registry.prometheus_text()
        for fam in ("aot_compile_seconds", "aot_cache_hits_total",
                    "aot_cache_misses_total", "aot_cache_entries",
                    "aot_cache_bytes"):
            check(fam in prom, f"family {fam}")
        sz = health.statusz_payload(h)
        cc = sz["providers"].get("compile_cache", {})
        for key in ("dir", "entries", "bytes", "hits", "misses",
                    "hit_rate", "programs"):
            check(key in cc, f"/statusz compile_cache key {key}")
        check(cc.get("entries", 0) == rep["entries"],
              "/statusz entry count matches the warmup plan")
        check(cc.get("hits", 0) >= rep2["disk"] > 0,
              "/statusz hit accounting reflects the disk re-warm")

    # -- 7. quant plane: pool-dtype/mode gauges + /statusz section -------
    print("== quant plane ==")
    eng5 = ServingEngine(model, max_seqs=2, page_size=4, max_len=64,
                         quant="int8", slos=[])
    h5 = [eng5.submit(rng.randint(1, 256, (n,)).astype(np.int32),
                      max_new_tokens=8) for n in (5, 11)]
    eng5.run()
    check(all(hd.state is RequestState.FINISHED for hd in h5),
          "int8 engine drained")
    prom = h.registry.prometheus_text()
    check('kv_pool_dtype{dtype="int8"} 1' in prom,
          "kv_pool_dtype gauge marks int8")
    check('quant_mode{mode="int8"} 1' in prom,
          "quant_mode gauge marks int8")
    sz = health.statusz_payload(h)
    qz = sz["providers"].get("serving", {}).get("quant", {})
    for key in ("mode", "kv_pool_dtype", "weight_format",
                "kv_scale_bytes"):
        check(key in qz, f"/statusz quant key {key}")
    check(qz.get("mode") == "int8" and qz.get("kv_pool_dtype") == "int8",
          "/statusz quant section reflects the int8 build")
    check(qz.get("kv_scale_bytes", 0) > 0,
          "/statusz reports per-page scale bytes")

    # -- 8. cluster plane: replica-labelled gauges + /statusz section ----
    print("== cluster plane ==")
    from paddle_tpu.inference.server import ServingCluster
    cl = ServingCluster(model, n_replicas=2, cluster=True,
                        disaggregated=True, max_seqs=2, page_size=4,
                        max_len=64, slos=[])
    h8 = [cl.submit(rng.randint(1, 256, (n,)).astype(np.int32),
                    max_new_tokens=6) for n in (6, 10, 14)]
    cl.run()
    check(all(hd.state is RequestState.FINISHED for hd in h8),
          "disaggregated fleet drained")
    prom = h.registry.prometheus_text()
    for fam in ("cluster_replica_free_pages", "cluster_replica_in_flight",
                "cluster_replica_state", "cluster_replicas_active"):
        check(fam in prom, f"family {fam}")
    check('cluster_replica_state{replica="r0"}' in prom
          and 'cluster_replica_state{replica="r1"}' in prom,
          "gauges labelled per replica")
    ev_kinds = {e["kind"] for e in h.events.events()}
    check("route.decide" in ev_kinds, "route.decide journaled")
    check("kv.handoff" in ev_kinds, "kv.handoff journaled")
    sz = health.statusz_payload(h)
    cz = sz["providers"].get("cluster", {})
    for key in ("tick", "enabled", "disaggregated", "router",
                "handoffs", "drains", "joins", "replicas"):
        check(key in cz, f"/statusz cluster key {key}")
    check(cz.get("disaggregated") is True
          and cz.get("handoffs", {}).get("done", 0) > 0,
          "/statusz records the prefill->decode handoffs")
    for row in cz.get("replicas", []):
        check({"name", "role", "state", "in_flight", "pool"}
              <= set(row), f"replica row schema for {row.get('name')}")
    check([r["role"] for r in cz.get("replicas", [])]
          == ["prefill", "decode"], "/statusz replica roles")

    # -- 9. survivability plane: fail/shed telemetry + /statusz ----------
    print("== survivability plane ==")
    from paddle_tpu.testing import faults
    faults.reset("replica.fail:before:5=crash")
    cl9 = ServingCluster(model, n_replicas=2, cluster=True, max_seqs=2,
                         page_size=4, max_len=64, max_queue=2, slos=[])
    h9 = [cl9.submit(rng.randint(1, 256, (n,)).astype(np.int32),
                     max_new_tokens=6, rid=f"sv{i}")
          for i, n in enumerate((6, 10, 14, 8, 12, 7))]
    cl9.run()
    faults.reset()
    check(all(hd.state in (RequestState.FINISHED, RequestState.REJECTED)
              for hd in h9), "fleet drained through crash + shedding")
    check(cl9.failovers > 0 and cl9.sheds > 0,
          "crash failed requests over AND the backlog shed")
    prom = h.registry.prometheus_text()
    for fam in ("cluster_failovers_total", "cluster_shed_total",
                "cluster_orphan_requests"):
        check(fam in prom, f"family {fam}")
    ev_kinds = {e["kind"] for e in h.events.events()}
    for kind in ("replica.fail", "req.failover", "req.shed",
                 "replica.restart"):
        check(kind in ev_kinds, f"{kind} journaled")
    sz = health.statusz_payload(h)
    sv = sz["providers"].get("survivability", {})
    for key in ("tick", "policy", "admission", "failovers", "shed",
                "orphans", "restarts", "retired", "replicas"):
        check(key in sv, f"/statusz survivability key {key}")
    check(sv.get("admission", {}).get("max_queue") == 2,
          "/statusz admission shows the backlog bound")
    for row in sv.get("replicas", []):
        check({"name", "state", "hung", "last_beat", "missed_beats",
               "fails", "fail_streak", "restarts"} <= set(row),
              f"survivability row schema for {row.get('name')}")

    # -- 10. durability plane: WAL counters + dedup + /statusz ----------
    print("== durability plane ==")
    from paddle_tpu.inference.server import wal as wal_mod

    wal_dir = os.path.join(tempfile.mkdtemp(prefix="pt-obs-wal-"), "j")
    cl10 = ServingCluster(model, n_replicas=2, cluster=True, max_seqs=2,
                          page_size=4, max_len=64, wal=wal_dir, slos=[])
    p10 = rng.randint(1, 256, (9,)).astype(np.int32)
    h10 = cl10.submit(p10, max_new_tokens=5, rid="dur0")
    toks10 = h10.result()
    dup10 = cl10.submit(p10, max_new_tokens=5, rid="dur0")
    check(dup10.tokens == toks10 and cl10.dedup_hits == 1,
          "duplicate rid deduped to the journaled stream")
    recs10, rep10 = wal_mod.replay(wal_dir)
    check(rep10["corrupt"] == 0 and rep10["records"] == len(recs10),
          "journal replays clean")
    kinds10 = {r["t"] for r in recs10}
    check({"submit", "admit", "token", "finish", "dedup"} <= kinds10,
          "lifecycle record kinds journaled")
    prom = h.registry.prometheus_text()
    for fam in ("wal_appended_total", "wal_fsyncs_total",
                "wal_replayed_total", "wal_lag_records"):
        check(fam in prom, f"family {fam}")
    ev_kinds = {e["kind"] for e in h.events.events()}
    for kind in ("req.dedup", "wal.replay"):
        check(kind in ev_kinds, f"{kind} journaled")
    dz = health.statusz_payload(h)["providers"].get("durability", {})
    for key in ("wal", "dedup_hits", "salvage", "recovery"):
        check(key in dz, f"/statusz durability key {key}")
    check((dz.get("wal") or {}).get("appended", 0) > 0
          and "lag_records" in (dz.get("wal") or {}),
          "/statusz WAL table live")
    # journal compaction: live state rewritten, telemetry published
    rep10c = cl10.wal.compact()
    check(rep10c is not None and rep10c["segments_dropped"] >= 1,
          "WAL compaction rewrote the journal")
    check("wal_compactions_total" in h.registry.prometheus_text(),
          "family wal_compactions_total")
    check(cl10.wal.statusz().get("compactions") == 1,
          "/statusz WAL compactions counter")

    # -- 11. sequence-parallel plane: sp counters + /statusz sp ---------
    print("== sequence-parallel plane ==")
    from paddle_tpu.distributed import ProcessMesh

    mesh11 = ProcessMesh(list(range(2)), dim_names=["sp"])
    eng11 = ServingEngine(model, max_seqs=2, page_size=4, max_len=128,
                          prefill_chunk=16, sp_mesh=mesh11,
                          sp_prefill=True, sp_min_tokens=16)
    h11 = eng11.submit(rng.randint(1, 256, (48,)).astype(np.int32),
                       max_new_tokens=4, rid="sp0")
    check(len(h11.result()) == 4, "sp engine served a long prompt")
    check(eng11.executor.sp_prefill_tokens >= 48,
          "prompt prefilled through serve.prefill_sp")
    prom = h.registry.prometheus_text()
    for fam in ("sp_prefill_tokens_total", "sp_gather_pages_total"):
        check(fam in prom, f"family {fam}")
    spz = (health.statusz_payload(h)["providers"].get("serving")
           or {}).get("sp") or {}
    for key in ("mode", "degree", "axis", "min_tokens",
                "prefill_tokens"):
        check(key in spz, f"/statusz sp key {key}")
    check(spz.get("mode") == "on" and spz.get("degree") == 2,
          "/statusz sp table live")

    if FAILURES:
        print(f"\nobs-check: {len(FAILURES)} check(s) FAILED")
        for f in FAILURES:
            print(f"  - {f}")
        return 1
    print(f"\nobs-check: all checks passed "
          f"({len(evs)} trace events, {len(events)} flight events)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
