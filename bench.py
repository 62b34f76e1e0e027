"""Benchmark: Llama pretrain step throughput on the local chip(s).

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Metric: tokens/sec/chip for a causal-LM train step (fwd+bwd+AdamW, bf16
compute, remat) — the BASELINE.md headline metric shape.  vs_baseline is
MFU / 0.45 (the north-star MFU target), since the reference publishes no
absolute numbers (BASELINE.md).

``python bench.py`` is the chip path: it exits non-zero when jax finds
no TPU, and when any leg that was asked for fails.  ``--cpu-counts``
is the explicit CPU lane (tiny config, logical-clock serving legs,
``"platform": "cpu"`` in every line) — counts for ``make perf-check``,
never a device metric.
"""
import json
import os
import sys
import time

import numpy as np

_T0 = time.perf_counter()

# set by main(): importing this module (tests do) must not move the
# process-global compile cache
_CACHE_DIR = None
_CACHE_WARM = False


def _enable_compile_cache():
    """JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
    (utils.enable_compile_cache); min_compile_secs=0 because a sealed
    chip machine keeps nothing between runs but this directory."""
    global _CACHE_DIR, _CACHE_WARM
    from paddle_tpu.utils import enable_compile_cache

    _CACHE_DIR = enable_compile_cache()
    n = _cache_entries()
    print(f"compile cache: {_CACHE_DIR} ({n} entries at start)",
          file=sys.stderr)
    # Warm start: when the persistent compile cache already has entries
    # compiles are cache hits and the cold-compile cost estimates below
    # would over-skip — use the warm estimates instead.
    _CACHE_WARM = n > 0


def _cache_entries():
    from paddle_tpu.utils import compile_cache_entries

    return compile_cache_entries(_CACHE_DIR) if _CACHE_DIR else 0


def _cache_report(tag):
    """Log cache growth so BENCH artifacts show whether compiles hit the
    persistent cache (VERDICT r3 weak #1)."""
    print(f"compile cache after {tag}: {_cache_entries()} entries",
          file=sys.stderr)


def _peak_flops_per_chip():
    # Single source of truth for peak figures: the perf plane's table
    # (obs/perf.py) — bench and the runtime MFU gauges must agree.
    from paddle_tpu.obs import perf

    return perf.peak_flops_per_chip()


def _require_tpu(jax):
    """The chip path has no CPU fallback: a run that finds no TPU must
    fail, not measure XLA's CPU backend under a device metric's name."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py: no TPU: jax.devices()[0].platform is "
              f"{dev.platform!r} (the chip path needs a chip; "
              f"--cpu-counts runs the CPU count lane)", file=sys.stderr)
        sys.exit(2)


def main(cpu_counts=False):
    # Keep stdout clean: everything but the final JSON goes to stderr.
    result = {}

    def _leg(key, fn, into=None):
        """Run one requested leg.  A failure is recorded in the artifact
        as {"error": ...} (earlier measurements are never lost); every
        such entry, sub-legs included, turns into a non-zero exit at
        the end (_failed_legs)."""
        into = result if into is None else into
        try:
            into[key] = fn()
        except Exception as e:
            print(f"{key}: FAILED: {e}", file=sys.stderr)
            into[key] = {"error": str(e)[:200]}
        _cache_report(key)
        print(json.dumps(result), flush=True)

    # Cold-start leg FIRST, while this process has not touched a jax
    # backend: its two children each build a ServingEngine, and a chip
    # belongs to one process at a time — a parent that had initialised
    # jax would hold the chip and the children could never get it.
    # (Runs on either lane: the children report their own platform.)
    if os.environ.get("PT_BENCH_COLDSTART", "1") != "0":
        _leg("coldstart", lambda: _bench_coldstart(cpu_counts))

    _enable_compile_cache()
    import jax

    from paddle_tpu.models import (
        CompiledTrainStep, LlamaConfig, LlamaForCausalLM, llama_shard_rules,
    )
    from paddle_tpu.distributed import ProcessMesh

    on_cpu = cpu_counts
    if on_cpu:
        if jax.default_backend() != "cpu":
            raise SystemExit("--cpu-counts needs JAX_PLATFORMS=cpu")
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                          intermediate_size=688, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=512, recompute=True,
                          scan_layers=True)
        batch, seq, steps = 4, 256, 3
    else:
        _require_tpu(jax)
        # ~640M-param model (largest that fits 16G HBM with fp32 master +
        # bf16 moments + full-layer remat): head_dim 128 keeps the MXU
        # lanes full; scan_layers compiles one decoder body.
        impl = os.environ.get("PT_BENCH_ATTN", "auto")
        blocks = os.environ.get("PT_BENCH_FLASH_BLOCKS")
        blocks = (tuple(int(x) for x in blocks.split(","))
                  if blocks else None)
        # full | dots | save_attn | save_mlp (save the two MLP dot
        # outputs; refwd skips the layer's two big H×I GEMMs — the
        # candidate 0.60-MFU setting, HBM math in PERF.md round-7)
        policy = os.environ.get("PT_BENCH_REMAT", "full")
        # fused Pallas rms_norm: ~3-4% step-time win at this shape
        # (PERF.md r5); PT_BENCH_FUSED_RMS=0 reverts to the stock op
        if os.environ.get("PT_BENCH_FUSED_RMS", "1") == "1":
            import paddle_tpu

            paddle_tpu.set_flags({"FLAGS_use_fused_rms_norm": True})
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=10,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=2048,
                          recompute=os.environ.get(
                              "PT_BENCH_RECOMPUTE", "1") == "1",
                          recompute_policy=policy,
                          scan_layers=True, attention_impl=impl,
                          flash_blocks=blocks)
        batch = int(os.environ.get("PT_BENCH_BATCH", "8"))
        seq, steps = 2048, int(os.environ.get("PT_BENCH_STEPS", "10"))

    print(f"building model (layers={cfg.num_hidden_layers}, "
          f"hidden={cfg.hidden_size})...", file=sys.stderr)
    model = LlamaForCausalLM(cfg)
    n_devices = len(jax.devices())
    mesh = None
    rules = None
    if n_devices > 1:
        mesh = ProcessMesh(shape=[n_devices, 1], dim_names=["dp", "mp"])
        rules = llama_shard_rules
    step = CompiledTrainStep(model, lr=1e-4, mesh=mesh, shard_rules=rules,
                             compute_dtype="bfloat16",
                             moments_dtype="bfloat16")

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    print("compiling + warmup...", file=sys.stderr)
    tokens_per_step = batch * seq
    # MFU convention: model FLOPs (6N + attn, fwd+bwd) / peak — remat's
    # extra forward is hardware overhead, not counted as useful FLOPs.
    flops_per_token = model.flops_per_token(seq)
    dt, loss = _guarded(
        lambda: _time_steps(step.step, (ids, ids), steps, "llama"),
        flops_per_token * tokens_per_step / n_devices, "llama")

    tok_s = tokens_per_step / dt
    tok_s_chip = tok_s / n_devices
    mfu = tok_s_chip * flops_per_token / _peak_flops_per_chip()
    print(f"step {dt * 1e3:.1f} ms, loss {float(loss):.3f}, "
          f"tokens/s/chip {tok_s_chip:.0f}, MFU {mfu:.3f}",
          file=sys.stderr)

    result.update({
        # perf-check only auto-compares same-platform rounds
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": n_devices,
        "metric": "llama_pretrain_tokens_per_sec_per_chip",
        "value": round(tok_s_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "model_params": model.num_params(),
        "mfu": round(mfu, 4),
        "batch": batch, "seq": seq,
        "config": {"hidden": cfg.hidden_size,
                   "layers": cfg.num_hidden_layers,
                   "heads": cfg.num_attention_heads,
                   "vocab": cfg.vocab_size},
    })

    # Emit the headline line IMMEDIATELY (VERDICT r3: the round-3 combined
    # line was lost to a timeout; never again).  Each extended config then
    # re-prints the full combined line, so the LAST complete stdout line is
    # always the freshest parseable result whatever the driver's budget.
    print(json.dumps(result), flush=True)

    # Wall-clock budget for the whole bench process.  The driver kills us
    # (rc 124 in rounds 3-4) at an unknown limit; rather than die
    # mid-compile and lose the tail configs, skip any config whose
    # worst-case (cold-cache) cost doesn't fit the remaining budget and
    # record WHY in the artifact.
    budget_s = float(os.environ.get("PT_BENCH_BUDGET_S", "1500"))

    def _extend(key, skip_env, fn, est_cold_s, est_warm_s=None):
        import signal

        if on_cpu or os.environ.get(skip_env) == "1":
            return
        est = (est_warm_s if (_CACHE_WARM and est_warm_s is not None)
               else est_cold_s)
        elapsed = time.perf_counter() - _T0
        if elapsed + est > budget_s:
            print(f"{key}: SKIPPED (elapsed {elapsed:.0f}s + est "
                  f"{est}s > budget {budget_s:.0f}s)",
                  file=sys.stderr)
            result[key] = {"skipped": "budget",
                           "elapsed_s": round(elapsed, 1)}
            print(json.dumps(result), flush=True)
            return
        # Hard per-config wall cap: the pre-skip only guards the
        # ESTIMATE — a config whose compile blows past it must not eat
        # the remaining configs' budget.  SIGALRM is delivered when
        # control next returns to Python, i.e. after the compile or
        # fence it interrupts — enough to bound the damage.
        cap = max(int(budget_s - elapsed), 1)

        def _on_alarm(signum, frame):
            raise TimeoutError(f"{key} hit per-config cap {cap}s")

        def run():
            old = signal.signal(signal.SIGALRM, _on_alarm)
            signal.alarm(cap)
            try:
                return fn(jax)
            except TimeoutError as e:
                print(f"{key}: TIMED OUT: {e}", file=sys.stderr)
                return {"skipped": "budget", "hard_cap_s": cap}
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)

        _leg(key, run)
        print(f"elapsed after {key}: "
              f"{time.perf_counter() - _T0:.0f}s", file=sys.stderr)

    # -- the CPU count lane (--cpu-counts) -------------------------------
    # Logical-clock and layout-arithmetic legs: what they report are
    # counts and ratios of counts (tok/step, pages, FLOPs from shapes),
    # which a CPU run can give.  They stay off the chip path.
    serving = result.setdefault("serving", {}) if on_cpu else None

    def _tiny_eval(**kw):
        m = LlamaForCausalLM(LlamaConfig(**kw))
        m.eval()
        return m

    def _fleet_model():
        return _tiny_eval(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256)

    if on_cpu and os.environ.get("PT_BENCH_QUANT", "1") == "1":
        # int8 serving: occupancy ratio is layout-analytic, drift is a
        # numeric; the tok/s pair is CPU wall time and only ever read
        # as a same-host ratio
        def quant():
            qcfg = dict(vocab_size=2048, hidden_size=256,
                        intermediate_size=688, num_hidden_layers=4,
                        num_attention_heads=8, num_key_value_heads=8,
                        max_position_embeddings=512)
            qmodel = _tiny_eval(**qcfg)
            return _measure_quant(
                qmodel, qmodel.config,
                int(os.environ.get("PT_BENCH_SERVE_SEQS", "8")))
        _leg("quant", quant, into=serving)
    if on_cpu and os.environ.get("PT_BENCH_CLUSTER", "1") == "1":
        # N simulated replicas over ONE shared logical clock: decode
        # tokens per cluster step at N=1/2/4, p99 TTFT in steps, and
        # the affinity-vs-random routing delta
        _leg("cluster", lambda: _measure_cluster(_fleet_model()),
             into=serving)
    if on_cpu and os.environ.get("PT_BENCH_CLUSTER_FAILOVER",
                                 "1") == "1":
        # kill 1 of 4 replicas mid-load: recovery steps, TTFT tax in
        # steps, fraction of healthy-fleet tok/step retained (r21)
        _leg("cluster_failover",
             lambda: _measure_cluster_failover(_fleet_model()),
             into=serving)
    if on_cpu and os.environ.get("PT_BENCH_WAL", "1") == "1":
        # write-ahead journal: share of wall inside append/fsync, steps
        # to drain after recover(), re-prefill tokens salvage saves (r22)
        _leg("durability", lambda: _measure_durability(_fleet_model()),
             into=serving)
    if on_cpu and os.environ.get("PT_BENCH_SP_PREFILL", "1") == "1":
        # sequence-parallel prefill: per-device critical-path FLOPs vs
        # prompt length at sp 1/2/4 (r23).  A child process, because the
        # sp mesh needs forced host devices; it pins JAX_PLATFORMS=cpu,
        # so it never asks for a chip.
        _leg("sp_prefill", _measure_sp_prefill, into=serving)

    if not on_cpu:
        # Free the small config's HBM state before the extended runs.
        import gc

        del step
        for _, p in model.named_parameters():
            p._data = None
        del model
        gc.collect()

    # Cheapest-compile-first, with the two never-yet-recorded configs
    # (serving, large) BEFORE the UNet: its compile is the longest and
    # least predictable, so it must only ever cost itself.  Cold-cost
    # estimates from the r4/r5 runs; warm estimates assume the
    # persistent compile cache holds the programs.
    _extend("graph_lint", "PT_BENCH_SKIP_LINT", _bench_graph_lint,
            120, 40)
    _extend("obs_overhead", "PT_BENCH_SKIP_OBS", _bench_obs_overhead,
            120, 40)
    _extend("resnet50", "PT_BENCH_SKIP_RESNET", _bench_resnet, 150, 40)
    _extend("bert_base_squad", "PT_BENCH_SKIP_BERT", _bench_bert, 200, 50)
    _extend("detection_amp_o2", "PT_BENCH_SKIP_DET", _bench_detection,
            150, 40)
    _extend("serving", "PT_BENCH_SKIP_SERVING", _bench_serving, 180, 60)
    _extend("moe", "PT_BENCH_SKIP_MOE", _bench_moe, 150, 40)
    _extend("large", "PT_BENCH_SKIP_LARGE", _bench_large, 500, 120)
    _extend("sd_unet", "PT_BENCH_SKIP_UNET", _bench_unet, 250, 60)
    failed = _failed_legs(result)
    if failed:
        # the legs that ran are in the artifact; the exit code says one
        # that was asked for did not (__main__ exits 1)
        result["failed_legs"] = failed
        print(json.dumps(result), flush=True)
        print(f"bench.py: legs failed: {', '.join(failed)}",
              file=sys.stderr)
    return result


def _failed_legs(doc, path=""):
    """Dotted paths of every {"error": ...} entry in the artifact — a
    leg or sub-leg that was asked for and raised."""
    if not isinstance(doc, dict):
        return []
    if "error" in doc:
        return [path or "?"]
    return [p for k, v in doc.items()
            for p in _failed_legs(v, f"{path}.{k}" if path else k)]


def _bench_coldstart(cpu_counts=False):
    """AOT cold-start A/B (r18): cold-process time-to-first-token with
    the persistent compile cache empty vs warmed.

    Each measurement is a FRESH python process (the dryrun-worker
    pattern) running ``bench.py --coldstart-worker <dir>``: build a
    small ServingEngine with ``aot=warm`` against the shared cache dir,
    serve one request, report process-start -> first-token seconds plus
    the warmup resolution counts.  Run 1 populates the cache (every
    entry compiles); run 2 must resolve from disk — the elastic-serving
    story where a preempted replica is serving again in seconds.

    The children need the device, so the caller must not have
    initialised a jax backend yet (main() runs this leg first).
    """
    import subprocess
    import tempfile

    root = os.path.dirname(os.path.abspath(__file__))
    # The one deliberate fresh cache directory in the repo: an EMPTY
    # cache is the point of the cold leg, so both cache levels (the AOT
    # executable store and jax's own cache under <dir>/xla) go to a
    # temp dir, and a cache placed from outside is kept out of it.
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PT_BENCH_COLDSTART"] = "0"

    def run_once(d, tag):
        p = subprocess.run(
            [sys.executable, os.path.join(root, "bench.py"),
             "--coldstart-worker", d]
            + (["--cpu-counts"] if cpu_counts else []),
            capture_output=True, text=True, timeout=1200, env=env)
        if p.returncode == 2 and "no TPU" in p.stderr:
            # the chip path found no chip: the whole run fails, now
            sys.stderr.write(p.stderr[-400:])
            sys.exit(2)
        if p.returncode != 0:
            raise RuntimeError(
                f"coldstart {tag} worker rc={p.returncode}: "
                f"{p.stderr[-400:]}")
        line = [ln for ln in p.stdout.splitlines()
                if ln.strip().startswith("{")][-1]
        doc = json.loads(line)
        print(f"coldstart {tag}: ttft {doc['ttft_s']}s "
              f"(compile={doc['compiled']} disk={doc['disk']})",
              file=sys.stderr)
        return doc

    with tempfile.TemporaryDirectory() as d:
        cold = run_once(d, "cold")
        warm = run_once(d, "warm")
    return {
        "coldstart_ttft_cold_s": cold["ttft_s"],
        "coldstart_ttft_s": warm["ttft_s"],
        "speedup": (round(cold["ttft_s"] / warm["ttft_s"], 2)
                    if warm["ttft_s"] else None),
        "compile_cache_hit_rate": warm["hit_rate"],
        "platform": warm["platform"],
        "cold": cold, "warm": warm,
    }


def _coldstart_worker(cache_dir, cpu_counts=False):
    """Child side of the cold-start A/B: one fresh process, one warmed
    engine, one served request.  Prints a single JSON line; all timing
    is measured from process start (module import ``_T0``)."""
    import jax

    if not cpu_counts:
        _require_tpu(jax)

    import paddle_tpu as paddle
    from paddle_tpu.inference.server import ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.utils import enable_compile_cache

    enable_compile_cache(cache_dir=os.path.join(cache_dir, "xla"))
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    eng = ServingEngine(model, max_seqs=2, page_size=4, max_len=64,
                        prefill_chunk=8, aot="warm",
                        compile_cache=cache_dir)
    build_s = time.perf_counter() - _T0
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    while not any(r.generated for r in eng.scheduler.requests.values()):
        eng.step()
    ttft_s = time.perf_counter() - _T0
    rep = eng._aot_report
    if rep["failed"]:
        # an entry that failed to AOT-compile falls to a lazy compile
        # inside the timed window and skews the cold/warm TTFT
        raise RuntimeError(f"AOT warm-up failed for {rep['failed']}")
    print(json.dumps({
        "build_s": round(build_s, 3),
        "ttft_s": round(ttft_s, 3),
        "compiled": rep["compile"],
        "disk": rep["disk"],
        "entries": rep["entries"],
        "hit_rate": round(eng.compile_cache.hit_rate, 4),
        "platform": jax.devices()[0].platform,
    }), flush=True)


def _bench_detection(jax):
    """BASELINE config 4: detection train step under O2-equivalent
    mixed precision (bf16 compute weights+activations, fp32 master) —
    ResNet-18 backbone + anchor-free box/cls heads at 320px, the
    PP-YOLOE-style workload shape (dynamic shapes re-expressed
    statically per SURVEY §7; nms/roi_align are eval-side, tested in
    tests/test_detection_amp.py)."""
    import gc

    from paddle_tpu import nn
    from paddle_tpu.models.training import CompiledTrainStep
    from paddle_tpu.vision.models import resnet18

    gc.collect()

    class Detector(nn.Layer):
        def __init__(self, num_classes=80):
            super().__init__()
            self.backbone = resnet18(num_classes=0, with_pool=False)
            self.box = nn.Conv2D(512, 4, 1)
            self.cls = nn.Conv2D(512, num_classes, 1)

        def forward(self, x, box_t, cls_t):
            from paddle_tpu import ops

            f = self.backbone(x)
            l_box = ops.mean(ops.abs(self.box(f) - box_t))
            l_cls = nn.functional.binary_cross_entropy_with_logits(
                self.cls(f), cls_t)
            return l_box + l_cls

    model = Detector()
    model.train()
    step = CompiledTrainStep(model, lr=1e-3, compute_dtype="bfloat16")
    batch = int(os.environ.get("PT_BENCH_DET_BATCH", "64"))
    rng = np.random.RandomState(0)
    import jax.numpy as jnp

    imgs = jnp.asarray(rng.randn(batch, 3, 320, 320), jnp.bfloat16)
    box_t = rng.randn(batch, 4, 10, 10).astype(np.float32)
    cls_t = (rng.rand(batch, 80, 10, 10) > 0.95).astype(np.float32)
    print("detection: compiling...", file=sys.stderr)
    dt, loss = _guarded(
        lambda: _time_multi(step, (imgs, box_t, cls_t), 10, "detection"),
        None, "detection")
    imgs_s = batch / dt
    print(f"detection: step {dt * 1e3:.1f} ms, {imgs_s:.0f} imgs/s",
          file=sys.stderr)
    return {"value": round(imgs_s, 1), "unit": "imgs/s/chip",
            "batch": batch, "image": 320,
            "precision": "bf16 compute (O2-equivalent)"}


def _bench_unet(jax):
    """BASELINE config 5: SD v1.5 UNet train step — noise-prediction
    MSE over [B, 4, 32, 32] latents + [B, 77, 768] text context,
    bf16 compute, AdamW with bf16 moments (memory pressure is the
    point of this config)."""
    import gc

    from paddle_tpu import nn
    from paddle_tpu.models.training import CompiledTrainStep
    from paddle_tpu.models.unet import UNet2DConditionModel

    gc.collect()

    class UNetTrain(nn.Layer):
        def __init__(self):
            super().__init__()
            self.unet = UNet2DConditionModel()

        def forward(self, latents, t, ctx, noise):
            pred = self.unet(latents, t, ctx)
            return ((pred - noise) ** 2).mean()

    with jax.default_device(jax.devices("cpu")[0]):
        model = UNetTrain()
    n_params = model.unet.num_params()
    model.train()
    step = CompiledTrainStep(model, lr=1e-4, compute_dtype="bfloat16",
                             moments_dtype="bfloat16",
                             state_device=jax.devices()[0])
    for _, p in model.named_parameters():
        p._data = None
    gc.collect()
    batch = int(os.environ.get("PT_BENCH_UNET_BATCH", "4"))
    rng = np.random.RandomState(0)
    lat = rng.randn(batch, 4, 32, 32).astype(np.float32)
    t = rng.randint(0, 1000, (batch,)).astype(np.int32)
    ctx = rng.randn(batch, 77, 768).astype(np.float32)
    noise = rng.randn(batch, 4, 32, 32).astype(np.float32)
    print("unet: compiling (~810M params)...", file=sys.stderr)
    dt, loss = _guarded(
        lambda: _time_multi(step, (lat, t, ctx, noise), 5, "unet"),
        None, "unet")
    samples_s = batch / dt
    print(f"unet: step {dt * 1e3:.1f} ms, {samples_s:.1f} samples/s",
          file=sys.stderr)
    return {"value": round(samples_s, 2), "unit": "samples/s/chip",
            "batch": batch, "latent": [4, 32, 32],
            "model_params": n_params}


def _bench_bert(jax):
    """BASELINE config 2: BERT-base SQuAD fine-tune step (span QA loss,
    fwd+bwd+AdamW, bf16 compute).  DP on one chip = the plain step; the
    dp-sharded CompiledTrainStep covers multi-chip (tests/test_engine)."""
    import gc

    from paddle_tpu import nn
    from paddle_tpu.models.bert import BertConfig, BertForQuestionAnswering
    from paddle_tpu.models.training import CompiledTrainStep

    gc.collect()
    cfg = BertConfig.base()

    class QATrain(nn.Layer):
        def __init__(self):
            super().__init__()
            self.qa = BertForQuestionAnswering(cfg)

        def forward(self, ids, starts, ends):
            return self.qa(ids, start_positions=starts,
                           end_positions=ends)

    model = QATrain()
    model.train()
    # remat off: B=48 activations fit HBM once attention probs stay in
    # VMEM (short_attention kernel), and the refwd was ~25% of the step.
    step = CompiledTrainStep(model, lr=3e-5, compute_dtype="bfloat16",
                             remat=os.environ.get(
                                 "PT_BENCH_BERT_REMAT", "0") == "1")
    batch, seq = (int(os.environ.get("PT_BENCH_BERT_BATCH", "48")), 384)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    starts = rng.randint(0, seq, (batch,)).astype(np.int32)
    ends = rng.randint(0, seq, (batch,)).astype(np.int32)
    print("bert: compiling...", file=sys.stderr)
    flops_tok = model.qa.bert.flops_per_token(seq)
    dt, loss = _guarded(
        lambda: _time_multi(step, (ids, starts, ends), 5, "bert"),
        flops_tok * batch * seq, "bert")
    seqs_s = batch / dt
    tok_s = batch * seq / dt
    mfu = tok_s * flops_tok / _peak_flops_per_chip()
    print(f"bert: step {dt * 1e3:.1f} ms, {seqs_s:.1f} seq/s, "
          f"MFU {mfu:.3f}", file=sys.stderr)
    return {"value": round(seqs_s, 1), "unit": "sequences/s/chip",
            "batch": batch, "seq": seq, "mfu": round(mfu, 4),
            "model_params": model.qa.bert.num_params()}


def _bench_resnet(jax):
    """BASELINE config 1: ResNet-50 ImageNet train step (fwd+bwd+SGD
    momentum, bf16 compute), images/sec on the single chip."""
    import gc

    from paddle_tpu.models.training import CompiledTrainStep
    from paddle_tpu.nn import functional as F
    from paddle_tpu.vision.models import resnet50

    gc.collect()
    model = resnet50(num_classes=1000)
    model.train()
    step = CompiledTrainStep(model, lr=0.1, compute_dtype="bfloat16",
                             loss_fn=F.cross_entropy)
    import jax.numpy as jnp

    batch = int(os.environ.get("PT_BENCH_RESNET_BATCH", "256"))
    rng = np.random.RandomState(0)
    # bf16 images to match the bf16-cast conv weights (XLA convs require
    # matching operand dtypes; matmul-only models auto-promote).
    imgs = jnp.asarray(rng.randn(batch, 3, 224, 224), jnp.bfloat16)
    labels = rng.randint(0, 1000, (batch,)).astype(np.int32)
    print("resnet50: compiling...", file=sys.stderr)
    dt, loss = _guarded(
        lambda: _time_multi(step, (imgs, labels), 10, "resnet50"),
        batch * 3 * 4.1e9, "resnet50")
    imgs_s = batch / dt
    # ~4.1 GFLOP fwd per 224x224 image; train ~= 3x fwd.
    mfu = imgs_s * 3 * 4.1e9 / _peak_flops_per_chip()
    print(f"resnet50: step {dt * 1e3:.1f} ms, {imgs_s:.0f} imgs/s, "
          f"~MFU {mfu:.3f}", file=sys.stderr)
    out = {"value": round(imgs_s, 1), "unit": "imgs/s/chip",
           "batch": batch, "mfu_est": round(mfu, 4)}
    # Roofline attribution (VERDICT r4 #4): XLA's own cost analysis of
    # the compiled step — bytes accessed per step vs HBM peak names the
    # limiting resource in the artifact itself.
    try:
        lowered = step._step.lower(
            step.params, step._master, step._m, step._v,
            jnp.asarray(1.0, jnp.float32), 0.1, imgs,
            jnp.asarray(labels))
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        bytes_step = float(ca.get("bytes accessed", 0.0))
        from paddle_tpu.obs import perf

        hbm_peak = perf.peak_hbm_bytes_s()
        out["roofline"] = {
            "xla_bytes_accessed_gb": round(bytes_step / 1e9, 2),
            "achieved_hbm_gb_s": round(bytes_step / dt / 1e9, 1),
            "hbm_peak_gb_s": hbm_peak / 1e9,
            "hbm_utilization": round(bytes_step / dt / hbm_peak, 3),
        }
        print(f"resnet50 roofline: {bytes_step / 1e9:.1f} GB/step, "
              f"{bytes_step / dt / 1e9:.0f} GB/s achieved "
              f"({bytes_step / dt / hbm_peak:.0%} of HBM peak)",
              file=sys.stderr)
    except Exception as e:
        out["roofline"] = {"error": str(e)[:120]}
    return out



def _fetch(x):
    """Fence + read: block until the device has produced the value,
    then pull the scalar to the host.  jax returns before the device
    finishes, so every timed section below ends here."""
    import jax

    return float(jax.block_until_ready(getattr(x, "_data", x)))


def _time_steps(step_fn, args, steps, tag):
    """Shared timing harness: one compile step, one warm step, then
    ``steps`` dispatches ending in one fence.  Dispatch overhead is real
    per-step cost for this path and is reported as part of the step."""
    t0 = time.perf_counter()
    loss = step_fn(*args)
    lv = _fetch(loss)
    print(f"{tag}: first step {time.perf_counter() - t0:.1f}s, "
          f"loss {lv:.3f}", file=sys.stderr)
    _fetch(step_fn(*args))      # warm
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step_fn(*args)
    lv = _fetch(loss)
    return (time.perf_counter() - t0) / steps, lv


def _time_multi(step, args, steps, tag):
    """Timed via CompiledTrainStep.multi_step: ``steps`` optimizer steps
    per dispatched program (lax.scan), so the per-dispatch host cost
    doesn't tax short-step models.  One compile dispatch, one warm
    dispatch, then one timed dispatch ending in a fence."""
    t0 = time.perf_counter()
    loss = step.step(*args)
    lv = _fetch(loss)
    print(f"{tag}: first step {time.perf_counter() - t0:.1f}s, "
          f"loss {lv:.3f}", file=sys.stderr)
    t0 = time.perf_counter()
    _fetch(step.multi_step(steps, *args))
    print(f"{tag}: multi-step compile+run {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    _fetch(step.multi_step(steps, *args))   # warm
    t0 = time.perf_counter()
    lv = _fetch(step.multi_step(steps, *args))
    return (time.perf_counter() - t0) / steps, lv


# Conservative absolute floor: no real train step of any bench config
# dispatches + executes in under this on one chip.
_STEP_FLOOR_S = 1e-3


def _implausible(dt, flops_per_step=None):
    """Reject physically impossible measurements instead of recording
    them (VERDICT r4 weak #1: a 61.23 MFU made it into the artifact).
    Returns a reason string, or None if the measurement is sane."""
    if not (dt > 0):
        return f"non-positive step time {dt}"
    if dt < _STEP_FLOOR_S:
        return f"step time {dt * 1e3:.3f} ms below {_STEP_FLOOR_S * 1e3} ms floor"
    if flops_per_step is not None:
        mfu = flops_per_step / dt / _peak_flops_per_chip()
        if mfu > 1.0:
            return f"MFU {mfu:.2f} > 1 (exceeds peak FLOPs)"
    return None


def _guarded(time_fn, flops_per_step, tag):
    """Run a timing closure with the plausibility guard: re-measure once
    on an implausible result, and raise (→ {"error": ...} in the
    artifact) if it stays implausible."""
    dt, lv = time_fn()
    reason = _implausible(dt, flops_per_step)
    if reason is not None:
        print(f"{tag}: IMPLAUSIBLE ({reason}); re-measuring once",
              file=sys.stderr)
        dt, lv = time_fn()
        reason = _implausible(dt, flops_per_step)
        if reason is not None:
            raise RuntimeError(f"implausible measurement: {reason}")
    return dt, lv


def _bench_graph_lint(jax):
    """Graph-contract linter over the hot-program registry: rebuilds
    the tiny hot programs the way tools/lint_graph.py does and times a
    full lint sweep (jaxpr checks + HLO host-sync scan).  Violations in
    the artifact mean a hot program drifted from its contract on this
    backend."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint_graph", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools", "lint_graph.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    owners = mod.build_programs()
    from paddle_tpu import analysis

    t0 = time.perf_counter()
    report = analysis.lint_all(hlo=True)
    dt = time.perf_counter() - t0
    del owners
    return {"programs": len(report.linted),
            "violations": len(report.violations),
            "skipped": len(report.skipped),
            "lint_s": round(dt, 2)}


def _bench_obs_overhead(jax):
    """Telemetry tax A/B: identical tiny-llama train steps with the
    obs plane off vs on (wall clock, real producers — spans, counters,
    step-wall histogram).  The acceptance target for the unified
    telemetry layer is on/off <= 1.03; a larger ratio in the artifact
    means a producer left allocation or a clock read on the hot path.
    The on-leg also runs the health plane per step (SLO snapshot +
    burn windows + heartbeat) so the ratio covers the full r16 tax."""
    import gc

    import paddle_tpu as paddle
    from paddle_tpu import obs
    from paddle_tpu.models import (
        CompiledTrainStep, LlamaConfig, LlamaForCausalLM)
    from paddle_tpu.obs import health

    ids = np.random.RandomState(0).randint(
        0, 2048, (8, 128)).astype(np.int64)

    def _measure(mode):
        obs.configure(mode=mode)   # producers cache at construction
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                          intermediate_size=704, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256)
        step = CompiledTrainStep(LlamaForCausalLM(cfg), lr=1e-3)
        slo = (health.SLOEngine(health.default_train_slos(),
                                source="train")
               if obs.handle() is not None else None)
        step.step(ids, ids)        # compile + settle
        n = 30
        t0 = time.perf_counter()
        for i in range(n):
            step.step(ids, ids)
            if slo is not None:
                slo.evaluate(step=i)
                obs.beat("train")
        dt = (time.perf_counter() - t0) / n
        del step
        gc.collect()
        return dt

    try:
        off_s = _measure("off")
        on_s = _measure("on")
    finally:
        obs.reset()                # back to the PT_OBS env default
    return {"step_off_ms": round(off_s * 1e3, 3),
            "step_on_ms": round(on_s * 1e3, 3),
            "on_off_ratio": round(on_s / off_s, 4)}


def _bench_serving(jax):
    """Serving throughput (VERDICT r4 next-8): continuous-batching
    greedy decode over the paged-KV engine — the Predictor/serving
    stack's hot path (reference block_multi_head_attention loop).
    Reports decode tokens/s at full batch occupancy, measured A/B:
    the self-authored fused paged-decode kernel vs the dense jnp
    gather path (PT_PAGED_IMPL routing in inference/paged.py)."""
    import gc

    import jax.numpy as jnp

    from paddle_tpu.inference.serving import PagedLlamaEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.ops import autotune

    gc.collect()
    # head_dim must be 128: the paged-attention Pallas kernels require
    # last-dim 128 blocks.
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=2752, num_hidden_layers=8,
                      num_attention_heads=8, num_key_value_heads=8,
                      max_position_embeddings=512, dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    model.eval()
    n_params = model.num_params()
    max_seqs = int(os.environ.get("PT_BENCH_SERVE_SEQS", "8"))
    rng = np.random.RandomState(0)

    def _measure(impl):
        """Decode tokens/s with the given attention impl.  A fresh
        engine per impl: the routing is read at trace time, and each
        engine holds its own decode executable."""
        old = os.environ.get("PT_PAGED_IMPL")
        os.environ["PT_PAGED_IMPL"] = impl
        try:
            eng = PagedLlamaEngine(model, max_seqs=max_seqs,
                                   page_size=16, max_len=512,
                                   dtype=jnp.bfloat16)
            print(f"serving[{impl}]: prefill + compiling decode...",
                  file=sys.stderr)
            for _ in range(max_seqs):
                eng.add_request(
                    rng.randint(0, cfg.vocab_size, (128,)))
            # decode_n keeps the greedy feedback on device: one
            # dispatch per k tokens (serving.py _decode_n_fwd) — the
            # measured quantity is decode THROUGHPUT, not per-dispatch
            # host latency.
            k = 32
            eng.decode_n(k)  # compile + settle
            # decode_n ends in a host transfer of all k tokens, so each
            # call's wall time is honest serving cost (dispatch +
            # decode + fetch); average over several calls.
            calls = 4
            t0 = time.perf_counter()
            for _ in range(calls):
                eng.decode_n(k)
            wall = time.perf_counter() - t0
            # plausibility at DISPATCH granularity (the 1 ms floor is
            # calibrated for wall-clock dispatches, not derived
            # per-token quantities)
            reason = _implausible(wall / calls)
            if reason is not None:
                raise RuntimeError(
                    f"implausible measurement: {reason}")
            dt = wall / (calls * k)  # per token-step, fetch amortized
            tok_s = max_seqs / dt
            print(f"serving[{impl}]: decode {dt * 1e3:.2f} "
                  f"ms/token-step, {tok_s:.0f} tok/s (batch "
                  f"{max_seqs}, {k}-token dispatches)", file=sys.stderr)
            del eng
            gc.collect()
            return tok_s, dt, k
        finally:
            if old is None:
                os.environ.pop("PT_PAGED_IMPL", None)
            else:
                os.environ["PT_PAGED_IMPL"] = old

    tok_s, dt, k = _measure("pallas")
    out = {"value": round(tok_s, 1), "unit": "decode_tokens/s/chip",
           "batch": max_seqs, "prompt": 128, "page_size": 16,
           "dispatch_tokens": k, "model_params": n_params,
           "impl": "pallas (fused paged_decode)"}
    if os.environ.get("PT_BENCH_SERVE_AB", "1") == "1":
        try:
            dense_tok_s, dense_dt, _ = _measure("dense")
            out["ab_dense_tokens_s"] = round(dense_tok_s, 1)
            out["ab_speedup_vs_dense"] = round(dt and dense_dt / dt, 2)
            # persist the measured winner so auto routing replays it
            autotune.record("paged_decode_impl", (128, 16),
                            "pallas" if dt <= dense_dt else "dense")
        except Exception as e:  # A/B leg must never cost the headline
            out["ab_dense_tokens_s"] = {"error": str(e)[:120]}
    if os.environ.get("PT_BENCH_SERVE_SCHED", "1") == "1":
        try:
            out["scheduler"] = _measure_scheduler(model, cfg, max_seqs)
        except Exception as e:  # same guard as the A/B leg
            out["scheduler"] = {"error": str(e)[:120]}
    if os.environ.get("PT_BENCH_SERVE_PREFIX", "1") == "1":
        try:
            out["prefix_cache"] = _measure_prefix(model, cfg, max_seqs)
        except Exception as e:  # same guard as the A/B leg
            out["prefix_cache"] = {"error": str(e)[:120]}
    if os.environ.get("PT_BENCH_SERVE_SPEC", "1") == "1":
        try:
            out["spec"] = _measure_spec(model, cfg, max_seqs)
        except Exception as e:  # same guard as the A/B leg
            out["spec"] = {"error": str(e)[:120]}
    if os.environ.get("PT_BENCH_SERVE_ASYNC", "1") == "1":
        try:
            out["async_exec"] = _measure_async(model, cfg, max_seqs)
        except Exception as e:  # same guard as the A/B leg
            out["async_exec"] = {"error": str(e)[:120]}
    if os.environ.get("PT_BENCH_QUANT", "1") == "1":
        try:
            out["quant"] = _measure_quant(model, cfg, max_seqs)
        except Exception as e:  # same guard as the A/B leg
            out["quant"] = {"error": str(e)[:120]}
    return out


def _measure_scheduler(model, cfg, max_seqs):
    """Continuous-batching scheduler under seeded load (r10): the
    ServingEngine admits/preempts/streams a generate_load workload and
    the SLO metrics come straight out of engine.stats() — serving
    tok/s, TTFT/TPOT percentiles, batch occupancy."""
    import jax.numpy as jnp

    from paddle_tpu.inference.server import ServingEngine
    from paddle_tpu.testing.load import LoadSpec, generate_load, run_load

    n_req = int(os.environ.get("PT_BENCH_SERVE_REQS", "16"))
    eng = ServingEngine(model, max_seqs=max_seqs, page_size=16,
                        max_len=512, dtype=jnp.bfloat16,
                        prefill_chunk=128)
    work = generate_load(LoadSpec(
        n_requests=n_req, mean_interarrival=1.0, prompt_len=(64, 128),
        max_new=(16, 32), vocab=cfg.vocab_size, seed=0))
    print(f"serving[scheduler]: {n_req} seeded requests, batch "
          f"{max_seqs}...", file=sys.stderr)
    res = run_load(eng, work)
    st = res["stats"]
    done = st["requests"]["finished"] + st["requests"]["truncated"]
    if done != n_req:
        raise RuntimeError(f"load did not finish cleanly: "
                           f"{st['requests']}")
    print(f"serving[scheduler]: {st['throughput_tok_s']:.0f} tok/s, "
          f"ttft p50 {st['ttft_ms_p50']} ms, occupancy "
          f"{st['batch_occupancy']}", file=sys.stderr)
    return {
        "serving_tok_s": st["throughput_tok_s"],
        "ttft_ms_p50": st["ttft_ms_p50"],
        "ttft_ms_p99": st["ttft_ms_p99"],
        "tpot_ms_p50": st["tpot_ms_p50"],
        "tpot_ms_p99": st["tpot_ms_p99"],
        "batch_occupancy": st["batch_occupancy"],
        "page_utilization": st["page_utilization"],
        "preemptions": st["preemptions"],
        "requests": n_req,
        "steps": st["steps"],
    }


def _measure_prefix(model, cfg, max_seqs):
    """Shared-prefix KV cache A/B (r11): the SAME seeded workload at
    prefix_share >= 0.5 (half the requests extend a common system-
    prompt-style prefix) through a cached and an uncached engine.  The
    contract quantities: TTFT percentiles (warm prefill covers only
    the novel suffix), serving tok/s, and the measured hit rate —
    PERF.md's capacity-multiplication math starts from these."""
    import jax.numpy as jnp

    from paddle_tpu.inference.server import ServingEngine
    from paddle_tpu.testing.load import LoadSpec, generate_load, run_load

    n_req = int(os.environ.get("PT_BENCH_SERVE_REQS", "16"))
    share = float(os.environ.get("PT_BENCH_PREFIX_SHARE", "0.6"))
    work = generate_load(LoadSpec(
        n_requests=n_req, mean_interarrival=1.0, prompt_len=(32, 64),
        max_new=(16, 32), vocab=cfg.vocab_size, seed=0,
        prefix_share=share, prefix_len=96, prefix_pool=2))

    def leg(cached):
        eng = ServingEngine(model, max_seqs=max_seqs, page_size=16,
                            max_len=512, dtype=jnp.bfloat16,
                            prefill_chunk=128, prefix_cache=cached)
        label = "on" if cached else "off"
        print(f"serving[prefix {label}]: {n_req} seeded requests at "
              f"share {share}...", file=sys.stderr)
        st = run_load(eng, work)["stats"]
        done = st["requests"]["finished"] + st["requests"]["truncated"]
        if done != n_req:
            raise RuntimeError(f"prefix load did not finish cleanly: "
                               f"{st['requests']}")
        print(f"serving[prefix {label}]: "
              f"{st['throughput_tok_s']:.0f} tok/s, ttft p50 "
              f"{st['ttft_ms_p50']} ms, hit rate "
              f"{st['prefix_hit_rate']}", file=sys.stderr)
        return {
            "serving_tok_s": st["throughput_tok_s"],
            "ttft_ms_p50": st["ttft_ms_p50"],
            "ttft_ms_p99": st["ttft_ms_p99"],
            "prefix_hit_rate": st["prefix_hit_rate"],
            "cached_tokens": st["cached_tokens"],
            "prefill_tokens": st["prefill_tokens"],
            "evicted_pages": st["evicted_pages"],
        }

    on, off = leg(True), leg(False)
    return {
        "prefix_share": share,
        "requests": n_req,
        "on": on,
        "off": off,
        "ttft_p50_speedup": round(
            (off["ttft_ms_p50"] / on["ttft_ms_p50"])
            if on["ttft_ms_p50"] else 0.0, 2),
        "prefill_tokens_saved": off["prefill_tokens"]
        - on["prefill_tokens"],
    }


def _measure_spec(model, cfg, max_seqs):
    """Speculative-decode A/B (r12): the SAME seeded repetitive
    workload (repeat_share tiles prompts from a short period — the
    templated/structured traffic where prompt-lookup drafting pays
    off) through `PT_SPEC_DECODE=ngram` and the plain greedy engine.
    Exactness is a test contract (streams bit-identical,
    tests/test_spec_decode.py); this leg records the perf contract:
    decode steps, tokens per decode step, acceptance rate, tok/s."""
    import jax.numpy as jnp

    from paddle_tpu.inference.server import ServingEngine
    from paddle_tpu.testing.load import LoadSpec, generate_load, run_load

    n_req = int(os.environ.get("PT_BENCH_SERVE_REQS", "16"))
    share = float(os.environ.get("PT_BENCH_SPEC_SHARE", "0.75"))
    work = generate_load(LoadSpec(
        n_requests=n_req, mean_interarrival=1.0, prompt_len=(32, 64),
        max_new=(32, 64), vocab=cfg.vocab_size, seed=0,
        repeat_share=share, repeat_period=4))

    def leg(mode):
        eng = ServingEngine(model, max_seqs=max_seqs, page_size=16,
                            max_len=512, dtype=jnp.bfloat16,
                            prefill_chunk=128, spec_decode=mode)
        print(f"serving[spec {mode}]: {n_req} seeded requests at "
              f"repeat share {share}...", file=sys.stderr)
        st = run_load(eng, work)["stats"]
        done = st["requests"]["finished"] + st["requests"]["truncated"]
        if done != n_req:
            raise RuntimeError(f"spec load did not finish cleanly: "
                               f"{st['requests']}")
        print(f"serving[spec {mode}]: {st['throughput_tok_s']:.0f} "
              f"tok/s, {st['steps']} steps, "
              f"{st['tokens_per_decode_step']} tok/decode-step, "
              f"acceptance {st['draft_acceptance_rate']}",
              file=sys.stderr)
        return {
            "serving_tok_s": st["throughput_tok_s"],
            "steps": st["steps"],
            "decode_tokens": st["decode_tokens"],
            "tokens_per_decode_step": st["tokens_per_decode_step"],
            "draft_acceptance_rate": st["draft_acceptance_rate"],
            "tpot_ms_p50": st["tpot_ms_p50"],
            "tpot_ms_p99": st["tpot_ms_p99"],
            "tpot_steps_p50": st["tpot_steps_p50"],
            "tpot_steps_p99": st["tpot_steps_p99"],
        }

    ng, off = leg("ngram"), leg("off")
    return {
        "repeat_share": share,
        "requests": n_req,
        "ngram": ng,
        "off": off,
        "step_reduction": round(
            (off["steps"] / ng["steps"]) if ng["steps"] else 0.0, 2),
        "tok_s_speedup": round(
            (ng["serving_tok_s"] / off["serving_tok_s"])
            if off["serving_tok_s"] else 0.0, 2),
    }


def _measure_async(model, cfg, max_seqs):
    """Async double-buffered executor A/B (r17): the SAME seeded
    workload through `PT_ASYNC_EXEC=on` (plan N+1 on the host while
    step N runs on the device, commit at the fence) and the sync
    engine.  Exactness is a test contract (streams bit-identical,
    tests/test_async_exec.py); this leg records the perf contract:
    serving tok/s async-vs-sync, TTFT/TPOT percentiles per leg, and
    host_overlap_ratio — overlapped host seconds over device compute
    seconds, the quantity PERF.md's hiding math starts from (target
    >0.8 at batch occupancy)."""
    import jax.numpy as jnp

    from paddle_tpu.inference.server import ServingEngine
    from paddle_tpu.testing.load import LoadSpec, generate_load, run_load

    n_req = int(os.environ.get("PT_BENCH_SERVE_REQS", "16"))
    work = generate_load(LoadSpec(
        n_requests=n_req, mean_interarrival=1.0, prompt_len=(64, 128),
        max_new=(32, 64), vocab=cfg.vocab_size, seed=0))

    def leg(async_exec):
        eng = ServingEngine(model, max_seqs=max_seqs, page_size=16,
                            max_len=512, dtype=jnp.bfloat16,
                            prefill_chunk=128, async_exec=async_exec)
        label = "on" if async_exec else "off"
        print(f"serving[async {label}]: {n_req} seeded requests, "
              f"batch {max_seqs}...", file=sys.stderr)
        st = run_load(eng, work)["stats"]
        done = st["requests"]["finished"] + st["requests"]["truncated"]
        if done != n_req:
            raise RuntimeError(f"async load did not finish cleanly: "
                               f"{st['requests']}")
        row = {
            "serving_tok_s": st["throughput_tok_s"],
            "ttft_ms_p50": st["ttft_ms_p50"],
            "ttft_ms_p99": st["ttft_ms_p99"],
            "tpot_ms_p50": st["tpot_ms_p50"],
            "tpot_ms_p99": st["tpot_ms_p99"],
            "batch_occupancy": st["batch_occupancy"],
            "steps": st["steps"],
        }
        if async_exec:
            s = eng.scheduler
            row["host_overlap_ratio"] = round(s.host_overlap_ratio, 4)
            row["replans"] = s.replans
            row["phase_seconds_total"] = {
                k: round(v, 4) for k, v in s.phase_totals.items()}
        print(f"serving[async {label}]: "
              f"{st['throughput_tok_s']:.0f} tok/s, tpot p50 "
              f"{st['tpot_ms_p50']} ms"
              + (f", overlap {row['host_overlap_ratio']}"
                 if async_exec else ""), file=sys.stderr)
        return row

    on, off = leg(True), leg(False)
    return {
        "requests": n_req,
        "on": on,
        "off": off,
        "tok_s_speedup": round(
            (on["serving_tok_s"] / off["serving_tok_s"])
            if off["serving_tok_s"] else 0.0, 2),
    }


def _measure_quant(model, cfg, max_seqs):
    """Quantized serving A/B (r19): the SAME seeded workload through
    `PT_QUANT=int8` (per-channel int8 projection weights fused into the
    matmul kernels + per-page int8 KV pools) and the bf16 engine.
    PT_QUANT=none exactness is a test contract (tests/test_quant.py);
    this leg records the perf contract: serving tok/s per leg, the KV
    capacity multiplier at a FIXED pool byte budget (bytes/page bf16
    over bytes/page int8+scales — the ROADMAP target is >= 1.8x), and
    the int8 logit drift vs the bf16 forward (rel RMS on a seeded
    prompt batch — the accuracy side of the trade)."""
    import jax.numpy as jnp

    from paddle_tpu.inference.server import ServingEngine
    from paddle_tpu.ops import quant as quant_mod
    from paddle_tpu.testing.load import LoadSpec, generate_load, run_load

    n_req = int(os.environ.get("PT_BENCH_SERVE_REQS", "16"))
    work = generate_load(LoadSpec(
        n_requests=n_req, mean_interarrival=1.0, prompt_len=(64, 128),
        max_new=(16, 32), vocab=cfg.vocab_size, seed=0))

    engines = {}

    def leg(mode):
        eng = ServingEngine(model, max_seqs=max_seqs, page_size=16,
                            max_len=512, dtype=jnp.bfloat16,
                            prefill_chunk=128, quant=mode)
        engines[mode] = eng
        print(f"serving[quant {mode}]: {n_req} seeded requests, "
              f"batch {max_seqs}...", file=sys.stderr)
        st = run_load(eng, work)["stats"]
        done = st["requests"]["finished"] + st["requests"]["truncated"]
        if done != n_req:
            raise RuntimeError(f"quant load did not finish cleanly: "
                               f"{st['requests']}")
        print(f"serving[quant {mode}]: "
              f"{st['throughput_tok_s']:.0f} tok/s, tpot p50 "
              f"{st['tpot_ms_p50']} ms", file=sys.stderr)
        return {
            "serving_tok_s": st["throughput_tok_s"],
            "ttft_ms_p50": st["ttft_ms_p50"],
            "tpot_ms_p50": st["tpot_ms_p50"],
            "tpot_ms_p99": st["tpot_ms_p99"],
            "batch_occupancy": st["batch_occupancy"],
            "kv_pool_dtype": str(
                eng.executor.cache.k_pages.dtype),
        }

    bf16, int8 = leg("none"), leg("int8")
    # capacity multiplier at a FIXED pool byte budget: how many more
    # pages (= resident sequences at a given context) the int8 pool
    # holds per byte.  Scales are charged to the int8 side.
    bpp_bf16 = quant_mod.kv_pool_bytes_per_page(
        engines["none"].executor.cache)
    bpp_int8 = quant_mod.kv_pool_bytes_per_page(
        engines["int8"].executor.cache)
    occupancy_ratio = round(bpp_bf16 / bpp_int8, 3)
    # logit drift: the two executors' OWN prefill programs over one
    # seeded prompt — rel RMS over the full vocab row
    rng = np.random.RandomState(7)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 64)),
                      jnp.int32)
    drift = []
    for _ in range(2):
        rows = {}
        for mode in ("none", "int8"):
            ex = engines[mode].executor
            lg, _k, _v = ex._jit_prefill(ex.layers, ex.tops, ids)
            rows[mode] = np.asarray(lg, np.float64)
        num = float(np.sqrt(np.mean((rows["none"] - rows["int8"]) ** 2)))
        den = float(np.sqrt(np.mean(rows["none"] ** 2))) or 1.0
        drift.append(num / den)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 64)),
                          jnp.int32)
    drift_rel_rms = round(max(drift), 5)
    print(f"serving[quant]: occupancy x{occupancy_ratio} at fixed "
          f"pool bytes ({bpp_bf16} -> {bpp_int8} B/page), logit "
          f"drift {drift_rel_rms}", file=sys.stderr)
    return {
        "requests": n_req,
        "bf16": bf16,
        "int8": int8,
        "bytes_per_page_bf16": bpp_bf16,
        "bytes_per_page_int8": bpp_int8,
        "occupancy_ratio": occupancy_ratio,
        "logit_drift_rel_rms": drift_rel_rms,
        "tok_s_ratio": round(
            (int8["serving_tok_s"] / bf16["serving_tok_s"])
            if bf16["serving_tok_s"] else 0.0, 2),
    }


def _measure_cluster(model):
    """Multi-replica fleet A/B (r20): one Zipf-skewed shared-prefix
    workload through ServingCluster at N=1/2/4 replicas (affinity
    routing) plus a random-routing control at N=4.  All replicas are
    simulated on one host over the shared logical clock, so throughput
    is decode tokens per cluster STEP (the unit that scales with N),
    never wall seconds.  The prefix pool is sized to overflow one
    replica's page pool: random routing duplicates hot prefixes across
    replicas and thrashes, affinity keeps each hot prefix resident on
    one replica — that gap is what perf-check gates."""
    from paddle_tpu.inference.server import ServingCluster
    from paddle_tpu.testing.load import LoadSpec, generate_load, run_load

    n_req = int(os.environ.get("PT_BENCH_CLUSTER_REQS", "32"))
    spec = LoadSpec(n_requests=n_req, mean_interarrival=1.0,
                    prompt_len=(4, 8), max_new=(8, 16), vocab=256,
                    seed=5, prefix_share=0.75, prefix_len=32,
                    prefix_pool=8, zipf_s=1.3)
    work = generate_load(spec)
    kw = dict(max_seqs=2, page_size=4, max_len=64, prefill_chunk=8,
              prefix_cache=True)

    def leg(n, policy):
        cl = ServingCluster(model, n_replicas=n, cluster=True,
                            router_policy=policy, **kw)
        print(f"serving[cluster n={n} {policy}]: {n_req} seeded "
              f"requests...", file=sys.stderr)
        res = run_load(cl, work)
        st = cl.stats()
        done = st["requests"]["finished"] + st["requests"]["truncated"]
        if done != n_req:
            raise RuntimeError(f"cluster load did not finish cleanly: "
                               f"{st['requests']}")
        ttft = [res["handles"][w["rid"]].metrics()["ttft_steps"]
                for w in work]
        out = {
            "replicas": n,
            "policy": policy,
            "steps": st["steps"],
            "agg_tok_per_step": round(st["agg_tok_per_step"], 4),
            "ttft_steps_p99": float(np.percentile(ttft, 99)),
            "prefix_hit_rate": round(st["prefix_hit_rate"], 4),
            "affinity_hits": st["router"]["affinity_hits"],
        }
        print(f"serving[cluster n={n} {policy}]: "
              f"{out['agg_tok_per_step']} tok/step over "
              f"{out['steps']} steps, hit rate "
              f"{out['prefix_hit_rate']}", file=sys.stderr)
        return out

    n1 = leg(1, "affinity")
    n2 = leg(2, "affinity")
    n4 = leg(4, "affinity")
    rnd = leg(4, "random")
    scaling = round(n4["agg_tok_per_step"]
                    / max(n1["agg_tok_per_step"], 1e-9), 2)
    tok_ratio = round(n4["agg_tok_per_step"]
                      / max(rnd["agg_tok_per_step"], 1e-9), 2)
    hit_delta = round(n4["prefix_hit_rate"] - rnd["prefix_hit_rate"], 4)
    print(f"serving[cluster]: N=4 vs N=1 x{scaling}, affinity vs "
          f"random x{tok_ratio} tok/step, hit-rate delta "
          f"{hit_delta:+.3f}", file=sys.stderr)
    return {
        "requests": n_req,
        "n1": n1,
        "n2": n2,
        "n4": n4,
        "random_n4": rnd,
        # headline: logical-clock aggregate throughput of the N=4
        # affinity fleet (what the scaling/routing ratios hang off)
        "value": n4["agg_tok_per_step"],
        "unit": "tok/step",
        "scaling_n4_vs_n1": scaling,
        "affinity_tok_ratio": tok_ratio,
        "hit_rate_delta": hit_delta,
        "ttft_steps_p99_n4": n4["ttft_steps_p99"],
    }


def _measure_cluster_failover(model):
    """Fleet survivability A/B (r21): the same Zipf-skewed workload
    through an N=4 affinity fleet twice — once healthy, once with one
    replica operator-killed at the median arrival tick.  The kill leg
    exercises the whole survivability plane: in-flight requests fail
    over (recompute) to healthy replicas, the supervisor schedules the
    restart, the rebuilt replica rejoins and takes traffic again.

    TTFT on BOTH legs is measured on the shared cluster clock against
    workload arrival ticks (never per-engine submit steps: failover
    re-adds reset those, and a restarted replica's engine clock starts
    over), so the per-request tax is an honest apples-to-apples delta.
    """
    from paddle_tpu.inference.server import ServingCluster
    from paddle_tpu.testing.load import LoadSpec, generate_load

    n_req = int(os.environ.get("PT_BENCH_FAILOVER_REQS", "32"))
    spec = LoadSpec(n_requests=n_req, mean_interarrival=1.0,
                    prompt_len=(4, 8), max_new=(8, 16), vocab=256,
                    seed=5, prefix_share=0.75, prefix_len=32,
                    prefix_pool=8, zipf_s=1.3)
    work = generate_load(spec)
    arrival = {w["rid"]: w["arrival_tick"] for w in work}
    kill_tick = int(np.median([w["arrival_tick"] for w in work]))
    kw = dict(max_seqs=2, page_size=4, max_len=64, prefill_chunk=8,
              prefix_cache=True)

    def drive(kill):
        cl = ServingCluster(model, n_replicas=4, cluster=True,
                            router_policy="affinity", **kw)
        pending = sorted(work, key=lambda w: (w["arrival_tick"],
                                              w["rid"]))
        handles, ttft = {}, {}
        victim, failed_over, recovered_tick = None, [], None
        while pending or cl.in_flight:
            if cl.tick > 10000:
                raise RuntimeError("failover load did not drain")
            while pending and pending[0]["arrival_tick"] <= cl.tick:
                w = pending.pop(0)
                handles[w["rid"]] = cl.submit(
                    w["prompt_ids"],
                    max_new_tokens=w["max_new_tokens"],
                    priority=w["priority"], rid=w["rid"])
            if kill and victim is None and cl.tick >= kill_tick:
                victim = cl.replicas[1]
                failed_over = [
                    rid for rid, req in
                    victim.engine.scheduler.requests.items()
                    if not req.terminal]
                cl.fail(victim.name, reason="bench_kill")
            cl.step()
            for rid, h in handles.items():
                if rid not in ttft and h.tokens:
                    ttft[rid] = cl.tick - arrival[rid]
            if victim is not None and recovered_tick is None \
                    and victim.state == "active" and victim.restarts:
                recovered_tick = cl.tick
        st = cl.stats()
        # zero-loss check on the HANDLES, not engine counters: the
        # restart rebuilds the victim's engine, dropping its pre-kill
        # finished counts from the aggregate
        bad = [rid for rid, h in handles.items()
               if h.state.value not in ("finished", "truncated")]
        if len(handles) != n_req or bad:
            raise RuntimeError(f"failover load lost requests: {bad}")
        return dict(stats=st, ttft=ttft, failed_over=failed_over,
                    recovered_tick=recovered_tick)

    print(f"serving[failover]: healthy N=4 leg, {n_req} requests...",
          file=sys.stderr)
    healthy = drive(kill=False)
    print(f"serving[failover]: kill r1 at tick {kill_tick}...",
          file=sys.stderr)
    killed = drive(kill=True)

    h_tok = healthy["stats"]["agg_tok_per_step"]
    k_tok = killed["stats"]["agg_tok_per_step"]
    retention = round(k_tok / max(h_tok, 1e-9), 4)
    recovery = killed["recovered_tick"] - kill_tick
    taxes = [killed["ttft"][r] - healthy["ttft"][r]
             for r in killed["failed_over"]]
    tax_mean = round(float(np.mean(taxes)), 2) if taxes else 0.0
    tax_max = int(max(taxes)) if taxes else 0
    out = {
        "requests": n_req,
        "kill_tick": kill_tick,
        "failed_over": len(killed["failed_over"]),
        "failovers": killed["stats"]["failovers"],
        "recovery_steps": int(recovery),
        "failover_ttft_tax_mean": tax_mean,
        "failover_ttft_tax_max": tax_max,
        "healthy_tok_per_step": round(h_tok, 4),
        "killed_tok_per_step": round(k_tok, 4),
        # headline: throughput retained through the incident
        "value": retention,
        "unit": "ratio",
        "tok_per_step_retention": retention,
    }
    print(f"serving[failover]: {len(killed['failed_over'])} failed "
          f"over, recovery {recovery} steps, TTFT tax mean "
          f"{tax_mean} steps, retention x{retention}",
          file=sys.stderr)
    return out


def _measure_durability(model):
    """Durable-serving A/B (r22), three measured questions:

    1. What does the journal cost?  The same seeded workload through a
       2-replica fleet with the WAL off vs on — wall-clock tok/s ratio
       (scheduling is bit-identical on both legs, so tok/step cannot
       see the flush/fsync cost; only the wall clock can).
    2. What does whole-process recovery cost?  Abandon the fleet at
       the median arrival tick (the in-process stand-in for the
       SIGKILL the test suite drives for real), rebuild via
       ``ServingCluster.recover``, replay the client's full workload
       (at-least-once -> dedup), and count cluster steps to drain:
       the recovery-time objective in steps.
    3. What does salvage save?  A replica hung mid-load, salvage on
       vs off: TTFT tax vs the healthy leg (arrival-tick clock, like
       the failover bench) and the re-prefilled token count each mode
       pays — salvaged KV pages are tokens NOT re-prefilled.
    """
    import tempfile

    from paddle_tpu.inference.server import ServingCluster
    from paddle_tpu.testing import faults
    from paddle_tpu.testing.load import LoadSpec, generate_load

    n_req = int(os.environ.get("PT_BENCH_WAL_REQS", "16"))
    spec = LoadSpec(n_requests=n_req, mean_interarrival=1.0,
                    prompt_len=(4, 12), max_new=(8, 16), vocab=256,
                    seed=5)
    work = generate_load(spec)
    kw = dict(max_seqs=4, page_size=4, max_len=64, prefill_chunk=8)
    tmp = tempfile.mkdtemp(prefix="pt-bench-wal-")

    def drive(cl, load, stop_tick=None):
        arrival = {w["rid"]: w["arrival_tick"] for w in load}
        pending = sorted(load, key=lambda w: (w["arrival_tick"],
                                              w["rid"]))
        handles, ttft = {}, {}
        while pending or cl.in_flight:
            if stop_tick is not None and cl.tick >= stop_tick:
                break
            if cl.tick > 10000:
                raise RuntimeError("durability load did not drain")
            while pending and pending[0]["arrival_tick"] <= cl.tick:
                w = pending.pop(0)
                handles[w["rid"]] = cl.submit(
                    w["prompt_ids"],
                    max_new_tokens=w["max_new_tokens"],
                    priority=w["priority"], rid=w["rid"])
            cl.step()
            for rid, h in handles.items():
                if rid not in ttft and h.tokens:
                    ttft[rid] = cl.tick - arrival[rid]
        return handles, ttft

    # -- 1. the WAL's throughput tax (wall clock) -----------------------
    print(f"serving[durability]: WAL off/on A/B, {n_req} requests...",
          file=sys.stderr)
    # untimed warm-up drive: both timed legs must see hot jit caches,
    # or the first leg eats every compile and the ratio is fiction
    drive(ServingCluster(model, n_replicas=2, cluster=True, **kw),
          work)
    # two estimators, one gate:
    # - wal_tok_ratio (GATED) is measured within the WAL-on run:
    #   the journal accounts every second it spends in append/fsync
    #   (wal.write_s), so (leg - write_s) / leg is the throughput the
    #   leg would have had with a free journal — host drift between
    #   legs cannot fake or hide the tax;
    # - wal_wall_ratio_ab (informational) is the classic cross-leg
    #   wall-clock A/B, paired per interleaved rep and medianed — on
    #   a shared host its ±10% swamps the journal's real ~0.1% cost,
    #   which is exactly why it does not gate.
    reps = int(os.environ.get("PT_BENCH_WAL_REPS", "3"))
    legs, ab_ratios, on_fracs = {}, [], []
    for rep in range(reps):
        pair = {}
        for mode, wal in (("off", False),
                          ("on", os.path.join(tmp, f"wal-ab{rep}"))):
            cl = ServingCluster(model, n_replicas=2, cluster=True,
                                wal=wal, **kw)
            t0 = time.perf_counter()
            handles, _ = drive(cl, work)
            dt = time.perf_counter() - t0
            toks = sum(len(h.tokens) for h in handles.values())
            pair[mode] = dict(
                tok_per_s=toks / max(dt, 1e-9),
                streams={r: h.tokens for r, h in handles.items()},
                appended=(cl.wal.appended
                          if cl.wal is not None else 0))
            if cl.wal is not None:
                on_fracs.append(cl.wal.write_s / max(dt, 1e-9))
            best = legs.get(mode)
            if best is None or pair[mode]["tok_per_s"] > best["tok_per_s"]:
                legs[mode] = pair[mode]
        if pair["on"]["streams"] != pair["off"]["streams"]:
            raise RuntimeError("WAL-on streams diverged from WAL-off")
        ab_ratios.append(pair["on"]["tok_per_s"]
                         / max(pair["off"]["tok_per_s"], 1e-9))
    base = legs["off"]["streams"]
    wal_frac = float(np.median(on_fracs))
    ratio = round(1.0 - wal_frac, 4)
    ab_ratio = round(float(np.median(ab_ratios)), 4)

    # -- 2. crash at the median arrival tick, recover, drain ------------
    kill_tick = int(np.median([w["arrival_tick"] for w in work]))
    print(f"serving[durability]: crash at tick {kill_tick}, "
          f"recover...", file=sys.stderr)
    wal_dir = os.path.join(tmp, "wal-rto")
    cl = ServingCluster(model, n_replicas=2, cluster=True,
                        wal=wal_dir, **kw)
    drive(cl, work, stop_tick=kill_tick)
    del cl
    rcl = ServingCluster.recover(model, wal_dir, n_replicas=2,
                                 cluster=True, **kw)
    rhandles = {w["rid"]: rcl.submit(
        w["prompt_ids"], max_new_tokens=w["max_new_tokens"],
        priority=w["priority"], rid=w["rid"])
        for w in sorted(work, key=lambda w: (w["arrival_tick"],
                                             w["rid"]))}
    recovery_steps = 0
    while rcl.in_flight:
        if recovery_steps > 10000:
            raise RuntimeError("recovered fleet did not drain")
        rcl.step()
        recovery_steps += 1
    bad = [r for r, h in rhandles.items() if h.tokens != base[r]]
    if bad:
        raise RuntimeError(f"recovery lost/diverged streams: {bad}")

    # -- 3. hung-replica salvage vs recompute failover ------------------
    sspec = LoadSpec(n_requests=8, mean_interarrival=1.0,
                     prompt_len=(4, 14), max_new=(4, 8), vocab=256,
                     seed=3)
    swork = generate_load(sspec)
    hang = "replica.fail:before:7=hang"
    print("serving[durability]: hung-replica salvage vs recompute...",
          file=sys.stderr)

    def hang_leg(fault, **over):
        faults.reset(fault)
        cl = ServingCluster(model, n_replicas=2, cluster=True,
                            beat_timeout=2, **over, **kw)
        handles, ttft = drive(cl, swork)
        faults.reset()
        return cl, {r: h.tokens for r, h in handles.items()}, ttft

    _healthy, sbase, h_ttft = hang_leg("")
    salv, s_streams, s_ttft = hang_leg(hang)
    reco, r_streams, r_ttft = hang_leg(hang, salvage=False)
    if s_streams != sbase or r_streams != sbase:
        raise RuntimeError("hang legs diverged from fault-free run")
    if salv.salvages < 1 or reco.salvages != 0:
        raise RuntimeError(
            f"salvage legs miswired: {salv.salvages}/{reco.salvages}")
    s_tax = float(np.mean([s_ttft[r] - h_ttft[r] for r in h_ttft]))
    r_tax = float(np.mean([r_ttft[r] - h_ttft[r] for r in h_ttft]))

    out = {
        "requests": n_req,
        "wal_records": legs["on"]["appended"],
        "wal_tok_per_s_off": round(legs["off"]["tok_per_s"], 2),
        "wal_tok_per_s_on": round(legs["on"]["tok_per_s"], 2),
        "wal_write_frac": round(wal_frac, 6),
        "wal_wall_ratio_ab": ab_ratio,
        "kill_tick": kill_tick,
        "served_from_log": rcl.recovery["served_from_log"],
        "resubmitted": rcl.recovery["resubmitted"],
        "recovery_steps": int(recovery_steps),
        "salvages": salv.salvages,
        "salvaged_pages": salv.salvaged_pages,
        "salvage_ttft_tax_mean": round(s_tax, 2),
        "recompute_ttft_tax_mean": round(r_tax, 2),
        "salvage_reprefill_tokens":
            salv.stats()["prefill_tokens"],
        "recompute_reprefill_tokens":
            reco.stats()["prefill_tokens"],
        "salvage_reprefill_saved_tokens":
            reco.stats()["prefill_tokens"]
            - salv.stats()["prefill_tokens"],
        # headline: throughput retained with the journal on
        "value": ratio,
        "unit": "ratio",
        "wal_tok_ratio": ratio,
    }
    print(f"serving[durability]: WAL tax x{ratio} "
          f"(wall A/B x{ab_ratio}), recovery "
          f"{recovery_steps} steps ({out['served_from_log']} from "
          f"log, {out['resubmitted']} resubmitted), salvage saved "
          f"{out['salvage_reprefill_saved_tokens']} re-prefill "
          f"tokens", file=sys.stderr)
    return out


def _measure_sp_prefill():
    """Long-context sequence-parallel prefill A/B (r23).

    The question: how does time-to-first-token scale with prompt
    length when chunked prefill is sharded across a sequence-parallel
    mesh?  On one shared CPU host, wall clock cannot honestly show an
    n-way speedup (all "devices" share the same cores), so the gated
    number is the **per-device TTFT critical path in FLOPs**: every
    chunk of the prompt priced through the jaxpr cost model at its
    exact shapes — the dense ``serve.prefill_chunk`` body for sp=1,
    the per-rank ``serve.prefill_sp`` shard_map body for sp=2/4 (the
    cost walker prices shard_map bodies at per-shard shapes, i.e. the
    work ONE device must retire before the first token; the ring's
    ppermute hops move bytes, not FLOPs).  A least-squares slope of
    critical-path FLOPs vs prompt length per sp degree, gated on the
    stripe-balance claim slope(sp4)/slope(sp1) <= 0.45 (ideal 0.25
    compute + the replicated non-attention epilogue).  Wall TTFT is
    recorded informationally (host-noisy, like every CPU wall row).

    Runs as a fresh subprocess so the mesh gets forced host devices.
    """
    import subprocess

    root = os.path.dirname(os.path.abspath(__file__))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                         + " --xla_force_host_platform_device_count=8"),
           "PT_BENCH_SP_PREFILL": "0"}
    p = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py"), "--sp-worker"],
        capture_output=True, text=True, timeout=1800, env=env)
    if p.returncode != 0:
        raise RuntimeError(f"sp worker rc={p.returncode}: "
                           f"{p.stderr[-400:]}")
    doc = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.strip().startswith("{")][-1])
    print(f"serving[sp_prefill]: slope ratio sp2 "
          f"x{doc['slope_ratio_sp2']}, sp4 x{doc['slope_ratio_sp4']} "
          f"(gate <= 0.45), {doc['sp_prefill_tokens']} sp tokens, "
          f"{doc['gather_pages']} pages gathered", file=sys.stderr)
    return doc


def _sp_worker():
    """Child side of the sp-prefill leg: one fresh process with 8
    forced host devices.  Prints a single JSON line."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.analysis import estimate_fn_cost
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.inference.server import ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=256)
    model = LlamaForCausalLM(cfg)
    model.eval()
    kw = dict(max_seqs=2, page_size=4, max_len=256, prefill_chunk=32)
    C = kw["prefill_chunk"]
    lens = (64, 128, 192, 224)       # multiples of the chunk: every
    rng = np.random.RandomState(9)   # chunk rides the sp program
    prompts = {n: rng.randint(0, 256, (n,)).astype(np.int64)
               for n in lens}

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def critical_path_flops(ex, fn, L):
        """Per-device FLOPs retired before the first token: each chunk
        priced at its exact (chunk, past-cover) shapes."""
        sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            jnp.shape(a), a.dtype), (ex.layers, ex.tops))
        layers, tops = sds
        nl = ex.config.num_hidden_layers
        kv, d = ex.config.num_key_value_heads, ex.config.head_dim
        total = 0
        for start in range(0, L, C):
            past = jax.ShapeDtypeStruct((nl, kv, start, d),
                                        ex.cache.compute_dtype)
            total += estimate_fn_cost(
                fn, layers, tops, i32(1, C), i32(), past, past,
                i32()).flops
        return total

    def ttft_wall_s(eng, ids):
        t0 = time.perf_counter()
        h = eng.submit(ids, max_new_tokens=8)
        while not h.tokens:
            eng.step()
        dt = time.perf_counter() - t0
        while eng.in_flight:
            eng.step()
        return dt, h.tokens

    out = {"chunk": C, "prompt_lens": list(lens), "ttft_flops": {},
           "slope_flops_per_token": {}, "ttft_wall_s": {}}
    streams, slopes = {}, {}
    for n_sp in (1, 2, 4):
        if n_sp == 1:
            eng = ServingEngine(model, **kw)
            fn = eng.executor._chunk_fwd
        else:
            mesh = ProcessMesh(list(range(n_sp)), dim_names=["sp"])
            eng = ServingEngine(model, sp_mesh=mesh, sp_prefill=True,
                                sp_min_tokens=C, **kw)
            fn = eng.executor._sp_chunk_fwd
        key = f"sp{n_sp}"
        flops = [critical_path_flops(eng.executor, fn, L)
                 for L in lens]
        slopes[key] = float(np.polyfit(lens, flops, 1)[0])
        out["ttft_flops"][key] = flops
        out["slope_flops_per_token"][key] = round(slopes[key], 1)
        # untimed warm-up serve (compiles), then the timed one
        ttft_wall_s(eng, prompts[lens[0]])
        wall, toks = ttft_wall_s(eng, prompts[lens[-1]])
        out["ttft_wall_s"][key] = round(wall, 4)
        streams[key] = toks
        if n_sp == 4:
            out["sp_prefill_tokens"] = eng.executor.sp_prefill_tokens
            out["gather_pages"] = int(
                sum(-(-n // kw["page_size"]) for n in
                    (lens[0], lens[-1])))
    if not (streams["sp1"] == streams["sp2"] == streams["sp4"]):
        raise RuntimeError(f"sp streams diverged: {streams}")
    r2 = slopes["sp2"] / slopes["sp1"]
    r4 = slopes["sp4"] / slopes["sp1"]
    out["slope_ratio_sp2"] = round(r2, 4)
    out["slope_ratio_sp4"] = round(r4, 4)
    # the stripe-balance acceptance bound is absolute, not just
    # round-over-round: fail the leg outright if sharding stops paying
    if r4 > 0.45:
        raise RuntimeError(f"sp4/sp1 slope ratio {r4:.3f} > 0.45")
    out["value"] = out["slope_ratio_sp4"]
    out["unit"] = "ratio"
    print(json.dumps(out), flush=True)


def _bench_moe(jax):
    """Fused-MoE step A/B (ROADMAP: >=1.5x vs the jnp path at d_model
    2048 / 8 experts / top-2 on-chip).  One train-step body of the MoE
    block — gate, dispatch, both expert GEMMs, combine, fwd+bwd — run
    twice through PT_MOE_IMPL routing: 'fused' (sort dispatch +
    grouped-GEMM Pallas kernel) vs 'einsum' (GShard mask-matmul).
    Both legs share the single-device ep_moe_local body bench'd
    directly at the jax level (no mesh — the all-to-alls are identical
    between impls, so the A/B isolates dispatch + GEMM).  The grouped
    GEMM tile is tuned first and the winning impl is persisted so auto
    routing replays it (PERF.md round-7 methodology)."""
    import gc
    import math

    import jax.numpy as jnp

    from paddle_tpu.distributed.utils import moe_utils
    from paddle_tpu.ops import autotune
    from paddle_tpu.ops.pallas_kernels import grouped_gemm

    gc.collect()
    H, E, k = 2048, 8, 2
    F = int(os.environ.get("PT_BENCH_MOE_FFN", "5504"))
    T = int(os.environ.get("PT_BENCH_MOE_TOKENS", "8192"))
    C = max(1, int(math.ceil(T * 1.25 * k / E)))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randn(T, H), jnp.bfloat16)
    wg = jnp.asarray(rng.randn(H, E) * 0.02, jnp.float32)
    w1 = jnp.asarray(rng.randn(E, H, F) * 0.02, jnp.bfloat16)
    b1 = jnp.zeros([E, 1, F], jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(E, F, H) * 0.02, jnp.bfloat16)
    b2 = jnp.zeros([E, 1, H], jnp.bfloat16)
    args = (tokens, wg, w1, b1, w2, b2)

    # Tile-tune the grouped GEMM at this shape before the A/B so the
    # fused leg runs its best configuration (same contract as
    # fa_blocks/paged_decode: winner cached per device+shape).
    x_bkt = jnp.asarray(rng.randn(E, C, H), jnp.bfloat16)

    def _measure_tile(cand):
        autotune.record("grouped_gemm_blocks", (H, F), cand)

        def thunk():
            return grouped_gemm.grouped_ffn(x_bkt, w1, b1, w2, b2,
                                            activation="gelu",
                                            impl="pallas")
        return autotune.measure_thunk(thunk, iters=4)

    prior = autotune.lookup("grouped_gemm_blocks", (H, F), None)
    if prior is None:
        cands = [(128, 256), (256, 256), (128, 512), (512, 256)]
        best = None
        best_t = float("inf")
        for cand in cands:
            try:
                t = _measure_tile(cand)
            except Exception as e:
                print(f"moe: tile {cand} failed: {e}", file=sys.stderr)
                continue
            print(f"moe: tile {cand}: {t * 1e3:.2f} ms", file=sys.stderr)
            if t < best_t:
                best, best_t = cand, t
        if best is not None:
            autotune.record("grouped_gemm_blocks", (H, F), best)
            prior = best

    def _step(impl):
        def loss_fn(tokens, wg, w1, b1, w2, b2):
            out, aux = moe_utils.ep_moe_local(
                tokens, wg, w1, b1, w2, b2, axis_name=None, n=1,
                num_experts=E, top_k=k, capacity=C, activation="gelu",
                gate_kind="gshard", impl=impl)
            return jnp.sum(out.astype(jnp.float32) ** 2) / T + aux
        g = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 2, 3, 4, 5)))

        def thunk():
            return g(*args)
        return thunk

    print("moe[fused]: compiling...", file=sys.stderr)
    fused_dt = autotune.measure_thunk(_step("fused"), iters=4)
    reason = _implausible(fused_dt)
    if reason is not None:
        raise RuntimeError(f"implausible measurement: {reason}")
    tok_s = T / fused_dt
    print(f"moe[fused]: step {fused_dt * 1e3:.2f} ms, "
          f"{tok_s:.0f} tok/s", file=sys.stderr)
    out = {"value": round(tok_s, 1), "unit": "moe_tokens/s/chip",
           "metric": "moe_block_fwdbwd_tokens_per_sec",
           "d_model": H, "experts": E, "top_k": k, "ffn": F,
           "tokens": T, "capacity": C, "dtype": "bfloat16",
           "gemm_blocks": list(prior) if prior else None,
           "impl": "fused (sort dispatch + grouped GEMM)"}
    if os.environ.get("PT_BENCH_MOE_AB", "1") == "1":
        try:
            einsum_dt = autotune.measure_thunk(_step("einsum"), iters=4)
            out["ab_einsum_tokens_s"] = round(T / einsum_dt, 1)
            out["ab_speedup_vs_einsum"] = round(einsum_dt / fused_dt, 2)
            print(f"moe[einsum]: step {einsum_dt * 1e3:.2f} ms "
                  f"(fused speedup {einsum_dt / fused_dt:.2f}x)",
                  file=sys.stderr)
            # persist the measured winner so auto routing replays it
            autotune.record("moe_impl", (H, E, k),
                            "fused" if fused_dt <= einsum_dt
                            else "einsum")
        except Exception as e:  # A/B leg must never cost the headline
            out["ab_einsum_tokens_s"] = {"error": str(e)[:120]}
    return out


def _bench_large(jax):
    """Second size point (VERDICT r3 #2): a ~1.6B-param Llama on the one
    16G chip — single-copy bf16 AdamW with stochastic rounding (8
    bytes/param of state; see models/training.py master_dtype) + full
    remat + scan + flash attention + fused CE head.  The 7B recipe for a
    v5p pod is documented in PERF.md."""
    import gc

    from paddle_tpu.models import (
        CompiledTrainStep, LlamaConfig, LlamaForCausalLM,
    )

    cfg = LlamaConfig(vocab_size=32000, hidden_size=2560,
                      intermediate_size=6880, num_hidden_layers=17,
                      num_attention_heads=20, num_key_value_heads=20,
                      max_position_embeddings=2048, recompute=True,
                      scan_layers=True, attention_impl="flash")
    batch, seq, steps = 4, 2048, 5
    # Build on host (fp32 init would not fit HBM next to the bf16 state),
    # then move only the bf16 training state to the chip.
    with jax.default_device(jax.devices("cpu")[0]):
        model = LlamaForCausalLM(cfg)
    n_params = model.num_params()
    flops_tok = model.flops_per_token(seq)
    step = CompiledTrainStep(model, lr=1e-4, compute_dtype="bfloat16",
                             moments_dtype="bfloat16",
                             master_dtype="bfloat16_sr",
                             state_device=jax.devices()[0])
    # The eager host init copies are dead once the step holds its state.
    for _, p in model.named_parameters():
        p._data = None
    gc.collect()

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    print("large: compiling (~1.6B params)...", file=sys.stderr)
    dt, loss = _guarded(
        lambda: _time_steps(step.step, (ids, ids), steps, "large"),
        flops_tok * batch * seq, "large")

    # The large config trains on exactly ONE chip (state_device above);
    # other local chips idle, so per-chip throughput divides by 1.
    tok_s_chip = batch * seq / dt
    mfu = tok_s_chip * flops_tok / _peak_flops_per_chip()
    print(f"large: step {dt * 1e3:.1f} ms, loss {float(loss):.3f}, "
          f"tokens/s/chip {tok_s_chip:.0f}, MFU {mfu:.3f}",
          file=sys.stderr)
    return {"model_params": n_params,
            "value": round(tok_s_chip, 1), "mfu": round(mfu, 4),
            "batch": batch, "seq": seq,
            "optimizer": "adamw bf16 single-copy + stochastic rounding",
            "config": {"hidden": cfg.hidden_size,
                       "layers": cfg.num_hidden_layers,
                       "heads": cfg.num_attention_heads,
                       "vocab": cfg.vocab_size}}


def _perf_md_section(n, parsed):
    """Markdown block appended to PERF.md for one recorded round."""
    lines = [f"\n## Round-{n} bench artifact (auto-recorded)\n"]
    if parsed is None:
        lines.append("Run FAILED — see `BENCH_r%02d.json` tail.\n" % n)
        return "\n".join(lines)
    lines.append("| metric | value |")
    lines.append("|---|---|")

    def _row(key, val):
        lines.append(f"| {key} | {val} |")

    for key in ("metric", "value", "unit", "mfu", "vs_baseline"):
        if key in parsed:
            _row(key, parsed[key])
    for key, sub in sorted(parsed.items()):
        if isinstance(sub, dict) and "value" in sub:
            _row(f"{key}.value", sub["value"])
        elif isinstance(sub, dict) and ("skipped" in sub
                                        or "error" in sub):
            _row(key, sub.get("skipped") or "ERROR")
    lines.append("")
    lines.append(f"Full payload: `BENCH_r{n:02d}.json` "
                 f"(schema at the top of this file).")
    return "\n".join(lines) + "\n"


def _write_round(n, parsed, rc=0, tail="", root=None):
    """Record one bench round: write ``BENCH_rNN.json`` in the driver
    wrapper schema ({n, cmd, rc, tail, parsed}) and append the round's
    summary section to PERF.md.  Both used to be manual — which is how
    the trajectory went stale after r05."""
    if root is None:
        root = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(root, f"BENCH_r{n:02d}.json")
    doc = {"n": int(n), "cmd": f"python bench.py --round {n}",
           "rc": int(rc), "tail": tail[-2000:], "parsed": parsed}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    with open(os.path.join(root, "PERF.md"), "a") as f:
        f.write(_perf_md_section(n, parsed))
    print(f"wrote {path} + PERF.md section", file=sys.stderr)
    return path


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--round", type=int, default=None, metavar="N",
                    help="record this run as BENCH_rNN.json and append "
                         "the PERF.md section (the first-BENCH-run-"
                         "after-any-PR rule in README)")
    ap.add_argument("--cpu-counts", action="store_true",
                    help="the explicit CPU lane: tiny config + the "
                         "logical-clock serving legs, every line "
                         "\"platform\": \"cpu\" (needs JAX_PLATFORMS=cpu); "
                         "without it a run that finds no TPU fails")
    ap.add_argument("--coldstart-worker", default=None, metavar="DIR",
                    help=argparse.SUPPRESS)  # child of _bench_coldstart
    ap.add_argument("--sp-worker", action="store_true",
                    help=argparse.SUPPRESS)  # child of _measure_sp_prefill
    args = ap.parse_args()
    if args.coldstart_worker is not None:
        _coldstart_worker(args.coldstart_worker, args.cpu_counts)
        sys.exit(0)
    if args.sp_worker:
        _sp_worker()
        sys.exit(0)
    import traceback

    rc, parsed, tail = 0, None, ""
    try:
        parsed = main(cpu_counts=args.cpu_counts)
        tail = json.dumps(parsed)
        rc = 1 if parsed.get("failed_legs") else 0
    except Exception:
        rc, tail = 1, traceback.format_exc()
        traceback.print_exc()
    if args.round is not None:
        _write_round(args.round, parsed, rc=rc, tail=tail)
    sys.exit(rc)
