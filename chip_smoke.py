#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once through the entry points a user calls,
at Llama-2-7B widths (hidden 4096, 32 heads x 128, FFN 11008, vocab
32000) cut only in depth, weights random from a seed:

  train      LlamaForCausalLM -> CompiledTrainStep, bf16 compute, fp32
             master, bf16 moments, seq 2048, a few steps on one batch
  four_chip  the same train step on a 2x2 dp x mp mesh (>= 4 devices),
             and the other Pallas attention kernels per shard under it
  kernels    every Pallas kernel compiled by Mosaic (never interpreted)
             at a production shape against its plain reference
  serve      ServingEngine (every gate at its default), bf16, requests
             of different lengths through submit()/step()/stream(), and
             prefill-then-decode logits against a float32 forward

    python3 chip_smoke.py                  # the smoke; needs a TPU
    python3 chip_smoke.py --rehearse-cpu   # tiny sizes on the CPU, to
                                           # debug this file; not a pass

One process, which touches jax once and starts no other.  It exits
non-zero, printing no result, when jax finds no TPU; a leg that fails
raises, so the reason is on the last lines and the exit code is
non-zero.  On success stdout holds two lines: the full report as one
JSON object (versions, compile cache, per leg compile and steady
seconds, kernel sites; it ends with ``"claim": null`` and is also
written to chiprun_out/chip_smoke.json), and then, as the LAST line,
the verdict with exactly these keys and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
— the device as jax reports it.  The report carries no MFU and no rate
under a benchmark metric's name: that is the benchmark's job.
"""
import argparse
import gc
import importlib
import json
import os
import sys
import time

import numpy as np

# four_chip right after the leg it compares with: on a four-chip host,
# which is charged four times over, its failure shows first
LEGS = ("train", "four_chip", "kernels", "serve")

# -- sizes ---------------------------------------------------------------
# Depth of the train leg: the largest N one 16 GB v5e holds when the
# step is built the plain way (bench.py's way: the Layer's eager fp32
# parameters stay alive next to the step's own state).  Per parameter:
# 4 B eager + 2 B bf16 compute + 4 B fp32 master + 2+2 B bf16 moments
# = 14 B held, 16 B at the peak of construction; 262 M parameters of
# embedding + head and 202 M per layer.  N=3 (869 M) measured on the
# v5e: 12.2 GB in use, 13.9 GB peak of the 16.9 GB the device reports.
# N=4 (1,072 M) needs 17.1 GB to build and is refused (PERF.md
# "Bring-up on v5e").
TRAIN_DEPTH = 3

FULL = dict(
    width=None,     # LlamaConfig.llama2_7b: hidden 4096, 32 heads x 128,
                    # FFN 11008, vocab 32000
    train_depth=TRAIN_DEPTH, train_batch=2, train_seq=2048,
    train_steps=5,
    serve_depth=4, max_seqs=8, page_size=16, max_len=2048,
    prefill_chunk=256, new_tokens=32,
    # tens to ~1,500 tokens: two single-chunk prompts, four that take
    # 2-6 chunks; the longest is submitted first so its prefill chunks
    # interleave with the others' decode steps
    prompt_lens=(1480, 24, 456, 200, 1224, 712), probe_len=328,
)
# --rehearse-cpu: same code, toy sizes, kernels in the interpreter
TINY = dict(
    width=dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=4),
    train_depth=2, train_batch=2, train_seq=32, train_steps=4,
    serve_depth=2, max_seqs=4, page_size=4, max_len=64,
    prefill_chunk=8, new_tokens=6,
    prompt_lens=(37, 3, 18, 7, 29, 12), probe_len=21,
)

# -- tolerances, each with its reason ------------------------------------
# Kernels: max |kernel - reference| over max |reference|.  The
# reference is float32 at matmul precision "highest"; the compiled
# kernels feed the MXU bf16 passes (Mosaic's default for an f32 dot)
# and round outputs to the I/O dtype, so agreement is a few bf16 ulps
# (2^-8 = 3.9e-3) of the output's scale, not float32 ulps.  Measured on
# the v5e: 4.5e-3 at worst (stock flash dq); the bound is ~3x that.
KERNEL_TOL = 1.5e-2
# Serving logits vs the float32 forward of the SAME (bf16-valued)
# weights: what differs is bf16 activations, a bf16 KV pool and
# single-pass bf16 MXU products through `serve_depth` layers and a
# 4096-wide head.  As max |delta| over the RMS of the reference logits,
# measured 0.029 on the v5e (0.0139 / 0.476; seeded, so it repeats);
# the bound is ~2x that.
LOGIT_TOL = 6e-2
# Four-chip first-step loss vs one chip: same weights, same batch, and
# per-shard attention is the same arithmetic; only the row-parallel
# contractions are split in two and all-reduced.  Measured 7.3e-7 on
# four v5e chips; the bound leaves ~100x for a different reduction
# order and still catches a mis-sharded operand (which moves the loss
# in the first or second digit).
FOUR_CHIP_LOSS_RTOL = 1e-4


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"[chip_smoke +{time.perf_counter() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


class CompileClock:
    """Sums jax's own backend-compile events, so a leg can report
    compile seconds apart from the rest, and cache hits apart from
    compiles.  (A cache hit still fires the event: it then times the
    load that stood in for the compile.)"""

    def __init__(self, jax):
        self.compile_s, self.compiles = 0.0, 0
        self.cache_hits = self.cache_misses = 0
        mon = jax.monitoring
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.compile_s, self.compiles, self.cache_hits,
                self.cache_misses)

    def since(self, snap, wall_s):
        c, n, h, m = (a - b for a, b in zip(self.snapshot(), snap))
        return {"wall_s": round(wall_s, 2), "compile_s": round(c, 2),
                "programs_compiled": n, "cache_hits": h,
                "cache_misses": m}


def llama_config(ctx, **kw):
    """The published widths (or the rehearsal's toy ones), cut in depth."""
    from paddle_tpu.models import LlamaConfig

    width = ctx["sizes"]["width"]
    if width is None:
        return LlamaConfig.llama2_7b(**kw)
    return LlamaConfig(**width, **kw)


def verdict(report):
    """The last line of stdout: "ok" and "device" (platform, kind,
    count), exactly — whoever checks the smoke reads this line and
    refuses any other key.  Everything else is in the report."""
    dev = report["device"]
    return {"ok": bool(report["ok"]),
            "device": {"platform": str(dev["platform"]),
                       "kind": str(dev["kind"]),
                       "count": int(dev["count"])}}


def save(report):
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)


def memory(jax, dev=None):
    s = (dev or jax.devices()[0]).memory_stats() or {}
    return {"bytes_in_use": s.get("bytes_in_use"),
            "peak_bytes_in_use": s.get("peak_bytes_in_use"),
            "bytes_limit": s.get("bytes_limit")}


def kernel_sites(jax, fn, *args):
    """Which Pallas kernels a function's program holds, and whether
    each is compiled or interpreted — read off the jaxpr, the repo's
    own way (analysis.walker)."""
    from paddle_tpu.analysis import walker

    with jax.enable_x64(False):
        jaxpr = jax.make_jaxpr(fn)(*args)
    return [{"kernel": os.path.relpath(k) if k.startswith("/") else k,
             "compiled": not interp}
            for k, interp in sorted(set(walker.pallas_kernels(jaxpr)))]


def rel_err(got, want):
    """max |got - want| over max |want|; shape and finiteness checked."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    check(got.shape == want.shape, f"{got.shape} vs {want.shape}")
    check(np.isfinite(got).all(), "non-finite kernel output")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def short(site):
    """'_fwd_kernel at paddle_tpu/ops/.../long_attention.py:52' ->
    'long_attention._fwd_kernel'"""
    fn, _, where = site["kernel"].partition(" at ")
    mod = os.path.basename(where.split(":")[0]).removesuffix(".py")
    return f"{mod}.{fn}"


# -- kernels leg ---------------------------------------------------------

def leg_kernels(ctx):
    """Each Pallas kernel once at a production shape, compiled, against
    its plain reference.  Every kernel is tried (so one run names every
    kernel Mosaic refuses); the leg fails if any did."""
    jax, jnp, full = ctx["jax"], ctx["jnp"], ctx["full"]
    from paddle_tpu.inference import paged
    from paddle_tpu.ops import nn_ops, quant
    from paddle_tpu.ops.pallas_kernels import (
        grouped_gemm, long_attention, paged_decode, rms_norm)

    # the package re-exports the short_attention FUNCTION under the
    # module's name; the module itself is wanted here
    short_attention = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.short_attention")

    key = jax.random.PRNGKey(0)

    def rnd(i, shape, dtype, scale=0.3):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale).astype(dtype)

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    def ref_attn(q, k, v, causal):          # [B, H, S, D] in, f32 out
        qs, ks, vs = (jnp.swapaxes(t, 1, 2) for t in f32(q, k, v))
        out = nn_ops._sdpa_plain(qs, ks, vs, causal=causal,
                                 impl="einsum")
        return jnp.swapaxes(out, 1, 2)

    err = rel_err
    results = []

    def kernel(name, shape, tpu_only=False):
        def deco(body):
            if tpu_only and not ctx["on_tpu"]:
                return log(f"kernel {name}: has no interpreter path; "
                           f"not rehearsed")
            t0 = time.perf_counter()
            row = {"kernel": name, "shape": shape}
            try:
                sites, errs = body()
                row["sites"] = [short(s) for s in sites]
                check(sites, "no pallas_call in the traced program")
                check(all(s["compiled"] for s in sites) or not
                      ctx["on_tpu"], f"interpreted on the chip: {sites}")
                row["max_rel_err"] = {k: float(f"{v:.3g}")
                                      for k, v in errs.items()}
                bad = {k: v for k, v in errs.items() if v > KERNEL_TOL}
                check(not bad, f"outside tolerance {KERNEL_TOL}: {bad}")
                row["ok"] = True
            except Exception as e:      # collected; the LEG still fails
                row["ok"] = False
                row["error"] = f"{type(e).__name__}: {e}"[-1500:]
            row["seconds"] = round(time.perf_counter() - t0, 1)
            log(f"kernel {name}: "
                + ("ok " + json.dumps(row.get("max_rel_err"))
                   if row["ok"] else "FAILED " + row["error"][-300:]))
            results.append(row)
        return deco

    # Under the `highest` context only references are traced; kernels
    # carry their own precision.
    hi = jax.default_matmul_precision("highest")

    def fwd_bwd(run, ref, names, *xs, cot=None):
        """Output and input gradients of ``run`` (the kernel: jitted,
        x64 off) against ``ref`` (float32, precision "highest")."""
        def loss(fn):
            return lambda *a: jnp.sum(
                fn(*a).astype(jnp.float32)
                * (1.0 if cot is None else cot.astype(jnp.float32)))

        argnums = tuple(range(len(xs)))
        grad = jax.grad(loss(run), argnums=argnums)
        sites = kernel_sites(jax, lambda *a: (run(*a), grad(*a)), *xs)
        with jax.enable_x64(False):
            out, grads = jax.jit(run)(*xs), jax.jit(grad)(*xs)
        with hi:
            want = ref(*f32(*xs))
            want_grads = jax.grad(loss(ref), argnums=argnums)(*f32(*xs))
        errs = {"out": err(out, want)}
        errs.update({f"d{n}": err(g, w)
                     for n, g, w in zip(names, grads, want_grads)})
        return sites, errs

    S, B, H = (2048, 1, 4) if full else (256, 1, 2)

    @kernel("long_attention fwd+bwd", f"B{B} H{H} S{S} D128 bf16 causal")
    def _():
        q, k, v, g = (rnd(i, (B, H, S, 128), jnp.bfloat16)
                      for i in range(4))
        return fwd_bwd(
            lambda q, k, v: long_attention.long_attention(
                q, k, v, None, 256, True, None),
            lambda q, k, v: ref_attn(q, k, v, True), "qkv", q, k, v, cot=g)

    Ss = 512 if full else 128

    @kernel("short_attention fwd+bwd, no dropout",
            f"B2 H4 S{Ss} D128 bf16")
    def _():
        q, k, v = (rnd(10 + i, (2, 4, Ss, 128), jnp.bfloat16)
                   for i in range(3))
        return fwd_bwd(
            lambda q, k, v: short_attention.short_attention(
                q, k, v, 0, None, 0.0, False),
            lambda q, k, v: ref_attn(q, k, v, False), "qkv", q, k, v)

    @kernel("short_attention in-kernel dropout", "B1 H1 S128 D128 f32")
    def _():
        # v = I makes the output the dropped probability matrix Pd, and
        # g = I makes the backward's dV its transpose: identical zero
        # patterns prove the backward regenerates the forward's mask
        # (tests/test_short_attention.py's probe).
        n, p_drop = 128, 0.3
        q, k = (rnd(20 + i, (1, 1, n, n), jnp.float32) for i in range(2))
        eye = jnp.eye(n, dtype=jnp.float32)[None, None]
        seed = short_attention._seed_arr(13)
        args = (q, k, eye, seed, 0.125, p_drop, False)
        sites = kernel_sites(
            jax, lambda q, k: short_attention._fwd_call_impl(
                q, k, eye, seed, 0.125, p_drop, False), q, k)
        with jax.enable_x64(False):
            out, lse = short_attention._fwd_call_impl(*args)
            out2, _ = short_attention._fwd_call_impl(*args)
            other, _ = short_attention._fwd_call_impl(
                q, k, eye, short_attention._seed_arr(14), 0.125, p_drop,
                False)
            clean, _ = short_attention._fwd_call_impl(
                q, k, eye, seed, 0.125, 0.0, False)
            _, _, dv = short_attention._bwd_call(
                q, k, eye, lse, eye, seed, 0.125, p_drop, False)
        pd_f, pd_b = np.asarray(out[0, 0]), np.asarray(dv[0, 0]).T
        check(bool(jnp.all(out == out2)), "same seed, different mask")
        check(not bool(jnp.all(out == other)), "seed does not matter")
        check(((pd_f == 0) == (pd_b == 0)).all(),
              "backward mask differs from forward mask")
        frac = float((pd_f == 0).mean())
        check(abs(frac - p_drop) < 0.05, f"dropped {frac}, asked {p_drop}")
        with hi:
            p_ref = jax.nn.softmax(
                jnp.einsum("bhsd,bhtd->bhst", q, k) * 0.125, -1)
        kept = pd_f != 0
        return sites, {
            "probs_no_dropout": err(clean, p_ref),
            "kept_probs": err(pd_f[kept] * (1 - p_drop),
                              np.asarray(p_ref[0, 0])[kept])}

    @kernel("stock flash wrapper (impl=flash) fwd+bwd",
            "B1 S1024 H4 D128 bf16 causal", tpu_only=True)
    def _():
        # [B, S, H, D] layout: this is nn_ops._sdpa_plain's own entry,
        # i.e. the private _flash_attention_impl/_bwd_dkv/_bwd_dq calls
        q, k, v = (rnd(30 + i, (1, 1024, 4, 128), jnp.bfloat16)
                   for i in range(3))
        return fwd_bwd(
            lambda q, k, v: nn_ops._sdpa_plain(q, k, v, causal=True,
                                               impl="flash"),
            lambda q, k, v: nn_ops._sdpa_plain(q, k, v, causal=True,
                                               impl="einsum"),
            "qkv", q, k, v)

    Hh, rows = (4096, 4096) if full else (256, 64)

    @kernel("fused rms_norm fwd+bwd", f"[{rows}, {Hh}] bf16")
    def _():
        x = rnd(40, (rows, Hh), jnp.bfloat16, 1.0)
        w = 1.0 + rnd(41, (Hh,), jnp.float32, 0.1)
        g = rnd(42, (rows, Hh), jnp.bfloat16, 1.0)
        return fwd_bwd(
            lambda x, w: rms_norm.fused_rms_norm_fn(x, w, epsilon=1e-5),
            lambda x, w: nn_ops._rms_norm_plain(x, w, epsilon=1e-5),
            "xw", x, w, cot=g)

    # the serve leg's decode shape: 8 slots, 32 KV heads (MHA: one query
    # head per program), max_len 2048 -> 128 pages of 16
    sz = ctx["sizes"]
    Bq, KV = (8, 32) if full else (3, 2)

    def pool_case(ps, pps, q_dtype):
        """q, float32 K/V pools, ragged lengths and a shuffled page
        table for Bq sequences of up to ps * pps tokens."""
        P = Bq * pps
        q = rnd(50, (Bq, KV, 128), q_dtype)
        kp, vp = (rnd(51 + i, (KV, P, ps, 128), jnp.float32)
                  for i in range(2))
        rs = np.random.RandomState(0)
        table = jnp.asarray(rs.permutation(P).reshape(Bq, pps), jnp.int32)
        top = ps * pps
        lens = jnp.asarray(([1, ps + 1, top // 2 + 3, top, top - 1, 7,
                             top // 3, 2 * ps] * 2)[:Bq], jnp.int32)
        return q, kp, vp, lens, table

    for pool_dtype, q_dtype in ((jnp.float32, jnp.float32),
                                (jnp.bfloat16, jnp.bfloat16),
                                (jnp.bfloat16, jnp.float32)):
        names = (jnp.dtype(pool_dtype).name, jnp.dtype(q_dtype).name)

        @kernel(f"fused_paged_decode {names[0]} pool, {names[1]} q",
                f"B{Bq} KV{KV} G1 D128 page {sz['page_size']} x "
                f"{sz['max_len'] // sz['page_size']}")
        def _(pool_dtype=pool_dtype, q_dtype=q_dtype):
            ps = sz["page_size"]
            q, kp, vp, lens, table = pool_case(
                ps, sz["max_len"] // ps, q_dtype)
            kp, vp = kp.astype(pool_dtype), vp.astype(pool_dtype)
            check(paged_decode.supported(128, ps, ctx["on_tpu"])
                  or not ctx["on_tpu"],
                  "shape gate refuses the serving shape")

            def run(q):
                return paged_decode.paged_decode(q, kp, vp, lens, table)

            sites = kernel_sites(jax, run, q)
            out = run(q)
            with hi:
                want = paged._dense_paged_attention(
                    *f32(q, kp, vp), lens, table)
            return sites, {"out": err(out, want)}

    @kernel("stock paged_attention (PT_PAGED_IMPL=stock)",
            f"B{Bq} KV{KV} D128 page {sz['page_size']} bf16",
            tpu_only=True)
    def _():
        # the jax-shipped kernel inference/paged.py falls to when the
        # fused kernel's gate refuses a shape: its call signature is
        # checked against the installed jax here
        ps = sz["page_size"]
        q, kp, vp, lens, table = pool_case(
            ps, sz["max_len"] // ps, jnp.bfloat16)
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)

        def run(q):
            return paged.paged_decode_attention(q, kp, vp, lens, table)

        os.environ["PT_PAGED_IMPL"] = "stock"
        try:
            sites = kernel_sites(jax, run, q)
            out = run(q)
        finally:
            del os.environ["PT_PAGED_IMPL"]
        with hi:
            want = paged._dense_paged_attention(
                *f32(q, kp, vp), lens, table)
        return sites, {"out": err(out, want)}

    @kernel("fused_paged_decode_quant int8 pool",
            f"B{Bq} KV{KV} G1 D128 page 32 x {sz['max_len'] // 32}")
    def _():
        ps = 32
        q, kp, vp, lens, table = pool_case(
            ps, max(sz["max_len"] // ps, 2), jnp.bfloat16)
        ks = jnp.max(jnp.abs(kp), axis=(2, 3)) / 127.0      # [KV, P]
        vs = jnp.max(jnp.abs(vp), axis=(2, 3)) / 127.0
        kq = jnp.round(kp / ks[..., None, None]).astype(jnp.int8)
        vq = jnp.round(vp / vs[..., None, None]).astype(jnp.int8)
        check(paged_decode.supported_quant(128, ps, ctx["on_tpu"])
              or not ctx["on_tpu"], "quant shape gate refuses page 32")

        def run(q):
            return paged_decode.paged_decode_quant(
                q, kq, vq, lens, table, ks, vs)

        sites = kernel_sites(jax, run, q)
        out = run(q)
        with hi:
            want = paged._dense_paged_attention_q(
                q.astype(jnp.float32), kq, vq, lens, table, ks, vs)
        return sites, {"out": err(out, want)}

    Hm, Fm = (4096, 11008) if full else (128, 256)
    E, C = (2, 256) if full else (2, 16)

    @kernel("grouped_gemm (both expert GEMMs) fwd",
            f"E{E} C{C} H{Hm} F{Fm} bf16 gelu")
    def _():
        x = rnd(60, (E, C, Hm), jnp.bfloat16, 1.0)
        w1 = rnd(61, (E, Hm, Fm), jnp.bfloat16, 0.02)
        b1 = rnd(62, (E, 1, Fm), jnp.bfloat16, 0.02)
        w2 = rnd(63, (E, Fm, Hm), jnp.bfloat16, 0.02)
        b2 = rnd(64, (E, 1, Hm), jnp.bfloat16, 0.02)
        check(grouped_gemm.resolve_impl(Hm, Fm) == "pallas"
              or not ctx["on_tpu"], "auto does not pick the kernel")

        def run(x):
            return grouped_gemm.grouped_ffn(x, w1, b1, w2, b2,
                                            activation="gelu",
                                            impl="pallas")

        sites = kernel_sites(jax, run, x)
        out = run(x)
        with hi:
            want = grouped_gemm.einsum_ffn(*f32(x, w1, b1, w2, b2),
                                           "gelu")
        return sites, {"out": err(out, want)}

    for M in ((8, 256) if full else (8,)):
        for K, N in ((Hm, Fm), (Fm, Hm)):
            @kernel("quant_matmul int8 weight", f"[{M}, {K}] x [{K}, {N}]")
            def _(M=M, K=K, N=N):
                x = rnd(70, (M, K), jnp.bfloat16, 1.0)
                qlin = quant.quantize_linear(
                    rnd(71, (K, N), jnp.float32, 0.02))

                def run(x):
                    return quant.qmatmul(x, qlin, impl="pallas")

                sites = kernel_sites(jax, run, x)
                out = run(x)
                with hi:
                    want = quant.qmatmul(x.astype(jnp.float32), qlin,
                                         impl="einsum")
                return sites, {"out": err(out, want)}

    failed = [r for r in results if not r["ok"]]
    ctx["report"]["legs"]["kernels"] = {"kernels": results}
    check(not failed, "kernels failed: " + "; ".join(
        f"{r['kernel']} [{r['shape']}]: {r['error'][-400:]}"
        for r in failed))
    return {"kernels": results}


# -- train leg -----------------------------------------------------------

def build_train(ctx, mesh=None):
    import paddle_tpu as paddle
    from paddle_tpu.models import (CompiledTrainStep, LlamaForCausalLM,
                                   llama_shard_rules)

    sz = ctx["sizes"]
    paddle.seed(0)
    cfg = llama_config(ctx, num_hidden_layers=sz["train_depth"],
                       recompute=True, scan_layers=True,
                       max_position_embeddings=sz["train_seq"])
    model = LlamaForCausalLM(cfg)
    step = CompiledTrainStep(
        model, lr=1e-4, mesh=mesh,
        shard_rules=llama_shard_rules if mesh is not None else None,
        zero_opt_states=True, compute_dtype="bfloat16",
        moments_dtype="bfloat16")
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      (sz["train_batch"], sz["train_seq"])).astype(np.int32)
    return model, step, ids


def run_steps(ctx, step, ids, n):
    """n steps on one batch; every loss fetched (the fence)."""
    jax, clock = ctx["jax"], ctx["clock"]
    snap, t0 = clock.snapshot(), time.perf_counter()
    losses, walls = [], []
    for _ in range(n):
        t = time.perf_counter()
        losses.append(float(jax.block_until_ready(step.step(ids, ids))))
        walls.append(round(time.perf_counter() - t, 3))
        log(f"  step {len(losses)}: loss {losses[-1]:.4f} "
            f"({walls[-1]:.2f}s)")
    timing = clock.since(snap, time.perf_counter() - t0)
    timing["first_step_s"] = walls[0]       # compile + one step
    timing["steady_step_s"] = walls[1:]     # no compile in these
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    return losses, timing


def leg_train(ctx):
    jax, jnp, sz = ctx["jax"], ctx["jnp"], ctx["sizes"]
    log(f"train: building Llama, depth {sz['train_depth']}")
    model, step, ids = build_train(ctx)
    n_params = model.num_params()
    # the attention site, read off the step's own loss+grad program
    sites = kernel_sites(
        jax, jax.value_and_grad(step.loss_of), step.params,
        jnp.asarray(ids), jnp.asarray(ids))
    log(f"train: kernel sites {[short(s) for s in sites]}")
    if ctx["on_tpu"]:
        names = {short(s) for s in sites if s["compiled"]}
        check({"long_attention._fwd_kernel",
               "long_attention._bwd_kernel"} <= names,
              f"attention did not resolve to compiled long_attention: "
              f"{sites}")
    losses, timing = run_steps(ctx, step, ids, sz["train_steps"])
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    out = {"depth": sz["train_depth"],
           "hidden": model.config.hidden_size, "params": n_params,
           "batch": sz["train_batch"], "seq": sz["train_seq"],
           "attention": ("long_attention (compiled)" if ctx["on_tpu"]
                         else "einsum (cpu rehearsal)"),
           "kernel_sites": [short(s) + ("" if s["compiled"]
                                        else " [interpreted]")
                            for s in sites],
           "losses": [round(v, 4) for v in losses],
           "memory": memory(jax), **timing}
    ctx["train_first_loss"] = losses[0]
    del model, step
    gc.collect()
    out["bytes_in_use_after_release"] = memory(jax)["bytes_in_use"]
    return out


# -- four-chip leg -------------------------------------------------------

def leg_four_chip(ctx):
    jax, sz = ctx["jax"], ctx["sizes"]
    n = jax.device_count()
    if n < 4:
        return f"not run: {n} device(s)"
    from paddle_tpu.distributed import ProcessMesh

    mesh = ProcessMesh(shape=[2, 2], dim_names=["dp", "mp"])
    log("four_chip: building the same step on a 2x2 dp x mp mesh")
    model, step, ids = build_train(ctx, mesh=mesh)
    four = set(mesh.jax_mesh.devices.flat)
    name = "llama.layers.0.self_attn.q_proj.weight"
    check(step.params[name].sharding.device_set == four,
          f"parameter {name} lives on "
          f"{step.params[name].sharding.device_set}")
    check(step._m[name].sharding.device_set == four
          and step._master[name].sharding.device_set == four,
          f"optimizer state of {name} does not span the mesh")
    losses, timing = run_steps(ctx, step, ids, 2)
    rel = abs(losses[0] - ctx["train_first_loss"]) \
        / abs(ctx["train_first_loss"])
    # (the rehearsal's toy widths in bf16 read 1.8e-4 on 8 virtual CPU
    # devices: it debugs this script, not the arithmetic)
    rtol = FOUR_CHIP_LOSS_RTOL if ctx["full"] else 1e-2
    check(rel <= rtol,
          f"first-step loss {losses[0]} vs one chip "
          f"{ctx['train_first_loss']}: rel {rel:.2e} > {rtol}")
    per_dev = {str(d): memory(jax, d)["bytes_in_use"]
               for d in sorted(four, key=lambda d: d.id)}
    check(all(per_dev.values()) or not ctx["on_tpu"],   # (the CPU client
          f"a device holds nothing: {per_dev}")          # reports no stats)
    check(step._kernel_shard == (mesh.jax_mesh, "dp", "mp"),
          f"attention kernels were told {step._kernel_shard[1:]}")
    out = {"mesh": "2x2 dp x mp, zero_opt_states", "losses": losses,
           "one_chip_first_loss": ctx["train_first_loss"],
           "first_loss_rel_diff": float(f"{rel:.3g}"),
           "param_spec": str(step.params[name].sharding.spec),
           "moment_spec": str(step._m[name].sharding.spec),
           "bytes_in_use": per_dev, **timing}
    del model, step
    gc.collect()
    out["sharded_attention"] = sharded_attention(ctx, mesh.jax_mesh)
    return out


def sharded_attention(ctx, mesh):
    """The train step above proves long_attention per shard.  The other
    Pallas attention kernels — short_attention (with its in-kernel
    dropout) and the stock flash wrapper — reach a sharded step the
    same way (nn_ops._per_shard), so each is compiled once under the
    mesh through attention's own entry, forward and backward, against
    float32 einsum attention."""
    jax, jnp = ctx["jax"], ctx["jnp"]
    if not ctx["on_tpu"]:
        return "not rehearsed: impl='short'/'flash' need a TPU"
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.ops import nn_ops

    shard = (mesh, "dp", "mp")
    spec = NamedSharding(mesh, P("dp", None, "mp"))     # [B, S, H, D]
    key = jax.random.PRNGKey(3)

    def attn(impl, causal, **kw):
        return lambda q, k, v: nn_ops._sdpa_plain(
            q, k, v, causal=causal, impl=impl, shard=shard, **kw)

    def vg(fn, cot):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * cot),
            argnums=(0, 1, 2)))

    out = {}
    for impl, S, causal in (("short", 512, False), ("flash", 1024, True)):
        q, k, v, cot = (jax.device_put(
            jax.random.normal(jax.random.fold_in(key, i), (4, S, 4, 128),
                              jnp.float32) * 0.3, spec) for i in range(4))
        lo = [t.astype(jnp.bfloat16) for t in (q, k, v)]
        sites = kernel_sites(jax, vg(attn(impl, causal), cot), *lo)
        check(sites and all(s["compiled"] for s in sites),
              f"{impl} under the mesh: kernel sites {sites}")
        with jax.enable_x64(False):
            _, grads = vg(attn(impl, causal), cot)(*lo)
            got = jax.jit(attn(impl, causal))(*lo)
        with jax.default_matmul_precision("highest"):
            want = attn("einsum", causal)(q, k, v)
            _, want_grads = vg(attn("einsum", causal), cot)(q, k, v)
        errs = {"out": rel_err(got, want)}
        errs.update({f"d{n}": rel_err(g, w)
                     for n, g, w in zip("qkv", grads, want_grads)})
        bad = {n: e for n, e in errs.items() if e > KERNEL_TOL}
        check(not bad, f"{impl} under the mesh outside {KERNEL_TOL}: {bad}")
        out[impl] = {"shape": f"B4 S{S} H4 D128 bf16",
                     "sites": [short(s) for s in sites],
                     "max_rel_err": {n: float(f"{e:.3g}")
                                     for n, e in errs.items()}}
        log(f"sharded_attention {impl}: ok {out[impl]['max_rel_err']}")

    # Dropout under the mesh: every (batch, head) holds the same rows
    # and v = I, so the output is the dropped probability matrix and
    # masks can be compared; the cotangent I makes dV its transpose.
    # Each shard sees only local program ids: without a per-shard seed
    # all four shards would drop the same elements.
    n, p_drop = 128, 0.3
    eye = jnp.eye(n, dtype=jnp.float32)
    q = jax.device_put(jnp.broadcast_to(
        jax.random.normal(key, (1, n, 1, n), jnp.float32) * 0.3,
        (4, n, 4, n)), spec)
    v = jax.device_put(jnp.broadcast_to(eye[None, :, None], (4, n, 4, n)),
                       spec)
    drop = attn("short", False, key=jax.random.PRNGKey(5), dropout=p_drop)
    with jax.enable_x64(False):
        pd = jax.jit(drop)(q, q, v)
        _, (_, _, dv) = vg(drop, eye[None, :, None])(q, q, v)
    fwd = np.asarray(pd).transpose(0, 2, 1, 3).reshape(16, n, n) == 0
    bwd = np.asarray(dv).transpose(0, 2, 3, 1).reshape(16, n, n) == 0
    check(abs(fwd.mean() - p_drop) < 0.02,
          f"dropped {fwd.mean()}, asked {p_drop}")
    check(len({m.tobytes() for m in fwd}) == 16,
          "dropout masks repeat across (batch, head) under the mesh")
    check((fwd == bwd).all(),
          "backward mask differs from forward mask under the mesh")
    out["short_dropout"] = {"shape": f"B4 S{n} H4 D{n} f32",
                            "distinct_masks": 16,
                            "dropped": round(float(fwd.mean()), 4)}
    log(f"sharded_attention dropout: ok {out['short_dropout']}")
    return out


# -- serve leg -----------------------------------------------------------

class Tap:
    """Stands in front of one executor program and keeps the logits it
    returned — the engine's own numbers, observed, not recomputed."""

    def __init__(self, prog):
        self.prog, self.logits = prog, []

    def __call__(self, *a, **kw):
        out = self.prog(*a, **kw)
        self.logits.append(out[0])
        return out

    def __getattr__(self, name):
        return getattr(self.prog, name)


def leg_serve(ctx):
    jax, jnp, sz, clock = ctx["jax"], ctx["jnp"], ctx["sizes"], ctx["clock"]
    import paddle_tpu as paddle
    from paddle_tpu.inference import paged
    from paddle_tpu.inference.server import ServingEngine
    from paddle_tpu.inference.server.request import RequestState
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.ops.pallas_kernels import paged_decode

    gated = sorted(k for k in os.environ if k in (
        "PT_PREFIX_CACHE", "PT_SPEC_DECODE", "PT_ASYNC_EXEC", "PT_AOT",
        "PT_QUANT", "PT_WAL", "PT_SP_PREFILL", "PT_CLUSTER",
        "PT_PAGED_IMPL"))
    check(not gated, f"serve leg runs the default engine; unset {gated}")

    log(f"serve: building bf16 Llama, depth {sz['serve_depth']}")
    t0 = time.perf_counter()
    paddle.seed(1)
    cfg = llama_config(ctx, num_hidden_layers=sz["serve_depth"],
                       max_position_embeddings=sz["max_len"])
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    model.eval()
    eng = ServingEngine(model, max_seqs=sz["max_seqs"],
                        page_size=sz["page_size"], max_len=sz["max_len"],
                        dtype=jnp.bfloat16,
                        prefill_chunk=sz["prefill_chunk"])
    ex = eng.executor
    build_s = time.perf_counter() - t0

    # which decode kernel the engine's own routing resolves to
    impl = paged._select_impl(cfg.head_dim, sz["page_size"])
    if ctx["on_tpu"]:
        check(impl == "pallas", f"decode resolved to {impl!r}, not the "
              f"fused kernel")
        check(not paged_decode._interpret(), "fused kernel would be "
              "interpreted")

    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in sz["prompt_lens"]]

    def mixed_load():
        """Three arrive, the engine takes a few steps (the longest
        prompt is mid-prefill, the short ones already decode), then
        three more; stream() and run() drive step()."""
        snap, t0, tick0 = clock.snapshot(), time.perf_counter(), eng.tick
        handles = [eng.submit(p, max_new_tokens=sz["new_tokens"])
                   for p in prompts[:3]]
        for _ in range(3):
            eng.step()
        handles += [eng.submit(p, max_new_tokens=sz["new_tokens"])
                    for p in prompts[3:]]
        streamed = list(handles[0].stream())
        eng.run()
        timing = clock.since(snap, time.perf_counter() - t0)
        timing["steps"] = eng.tick - tick0
        # "the loop ended" proves nothing: the scheduler turns any
        # exception (a Mosaic compile error included) into one FAILED
        # request and carries on.  Every handle must have finished with
        # what it asked for.
        rows = []
        for h, p in zip(handles, prompts):
            m = h.metrics()
            rows.append({"prompt": len(p), "state": m["state"],
                         "reason": m["finish_reason"],
                         "tokens": m["tokens"],
                         "preemptions": m["preemptions"]})
            check(h.state is RequestState.FINISHED
                  and m["finish_reason"] == "length"
                  and m["tokens"] == sz["new_tokens"],
                  f"request {rows[-1]}: "
                  f"{getattr(eng.request(h.rid), 'error', None)!r}")
        check(streamed == handles[0].tokens, "stream() lost tokens")
        first = [eng.request(h.rid).first_token_step for h in handles]
        check(min(first[1:]) < first[0], "no request decoded while the "
              "long prompt prefilled")
        return rows, [h.tokens for h in handles], timing

    # first pass: every program compiles (or loads from the cache);
    # second pass: the same load again — steady state, and it must
    # compile nothing and say the same thing
    rows, tokens, first_pass = mixed_load()
    log(f"serve: first pass {first_pass}")
    _, tokens2, second_pass = mixed_load()
    log(f"serve: second pass {second_pass}")
    check(tokens2 == tokens, "the same load gave different tokens")
    check(second_pass["programs_compiled"] == 0,
          f"steady state compiled programs: {second_pass}")

    # One probe request, alone, with the programs tapped: the logits of
    # its last prefill chunk and of every decode step.
    probe = rng.randint(0, cfg.vocab_size,
                        (sz["probe_len"],)).astype(np.int32)
    ex._jit_chunk, ex._jit_decode = Tap(ex._jit_chunk), Tap(ex._jit_decode)
    ph = eng.submit(probe, max_new_tokens=sz["new_tokens"])
    step_walls = []                 # host clock per engine step; each
    while not eng.request(ph.rid).terminal:     # step ends in a fetch
        t = time.perf_counter()
        eng.step()
        step_walls.append(time.perf_counter() - t)
    toks = ph.result()
    chunk_tap, decode_tap = ex._jit_chunk, ex._jit_decode
    ex._jit_chunk, ex._jit_decode = chunk_tap.prog, decode_tap.prog
    check(ph.state is RequestState.FINISHED
          and len(toks) == sz["new_tokens"], f"probe: {ph.metrics()}")
    got = np.stack(
        [np.asarray(chunk_tap.logits[-1], np.float32)]
        + [np.asarray(lg, np.float32)[0] for lg in decode_tap.logits])
    check(got.shape == (sz["new_tokens"], cfg.vocab_size),
          f"tapped logits {got.shape}")
    check(np.isfinite(got).all(), "non-finite serving logits")
    # the engine's tokens are the argmax of those logits
    check([int(r.argmax()) for r in got] == toks,
          "tapped logits are not what the engine sampled from")
    serve_mem = memory(jax)

    # Reference: ONE plain forward of the same weights over prompt +
    # generated tokens, float32, einsum attention, precision "highest".
    # Row i of `got` predicts token i: position probe_len - 1 + i.
    log("serve: float32 reference forward")
    decode_sites = kernel_sites(
        jax, ex._decode_fwd, ex.layers, ex.tops,
        jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
        *ex.cache.pools(), jnp.ones((1,), jnp.int32),
        jnp.zeros((1, ex.cache.max_pages_per_seq), jnp.int32))
    # every handle holds the engine: let go of all of them, or the
    # pools and stacked weights stay in HBM under the reference
    del eng, ex, chunk_tap, decode_tap, ph
    gc.collect()
    log(f"serve: engine released, {memory(jax)['bytes_in_use']} B in use")
    model.float()
    cfg.attention_impl = "einsum"
    full = np.concatenate([probe, np.asarray(toks[:-1], np.int32)])
    with jax.default_matmul_precision("highest"), paddle.no_grad():
        ref = model(paddle.to_tensor(full[None].astype(np.int64)))
    ref = np.asarray(ref._data, np.float32)[0, len(probe) - 1:]
    check(ref.shape == got.shape, f"{ref.shape} vs {got.shape}")
    rms = float(np.sqrt(np.mean(ref ** 2)))
    delta = float(np.abs(got - ref).max())
    agree = int(sum(int(a.argmax()) == int(b.argmax())
                    for a, b in zip(got, ref)))
    log(f"serve: logits max|delta| {delta:.4g}, ref rms {rms:.4g}, "
        f"ratio {delta / rms:.4g}; argmax agrees {agree}/{len(got)}")
    check(delta / rms <= LOGIT_TOL,
          f"logits off the float32 reference: max|delta| {delta:.4g} "
          f"/ rms {rms:.4g} = {delta / rms:.4g} > {LOGIT_TOL}")
    del model
    gc.collect()
    return {"depth": sz["serve_depth"], "hidden": cfg.hidden_size,
            "dtype": "bfloat16",
            "engine": {k: sz[k] for k in ("max_seqs", "page_size",
                                          "max_len", "prefill_chunk")},
            "decode_impl": impl,
            "kernel_sites": [short(s) + ("" if s["compiled"]
                                         else " [interpreted]")
                             for s in decode_sites],
            "requests": rows,
            "build_s": round(build_s, 2),
            "first_pass": first_pass,       # compile inside
            "second_pass": second_pass,     # steady: zero compiles
            # one lone request's decode steps, dispatch + device + fetch
            # (a smoke reading for ROADMAP S3(b), not a benchmark metric)
            "probe_decode_step_ms_median": round(float(np.median(
                step_walls[-(sz["new_tokens"] // 2):])) * 1e3, 2),
            "logits": {"rows": len(got), "max_abs_delta": delta,
                       "ref_rms": rms,
                       "ratio": float(f"{delta / rms:.3g}"),
                       "tolerance": LOGIT_TOL,
                       "argmax_agree": f"{agree}/{len(got)}"},
            "memory": serve_mem}


LEG_FNS = {"kernels": leg_kernels, "train": leg_train,
           "serve": leg_serve, "four_chip": leg_four_chip}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU with kernels interpreted: "
                         "debugs this script, proves nothing about the "
                         "chip, and says so")
    args = ap.parse_args(argv)

    import jax      # the one process that touches the chip

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if args.rehearse_cpu:
        if dev.platform != "cpu":
            sys.exit("chip_smoke.py: --rehearse-cpu is for the CPU "
                     f"(JAX_PLATFORMS=cpu); found {dev.platform!r}")
    elif not on_tpu:
        print(f"chip_smoke.py: no TPU: jax.devices()[0].platform is "
              f"{dev.platform!r} ({len(jax.devices())} device(s)). "
              f"There is no CPU path; nothing was run.", file=sys.stderr)
        sys.exit(2)

    import jax.numpy as jnp
    import jaxlib

    # (in a directory that holds nothing else of the repo this import
    # fails: non-zero exit, no result line)
    from paddle_tpu.utils import (compile_cache_entries as cache_entries,
                                  enable_compile_cache)

    cache_dir = enable_compile_cache()      # raises if it cannot
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:       # the CPU rehearsal
        libtpu = None
    sizes = TINY if args.rehearse_cpu else FULL
    report = {
        "ok": False,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "versions": {"python": sys.version.split()[0],
                     "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "compile_cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": cache_entries(cache_dir)},
        "legs": {},
    }
    ctx = {"jax": jax, "jnp": jnp, "on_tpu": on_tpu, "report": report,
           "sizes": sizes, "full": not args.rehearse_cpu,
           "clock": CompileClock(jax)}
    log(f"device {report['device']}, cache {cache_dir} "
        f"({report['compile_cache']['entries_before']} entries)")

    import contextlib

    from jax.experimental.pallas import tpu as pltpu

    interp = (pltpu.force_tpu_interpret_mode() if args.rehearse_cpu
              else contextlib.nullcontext())
    try:
        with interp:
            for name in LEGS:
                t0 = time.perf_counter()
                log(f"=== leg {name}")
                # no except: a failing leg raises through to the exit code
                report["legs"][name] = LEG_FNS[name](ctx)
                log(f"=== leg {name} done "
                    f"({time.perf_counter() - t0:.1f}s)")
    finally:
        # what is too long for the end of the output, also on failure
        # (chiprun brings chiprun_out/ back; .gitignore lists it)
        report["compile_cache"].update(
            entries_after=cache_entries(cache_dir),
            hits=ctx["clock"].cache_hits, misses=ctx["clock"].cache_misses)
        report["seconds"] = round(time.perf_counter() - _T0, 1)
        save(report)

    if args.rehearse_cpu:
        report["rehearsal"] = ("cpu, toy sizes, kernels interpreted: "
                               "not a pass")
    else:
        # a four_chip leg that reads "not run: N device(s)" stays that
        # string in the report: on one chip it is not run, not passed
        report["ok"] = True
    report["claim"] = None
    save(report)
    print(json.dumps(report), flush=True)           # the full report
    print(json.dumps(verdict(report)), flush=True)  # last line: verdict
    return 0


if __name__ == "__main__":
    sys.exit(main())
