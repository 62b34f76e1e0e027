"""Serving traffic: a closed loop of a fixed number of clients.

Each client submits a request, waits for its last token and submits its
next at once.  The mix's file gives the number of clients, the lists
prompt and answer lengths are drawn from with their weights (and the
public trace the weights were fitted to: ``source``, ``published``,
``fit``), and ``deck``: the lengths are dealt from a deck of that many
(prompt, answer) pairs which holds each length in proportion to its
weight and is the same for every seed; the seed shuffles each pass
through it and draws the token ids.  So every seed offers the same work
in another order.  The harness drives ``submit()`` / ``step()`` itself,
in one thread, and stamps every token with the host clock after the
``step()`` that produced it.

Set-up builds the engine, warms every program shape the mix can reach
(each prompt length once; decode batches of every size from ``clients``
down to 1), then runs the loop itself until ``preroll_requests`` have
finished, so that the window opens on a loop in its steady state and
not on all clients prefilling at once.  A traced run measures the same
window, untraced, and then lets the loop go on for ``trace_seconds``
under the profiler: host-clock numbers come from the whole window, the
device's from the stretch after it.
"""
import gc
import time

import numpy as np

from chipbench import compare, program, reference, weights
from chipbench.harness import (GcClock, bytes_in_use, log, memory_peak_bytes,
                               percentile)


def deck(traffic):
    """The (prompt, answer) pairs every seed deals from: each length as
    often as its weight says, paired once and for all."""
    def spread(lens, wts):
        counts = [round(w * traffic["deck"]) for w in wts]
        if sum(counts) != traffic["deck"]:
            raise SystemExit(f"weights {wts} do not deal a deck of "
                             f"{traffic['deck']} evenly: {counts}")
        return [n for n, c in zip(lens, counts) for _ in range(c)]

    prompts = spread(traffic["prompt_lens"], traffic["prompt_weights"])
    answers = spread(traffic["answer_lens"], traffic["answer_weights"])
    fixed = np.random.Generator(np.random.Philox(key=[0, 0]))
    return list(zip(prompts, fixed.permutation(answers).tolist()))


class Dealer:
    """Request n of the run: its lengths and its prompt ids, from the
    seed alone.  Pass after pass through the deck, each pass in the
    seed's order."""

    def __init__(self, cfg, traffic, seed):
        self.vocab, self.seed, self.deck = cfg["vocab_size"], int(seed), \
            deck(traffic)
        self.n, self.order = 0, []

    def next(self):
        if not self.order:
            rng = np.random.Generator(np.random.Philox(
                key=[self.seed, 1 + self.n // len(self.deck)]))
            self.order = rng.permutation(len(self.deck)).tolist()
        plen, alen = self.deck[self.order.pop()]
        rng = np.random.Generator(np.random.Philox(
            key=[self.seed, (1 << 40) + self.n]))
        self.n += 1
        return rng.integers(0, self.vocab, (plen,), np.int32), alen


class Sent:
    """What the harness keeps of one request: plain data, no handle."""

    __slots__ = ("rid", "prompt", "asked", "t_submit", "stamps", "tokens",
                 "prefilled", "state", "in_window")

    def __init__(self, rid, prompt, asked, t_submit, in_window):
        self.rid, self.prompt, self.asked = rid, prompt, asked
        self.t_submit, self.in_window = t_submit, in_window
        self.stamps, self.tokens, self.prefilled, self.state = [], [], 0, None


def build_engine(cfg, seed, jnp):
    from paddle_tpu.inference.server import ServingEngine

    e = cfg["engine"]
    dtype = jnp.dtype(e["dtype"])
    model = program.build_model(cfg)
    if e["dtype"] == "bfloat16":
        model.bfloat16()
    model.eval()
    program.load_weights(model, weights.make(cfg, seed, dtype))
    return ServingEngine(model, max_seqs=e["max_seqs"],
                         page_size=e["page_size"], max_len=e["max_len"],
                         dtype=dtype, prefill_chunk=e["prefill_chunk"],
                         num_pages=e["num_pages"])


def warm(eng, cfg, traffic):
    """Every program shape the mix can reach, once: each prompt length
    (its chunks at their starts), then decode batches of every size."""
    rng = np.random.Generator(np.random.Philox(key=[0, 1]))

    def prompt(n):
        return rng.integers(0, cfg["vocab_size"], (n,), np.int32)

    def drain(handles, want):
        eng.run()
        for h, n in zip(handles, want):
            if len(h.tokens) != n:
                raise SystemExit(f"warm-up request {h.rid}: {h.metrics()}")

    lens = traffic["prompt_lens"]
    drain([eng.submit(prompt(n), max_new_tokens=1) for n in lens],
          [1] * len(lens))
    log(f"serve: warmed the {len(lens)} prompt lengths")
    # the decode batches, behind the shortest prefill the mix holds: its
    # shortest prompt, or one full chunk where every prompt starts with one
    n, short = traffic["clients"], min(min(lens), cfg["engine"]["prefill_chunk"])
    drain([eng.submit(prompt(short), max_new_tokens=2 + i)
           for i in range(n)], [2 + i for i in range(n)])


class Loop:
    """The closed loop and its records."""

    def __init__(self, eng, dealer, clients, trace):
        self.eng, self.dealer, self.trace = eng, dealer, trace
        self.live = {}          # rid -> Sent, in flight
        self.done = []          # Sent, terminal
        self.steps = []         # per step: dict (see step())
        self.t_open = None      # window start; None before it
        self.submitting = True
        for _ in range(clients):
            self.submit()

    def submit(self):
        prompt, asked = self.dealer.next()
        t = time.perf_counter()
        h = self.eng.submit(prompt, max_new_tokens=asked)
        self.live[h.rid] = Sent(h.rid, prompt, asked, t,
                                self.t_open is not None)

    def step(self):
        eng, ex = self.eng, self.eng.executor
        chunks_before = len(ex.prefill_events)
        t_a = time.perf_counter()
        with self.trace.span("cb:step"):
            emitted = eng.step()
        t_b = time.perf_counter()
        with self.trace.span("cb:stamp"):
            row = {"t": t_b, "wall": t_b - t_a, "decode": [], "tokens": 0,
                   "prefill_tokens": 0, "prefill_context": 0, "first": 0,
                   "chunks": len(ex.prefill_events) - chunks_before,
                   "pages_used": ex.cache.num_pages - ex.free_pages}
            for rid, toks in emitted.items():
                s = self.live[rid]
                if s.tokens:        # it was in this step's decode batch
                    row["decode"].append(len(s.prompt) + len(s.tokens))
                else:
                    row["first"] += 1
                s.tokens.extend(int(t) for t in toks)
                s.stamps.extend([t_b] * len(toks))
                row["tokens"] += len(toks)
            finished = []
            for rid, s in self.live.items():
                req = eng.request(rid)
                if len(s.prompt) > s.prefilled:     # still prefilling
                    now = len(s.prompt) if s.tokens else req.prefill_done
                    c = now - s.prefilled
                    row["prefill_tokens"] += c
                    row["prefill_context"] += c * s.prefilled + c * (c + 1) // 2
                    s.prefilled = now
                if req.terminal:
                    s.state = (req.state.value, req.finish_reason)
                    finished.append(rid)
            self.steps.append(row)
        with self.trace.span("cb:submit"):
            for rid in finished:
                self.done.append(self.live.pop(rid))
                if self.submitting:
                    self.submit()
        return t_b


def step_facts(loop, t0, t1):
    """What the harness's per-step record holds of the steps that ended
    in (t0, t1]."""
    steps = [r for r in loop.steps if t0 < r["t"] <= t1]
    return {
        "steps": len(steps),
        "output_tokens": sum(r["tokens"] for r in steps),
        "layer_tokens": sum(r["prefill_tokens"] + len(r["decode"])
                            for r in steps),
        "sampled_tokens": sum(r["first"] + len(r["decode"]) for r in steps),
        "context_sum": sum(r["prefill_context"] + sum(r["decode"])
                           for r in steps),
        "decode_calls": [[len(r["decode"]), sum(r["decode"])]
                         for r in steps if r["decode"]],
        "decode_step_s": [r["wall"] for r in steps if not r["chunks"]],
        "prefill_step_s": [r["wall"] for r in steps if r["chunks"]],
        "pages_used_mean": (sum(r["pages_used"] for r in steps) / len(steps)
                            if steps else None),
        "step_s_max": max((r["wall"] for r in steps), default=None),
    }


def numbers(loop, t0, t1, window_s):
    """The window's end-to-end metrics and the facts the readers use."""
    sent = [s for s in loop.done + list(loop.live.values()) if s.in_window]
    ok = ("finished", "length")
    failed = {s.rid for s in sent
              if not s.stamps or (s.state is not None and s.state != ok)}
    # a request that failed or never answered misses any limit
    ttft = [float(window_s) if s.rid in failed else s.stamps[0] - s.t_submit
            for s in sent]
    everyone = loop.done + list(loop.live.values())
    gaps = [b - a for s in everyone
            for a, b in zip(s.stamps, s.stamps[1:]) if t0 < b <= t1]
    facts = step_facts(loop, t0, t1)
    facts.update({
        "kind": "serve", "window_s": window_s,
        "requests_submitted": len(sent),
        "requests_finished": sum(s.state == ok and t0 < s.stamps[-1] <= t1
                                 for s in loop.done),
        "ttft_p50_ms": 1e3 * percentile(ttft, 50) if ttft else None,
        "ttft_p85_ms": 1e3 * percentile(ttft, 85) if ttft else None,
        "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else None,
        "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None,
    })
    rate = facts["output_tokens"] / window_s
    return sent, failed, {"serve_tokens_per_s": rate}, facts


def sample(loop, traffic, seed, t0, t1):
    """The requests whose served tokens the reference scores: finished
    inside the window, the longest among them, the rest drawn from the
    seed."""
    pool = sorted((s for s in loop.done
                   if s.state == ("finished", "length") and s.stamps
                   and t0 < s.stamps[-1] <= t1), key=lambda s: s.rid)
    if not pool:
        return []
    longest = max(pool, key=lambda s: len(s.prompt) + len(s.tokens))
    rest = [s for s in pool if s is not longest]
    rng = np.random.Generator(np.random.Philox(key=[int(seed), 2]))
    k = min(len(rest), traffic["check_requests"] - 1)
    picked = [rest[i] for i in rng.choice(len(rest), size=k, replace=False)]
    return [longest] + picked


def score(cfg, traffic, seed, picked, jnp, control=False):
    """Runs the reference once over each picked request's prompt and
    served tokens.  Returns the widest gap by which a served token's
    logit lies below the reference's best, over the RMS of the
    reference's logits — and with ``control`` the same reading for the
    token that the int8 reference puts first at each position."""
    e = cfg["engine"]
    w = weights.make(cfg, seed, jnp.dtype(e["dtype"]))
    rows = max(traffic["answer_lens"])
    ref, ref_int8 = reference.make_scorer(cfg, e["max_len"], rows)
    worst, worst_ctl, sq, n, agree = 0.0, 0.0, 0.0, 0, 0
    for s in picked:
        seq = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
        first, k = len(s.prompt) - 1, len(s.tokens)
        lg = np.asarray(ref(w, seq, first), np.float32)[:k]
        served = np.asarray(s.tokens)
        at = lg[np.arange(k), served]
        worst = max(worst, float((lg.max(-1) - at).max()))
        agree += int((lg.argmax(-1) == served).sum())
        sq, n = sq + float(np.square(lg).sum()), n + lg.size
        if control:
            low = np.asarray(ref_int8(w, seq, first), np.float32)[:k]
            at = lg[np.arange(k), low.argmax(-1)]
            worst_ctl = max(worst_ctl, float((lg.max(-1) - at).max()))
    rms = float(np.sqrt(sq / max(n, 1)))
    out = {"served_token_gap": worst / rms if n else float("nan"),
           "tokens_scored": int(sum(len(s.tokens) for s in picked)),
           "argmax_agree": agree, "logit_rms": rms}
    if control:
        out["control_token_gap"] = worst_ctl / rms
    return out


def run(ctx, control=False):
    jax, jnp = ctx.jax, ctx.jnp
    cfg, traffic, seed = ctx.cell["config"], ctx.cell["traffic"], ctx.seed
    program.check_gates()

    log(f"serve: building depth {cfg['num_hidden_layers']}, "
        f"{weights.count(cfg) / 1e6:.1f} M parameters")
    eng = build_engine(cfg, seed, jnp)
    log(f"serve: engine built, {bytes_in_use(jax)} B in use")
    warm(eng, cfg, traffic)
    log(f"serve: warmed, {ctx.clock.compiles} programs")
    loop = Loop(eng, Dealer(cfg, traffic, seed), traffic["clients"], ctx.trace)
    while len(loop.done) < traffic["preroll_requests"]:
        loop.step()
    warm_programs = ctx.clock.compiles
    log(f"serve: pre-roll done after {len(loop.steps)} steps, "
        f"{warm_programs} programs")

    # -- the window ----------------------------------------------------------
    t0 = t = time.perf_counter()
    loop.t_open = t0
    ctx.window_started(t0)
    with GcClock() as gc_clock:
        while t - t0 < ctx.seconds:
            t = loop.step()
    t1, loop.t_open = t, None
    compiled_in_window = ctx.clock.compiles - warm_programs
    held = bytes_in_use(jax)
    traced = None
    if ctx.traced:      # the same loop goes on, under the profiler
        with ctx.trace:
            with ctx.trace.span("cb:window"):
                tt0 = t = time.perf_counter()
                while t - tt0 < traffic["trace_seconds"]:
                    t = loop.step()
        traced = step_facts(loop, tt0, t)
    # late answers are late, not wrong: every request of the window gets
    # its first token (no new ones are sent meanwhile)
    loop.submitting = False
    while any(s.in_window and not s.stamps for s in loop.live.values()):
        loop.step()
    window_s = t1 - t0
    sent, failed, end_to_end, facts = numbers(loop, t0, t1, window_s)
    facts.update(warm_programs=warm_programs,
                 compiled_in_window=compiled_in_window, traced=traced,
                 num_pages=eng.executor.cache.num_pages,
                 bytes_in_use_at_close=held, gc_s=gc_clock.seconds,
                 gc_collections=gc_clock.collections)
    log(f"serve: {facts['steps']} steps, {facts['requests_finished']} "
        f"requests finished, {facts['output_tokens']} tokens in "
        f"{window_s:.2f} s; programs compiled inside the window: "
        f"{compiled_in_window}")
    peak = memory_peak_bytes(jax)
    short = sum(len(s.tokens) != s.asked for s in loop.done
                if s.state == ("finished", "length"))
    picked = sample(loop, traffic, seed, t0, t1)

    # -- release the engine, then the reference ----------------------------
    loop.eng = None
    del eng
    gc.collect()
    log(f"serve: engine released, {bytes_in_use(jax)} B in use")
    t_ref = time.perf_counter()
    scored = score(cfg, traffic, seed, picked, jnp, control)
    log(f"serve: reference scored {scored['tokens_scored']} tokens of "
        f"{len(picked)} requests in {time.perf_counter() - t_ref:.1f} s: "
        f"{scored}")
    facts["scored"] = scored
    checks = compare.checks(
        {"served_token_gap": scored["served_token_gap"],
         "short_answers": float(short)}, ctx.limits)
    return {"attempted": len(sent), "failed": len(failed),
            "end_to_end": end_to_end, "checks": checks,
            "memory_peak_bytes": peak, "facts": facts}
