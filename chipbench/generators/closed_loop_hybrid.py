"""Serving traffic for the hybrid (Mamba-2 + attention) configuration: the
closed loop of ``closed_loop.py`` — its ``Loop``, ``Dealer``, ``numbers``,
``sample`` and ``step_facts``, by import — around an engine built from the
hybrid model, warmed and scored with this configuration's own weights and
plain reference.

What differs from ``closed_loop.py``:

- the model and its weights are made directly in the engine's dtype and
  loaded layer by layer (``program_hybrid.py``): a float32 copy of 3.2 B
  parameters fits beside nothing;
- the warm-up submits as many requests as ``closed_loop.warm`` does (the
  span readers count them to find the window), but the hybrid engine has
  ONE decode program whatever the batch, so the second group only fills
  every slot once;
- ``score`` runs ``reference_hybrid.py`` (the recurrence token by token)
  and, for ``control.py``, two controls: every matmul on int8 operands,
  and the recurrent state rounded to bf16 after every token;
- a second number is compared, ``state_row_gap``.  Served greedy tokens
  cannot see the precision of the recurrent state (a reference whose
  state is rounded to bf16 after every token serves the float32
  reference's own tokens), so before the engine is released the harness
  reads the state rows the engine holds for ``state_requests`` requests
  in flight, and the reference takes the same tokens through its own
  recurrence.  The number is read in the FIRST recurrent layer: its
  input is the embedding through one norm and one projection, so what
  differs there is the recurrence's own arithmetic and what the pool
  keeps of it; deeper layers carry the bf16 activations' noise of every
  layer above them (``state_gap_by_layer``, which ``control.py`` prints),
  which ``served_token_gap`` bounds.
"""
import gc
import time

import numpy as np

from chipbench import (compare, program, program_hybrid, reference_hybrid,
                       spec, weights_hybrid)
from chipbench.harness import (GcClock, bytes_in_use, log, memory_peak_bytes)


def build_engine(cfg, seed, jnp):
    from paddle_tpu.inference.server import ServingEngine

    e = cfg["engine"]
    dtype = jnp.dtype(e["dtype"])
    model = program_hybrid.build_model(cfg, dtype)
    model.eval()
    program_hybrid.load_weights(model, cfg, seed, dtype)
    return ServingEngine(model, max_seqs=e["max_seqs"],
                         page_size=e["page_size"], max_len=e["max_len"],
                         dtype=dtype, prefill_chunk=e["prefill_chunk"],
                         num_pages=e["num_pages"])


def warm(eng, cfg, traffic):
    """Every program shape the mix can reach, once: each prompt length
    (its chunks at their starts), then every slot through one decode."""
    rng = np.random.Generator(np.random.Philox(key=[0, 1]))

    def prompt(n):
        return rng.integers(0, cfg["vocab_size"], (n,), np.int32)

    def drain(handles, want):
        eng.run()
        for h in handles:
            if len(h.tokens) != want:
                raise SystemExit(f"warm-up request {h.rid}: {h.metrics()}")

    lens = traffic["prompt_lens"]
    drain([eng.submit(prompt(n), max_new_tokens=1) for n in lens], 1)
    log(f"serve: warmed the {len(lens)} prompt lengths")
    drain([eng.submit(prompt(min(lens)), max_new_tokens=2)
           for _ in range(traffic["clients"])], 2)


def hold_states(eng, loop, traffic):
    """``[(ids the engine has taken in, its state rows)]`` for
    ``state_requests`` requests in flight that have been prefilled: the
    longest, the shortest and those midway.  A request that has emitted
    k tokens has taken in its prompt and the first k - 1 of them."""
    live = sorted((s for s in loop.live.values()
                   if s.tokens and eng.request(s.rid).sid is not None
                   and not eng.request(s.rid).terminal),
                  key=lambda s: (len(s.prompt) + len(s.tokens), s.rid))
    k = min(len(live), traffic["state_requests"])
    at = sorted({round(i * (len(live) - 1) / max(k - 1, 1))
                 for i in range(k)}, reverse=True)
    return [(np.concatenate([live[i].prompt,
                             np.asarray(live[i].tokens[:-1], np.int32)]),
             program_hybrid.slot_state(eng, live[i].rid)) for i in at]


def _head_gaps(got, want):
    """By layer and head: the norm of the difference between two states
    ``[layers, heads, P, N]`` over the norm of ``want``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    diff = np.sqrt(np.square(got - want).sum((-1, -2)))
    return diff / np.maximum(np.sqrt(np.square(want).sum((-1, -2))), 1e-30)


def _gap(lg, tokens):
    """The widest gap by which one of ``tokens`` lies below the best
    logit at its position."""
    k = len(tokens)
    return float((lg.max(-1) - lg[np.arange(k), tokens]).max())


def score(cfg, traffic, seed, picked, held, jnp, control=False):
    """Runs the reference once over each picked request's prompt and
    served tokens, and through its first recurrent layer over the tokens
    of each held state.  Returns the widest gap by which a served
    token's logit lies below the reference's best, over the RMS of the
    reference's logits; the widest gap of a head's held state from the
    reference's in the first recurrent layer (``state_row_gap``) — and
    with ``control`` the same two readings for each control in the
    program's place, and the state's gap layer by layer."""
    w = weights_hybrid.make(cfg, seed, jnp.dtype(cfg["engine"]["dtype"]))
    rows = max(traffic["answer_lens"])
    scorers = {"reference": reference_hybrid.Scorer(cfg, rows)}
    if control:
        scorers["int8"] = reference_hybrid.Scorer(cfg, rows, quant="int8")
        scorers["bf16_state"] = reference_hybrid.Scorer(
            cfg, rows, state="bfloat16")
    worst = {who: 0.0 for who in scorers}
    sq, n, agree = 0.0, 0, 0
    for s in picked:
        seq = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
        first, k = len(s.prompt) - 1, len(s.tokens)
        lg = np.asarray(scorers["reference"](w, seq, first), np.float32)[:k]
        served = np.asarray(s.tokens)
        worst["reference"] = max(worst["reference"], _gap(lg, served))
        agree += int((lg.argmax(-1) == served).sum())
        sq, n = sq + float(np.square(lg).sum()), n + lg.size
        for who in scorers:
            if who != "reference":
                low = np.asarray(scorers[who](w, seq, first), np.float32)[:k]
                worst[who] = max(worst[who], _gap(lg, low.argmax(-1)))
    rms = float(np.sqrt(sq / max(n, 1)))
    out = {"served_token_gap": (worst.pop("reference") / rms if n
                                else float("nan")),
           "tokens_scored": int(sum(len(s.tokens) for s in picked)),
           "argmax_agree": agree, "logit_rms": rms}
    out.update({f"{who}_token_gap": gap / rms for who, gap in worst.items()})

    # -- the held states, in the first recurrent layer -----------------------
    first = cfg["layer_types"].index("mamba")
    state_gap = {who: 0.0 for who in scorers}
    for ids, rows_held in held:
        want = scorers["reference"].states(w, ids, depth=first + 1)
        state_gap["reference"] = max(
            state_gap["reference"],
            float(_head_gaps(rows_held[:1], want).max()))
        for who in scorers:
            if who != "reference":
                low = scorers[who].states(w, ids, depth=first + 1)
                state_gap[who] = max(state_gap[who],
                                     float(_head_gaps(low, want).max()))
    # no state held: nothing was compared, which is no pass
    out["state_row_gap"] = state_gap.pop("reference") if held \
        else float("nan")
    out["state_tokens"] = [len(ids) for ids, _ in held]
    out.update({f"{who}_state_gap": g for who, g in state_gap.items()})
    if control and held:    # layer by layer, the longest held sequence
        ids, rows_held = held[0]
        want = scorers["reference"].states(w, ids)
        by_layer = {"program": _head_gaps(rows_held, want)}
        by_layer.update({who: _head_gaps(scorers[who].states(w, ids), want)
                         for who in scorers if who != "reference"})
        out["state_gap_by_layer"] = {
            who: [round(float(g), 5) for g in gaps.max(-1)]
            for who, gaps in by_layer.items()}
    return out


def run(ctx, control=False):
    jax, jnp = ctx.jax, ctx.jnp
    cfg, traffic, seed = ctx.cell["config"], ctx.cell["traffic"], ctx.seed
    base = spec.load_module(ctx.bench, "generators", "closed_loop")
    program.check_gates()

    log(f"serve: building depth {cfg['num_hidden_layers']}, "
        f"{weights_hybrid.count(cfg) / 1e6:.1f} M parameters")
    eng = build_engine(cfg, seed, jnp)
    gc.collect()
    log(f"serve: engine built, {bytes_in_use(jax)} B in use, peak "
        f"{memory_peak_bytes(jax)}")
    warm(eng, cfg, traffic)
    log(f"serve: warmed, {ctx.clock.compiles} programs, "
        f"{bytes_in_use(jax)} B in use")
    loop = base.Loop(eng, base.Dealer(cfg, traffic, seed),
                     traffic["clients"], ctx.trace)
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
        traced = base.step_facts(loop, tt0, t)
    # late answers are late, not wrong: every request of the window gets
    # its first token (no new ones are sent meanwhile)
    loop.submitting = False
    while any(s.in_window and not s.stamps for s in loop.live.values()):
        loop.step()
    window_s = t1 - t0
    sent, failed, end_to_end, facts = base.numbers(loop, t0, t1, window_s)
    ex = eng.executor
    facts.update(warm_programs=warm_programs,
                 compiled_in_window=compiled_in_window, traced=traced,
                 num_pages=ex.cache.num_pages, state_bytes=ex.state_bytes,
                 bytes_in_use_at_close=held, gc_s=gc_clock.seconds,
                 gc_collections=gc_clock.collections)
    log(f"serve: {facts['steps']} steps, {facts['requests_finished']} "
        f"requests finished, {facts['output_tokens']} tokens in "
        f"{window_s:.2f} s; programs compiled inside the window: "
        f"{compiled_in_window}")
    peak = memory_peak_bytes(jax)
    short = sum(len(s.tokens) != s.asked for s in loop.done
                if s.state == ("finished", "length"))
    picked = base.sample(loop, traffic, seed, t0, t1)
    held = hold_states(eng, loop, traffic)

    # -- release the engine, then the reference ----------------------------
    loop.eng = None
    del eng, ex
    gc.collect()
    log(f"serve: engine released, {bytes_in_use(jax)} B in use")
    t_ref = time.perf_counter()
    scored = score(cfg, traffic, seed, picked, held, jnp, control)
    log(f"serve: reference scored {scored['tokens_scored']} tokens of "
        f"{len(picked)} requests and {len(held)} held states in "
        f"{time.perf_counter() - t_ref:.1f} s: {scored}")
    facts["scored"] = scored
    mine = {"served_token_gap": scored["served_token_gap"],
            "state_row_gap": scored["state_row_gap"],
            "short_answers": float(short)}
    if control:     # what control.py puts through the run's own limits
        facts["readings"] = {"program": mine}
        facts["readings"].update({
            who: {"served_token_gap": scored[f"{who}_token_gap"],
                  "state_row_gap": scored[f"{who}_state_gap"],
                  "short_answers": 0.0} for who in ("int8", "bf16_state")})
    return {"attempted": len(sent), "failed": len(failed),
            "end_to_end": end_to_end,
            "checks": compare.checks(mine, ctx.limits),
            "memory_peak_bytes": peak, "facts": facts}
