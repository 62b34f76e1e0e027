"""Serving traffic for the window + full attention configuration with
routed experts: the closed loop of ``closed_loop.py`` — its ``Loop``,
``Dealer``, ``numbers``, ``sample`` and ``step_facts``, by import — behind
``closed_loop_mla_moe.py``'s warm-up and ramp (``warm``, ``preroll``, the
row-gap helpers, by import too), around an engine built from that model
and scored with this configuration's own weights and plain reference.

What differs from ``closed_loop_mla_moe.py``:

- the rows compared are K and V.  Before the engine is released the
  harness reads what the engine HOLDS for ``row_requests`` requests in
  flight (the longest and the shortest) in three layers: the first
  sliding layer (its input is the embedding through one norm and one
  projection), the first full layer, and the last layer (whose input has
  been through every expert layer but it).  Of a full layer it reads every
  token; of a sliding layer what the window group still keeps, and the
  token that span begins at must be the first page with a key the next
  query can see: an older page kept, or a visible one released, fails the
  number outright.  ``kv_row_gap``: the norm of the difference over the
  norm of the reference's rows, keys and values together, the worse of
  the first sliding and the first full layer, the worst request.
  ``deep_row_gap``: in the last layer the MEDIAN over the tokens held of
  each token's own gap (a router in bf16 picks another last expert than
  the float32 reference where two scores nearly tie, which moves single
  tokens by 5-30 % and is no fault; leaving out or altering part of the
  mathematics moves every token); the worst request;
- ``control.py`` gets four controls beside the program, each through the
  run's own limits: every matmul on int8 operands, one expert fewer a
  token, the window ignored on the sliding layers, and rope applied on
  the full layers;
- the facts gain the executor's counters, read at the window's two ends:
  the decode program's expert counter (``experts``) and the cache's pages
  by layer group (``pages``: the sum of used pages after every chunk and
  decode step, the samples summed, the pages released behind the window,
  the pools' sizes); and the keys the window's tokens SEE in a sliding
  layer, ``min(position + 1, sliding_window)`` each (``seen_window_sum``
  beside ``context_sum``; per decode step ``decode_window_keys`` beside
  ``decode_calls``, in the traced stretch too).
"""
import gc
import time

import numpy as np

from chipbench import (compare, flops_window_moe, program,
                       program_window_moe, reference_window_moe, spec,
                       weights_window_moe)
from chipbench.harness import (GcClock, bytes_in_use, log, memory_peak_bytes)

CONTROLS = {"int8": dict(quant="int8"), "top_k_less_1": None,
            "window_ignored": dict(ignore_window=True),
            "rope_on_full": dict(rope_on_full=True)}


def build_engine(cfg, seed, jnp):
    from paddle_tpu.inference.server import ServingEngine

    e = cfg["engine"]
    dtype = jnp.dtype(e["dtype"])
    model = program_window_moe.build_model(cfg, dtype)
    model.eval()
    program_window_moe.load_weights(model, cfg, seed, dtype)
    return ServingEngine(model, max_seqs=e["max_seqs"],
                         page_size=e["page_size"], max_len=e["max_len"],
                         dtype=dtype, prefill_chunk=e["prefill_chunk"],
                         num_pages=e["num_pages"])


def compared_layers(cfg):
    """(first sliding layer, first full layer, last layer)."""
    kinds = reference_window_moe.layer_types(cfg)
    return (kinds.index(reference_window_moe.SLIDING),
            kinds.index(reference_window_moe.FULL), len(kinds) - 1)


def hold_rows(eng, loop, traffic, layers):
    """``[(ids the engine has taken in, {layer: (first token held, its
    keys and values)})]`` for ``row_requests`` requests in flight that
    have been prefilled: the longest, the shortest and those midway.  A
    request that has emitted k tokens has taken in its prompt and the
    first k - 1 of them."""
    live = sorted((s for s in loop.live.values()
                   if s.tokens and eng.request(s.rid).sid is not None
                   and not eng.request(s.rid).terminal),
                  key=lambda s: (len(s.prompt) + len(s.tokens), s.rid))
    k = min(len(live), traffic["row_requests"])
    at = sorted({round(i * (len(live) - 1) / max(k - 1, 1))
                 for i in range(k)}, reverse=True)
    return [(np.concatenate([live[i].prompt,
                             np.asarray(live[i].tokens[:-1], np.int32)]),
             program_window_moe.slot_kv(eng, live[i].rid, layers))
            for i in at]


def kept_from(cfg, n_tokens, layer):
    """(the first token a sequence of ``n_tokens`` must still hold in
    ``layer``, the first its next query sees)."""
    if reference_window_moe.layer_types(cfg)[layer] \
            == reference_window_moe.FULL:
        return 0, 0
    seen = max(0, n_tokens + 1 - cfg["sliding_window"])
    ps = cfg["engine"]["page_size"]
    return seen // ps * ps, seen


def window_keys(cfg, steps):
    """Per decode step of ``steps`` (the loop's records): the keys its
    sequences' queries see in a sliding layer."""
    w = cfg["sliding_window"]
    return [sum(min(n, w) for n in r["decode"]) for r in steps if r["decode"]]


def seen_window_sum(cfg, steps, chunks):
    """The keys the tokens of ``steps`` saw in a sliding layer in all:
    decode tokens at their lengths, prefill chunks (``(slot, tokens,
    start)``) token by token."""
    w = cfg["sliding_window"]
    return (sum(window_keys(cfg, steps))
            + sum(flops_window_moe.seen_by_window(start, n, w)
                  for _, n, start in chunks))


def score(cfg, traffic, seed, picked, held, jnp, helpers, control=False):
    """Runs the reference once over each picked request's prompt and
    served tokens and over the tokens of each held request.  Returns the
    widest gap by which a served token's logit lies below the reference's
    best, over the RMS of the reference's logits; the worst held
    request's row gaps — and with ``control`` the same readings for each
    control in the program's place."""
    dtype = jnp.dtype(cfg["engine"]["dtype"])
    rows = max(traffic["answer_lens"])
    layers = compared_layers(cfg)
    # every sequence padded to the engine's longest: one shape a layer
    # kind compiles whatever the lengths (a control's five scorers would
    # else compile five times a handful of lengths)
    bucket = cfg["engine"]["max_len"]
    scorers = {"reference": reference_window_moe.Scorer(cfg, rows,
                                                        bucket=bucket)}
    if control:
        for who, kw in CONTROLS.items():
            kw = kw or dict(top_k=cfg["num_experts_per_tok"] - 1)
            scorers[who] = reference_window_moe.Scorer(cfg, rows,
                                                       bucket=bucket, **kw)
    top = weights_window_moe.top(cfg, seed, dtype)
    seqs = [(np.concatenate([s.prompt,
                             np.asarray(s.tokens[:-1], np.int32)]),
             len(s.prompt) - 1 + rows) for s in picked]
    seqs += [(ids, 0) for ids, _ in held]
    keep = range(len(picked), len(seqs))
    ref_logits, best, kept = [], {}, {}
    for who, scorer in scorers.items():
        hidden, kept[who] = scorer.forward(
            top, lambda n: weights_window_moe.layer(cfg, seed, n, dtype),
            seqs, keep_rows=keep, keep_layers=layers)
        for i, s in enumerate(picked):
            lg = scorer.logits(top, hidden[i], len(s.prompt) - 1)
            if who == "reference":
                ref_logits.append(np.asarray(lg, np.float32)[:len(s.tokens)])
            else:
                best.setdefault(who, []).append(
                    np.asarray(jnp.argmax(lg, -1))[:len(s.tokens)])
            del lg
        del hidden
    worst = {who: 0.0 for who in scorers}
    sq, n, agree = 0.0, 0, 0
    for i, s in enumerate(picked):
        ref, served = ref_logits[i], np.asarray(s.tokens)
        worst["reference"] = max(worst["reference"],
                                 helpers._gap(ref, served))
        agree += int((ref.argmax(-1) == served).sum())
        sq, n = sq + float(np.square(ref).sum()), n + ref.size
        for who in best:
            worst[who] = max(worst[who], helpers._gap(ref, best[who][i]))
    rms = float(np.sqrt(sq / max(n, 1)))
    out = {"served_token_gap": (worst.pop("reference") / rms if n
                                else float("nan")),
           "tokens_scored": int(sum(len(s.tokens) for s in picked)),
           "argmax_agree": agree, "logit_rms": rms}
    out.update({f"{who}_token_gap": gap / rms for who, gap in worst.items()})

    # -- the held rows ---------------------------------------------------------
    sliding, full, last = layers
    ref_rows = kept["reference"]

    def token_median(got, want):
        return float(np.median(helpers._token_gaps(
            got.reshape(len(got), -1), want.reshape(len(want), -1))))

    def worst_gap(rows_of, measure, layer):
        """The worst held request's ``measure`` in ``layer``, of the rows
        ``rows_of(j, i, layer)`` gives as (first token, rows), from the
        first token the next query sees on."""
        worst = float("nan")        # no row held: nothing was compared
        for j, i in enumerate(keep):
            n_tokens = len(seqs[i][0])
            must, seen = kept_from(cfg, n_tokens, layer)
            first, got = rows_of(j, i, layer)
            if first != must or len(got) != n_tokens - first:
                return float("inf")     # an old page kept, a seen one gone
            gap = measure(got[seen - first:], ref_rows[i][layer][seen:])
            worst = gap if worst != worst else max(worst, gap)
        return worst

    def numbers(rows_of):
        by_layer = {n: worst_gap(rows_of, helpers._row_gap, n)
                    for n in (sliding, full)}
        return {"kv_row_gap": max(by_layer.values()),
                "deep_row_gap": worst_gap(rows_of, token_median, last),
                "kv_row_gap_by_layer": by_layer}

    def as_held(who):
        """A control's rows in the engine's place: just what it must hold."""
        def rows_of(j, i, layer):
            first = kept_from(cfg, len(seqs[i][0]), layer)[0]
            return first, kept[who][i][layer][first:]
        return rows_of

    out.update(numbers(lambda j, i, layer: held[j][1][layer]))
    for who in scorers:
        if who != "reference":
            out.update({f"{who}_{name}": value for name, value
                        in numbers(as_held(who)).items()})
    out["row_tokens"] = [len(ids) for ids, _ in held]
    out["rows_held_from"] = [[rows[n][0] for n in layers]
                             for _, rows in held]
    return out


def run(ctx, control=False):
    jax, jnp = ctx.jax, ctx.jnp
    cfg, traffic, seed = ctx.cell["config"], ctx.cell["traffic"], ctx.seed
    base = spec.load_module(ctx.bench, "generators", "closed_loop")
    helpers = spec.load_module(ctx.bench, "generators",
                               "closed_loop_mla_moe")
    program.check_gates()

    log(f"serve: building depth {cfg['num_hidden_layers']}, "
        f"{weights_window_moe.count(cfg) / 1e6:.1f} M parameters "
        f"({flops_window_moe.params(cfg) / 1e6:.1f} M by flops_window_moe)")
    eng = build_engine(cfg, seed, jnp)
    gc.collect()
    log(f"serve: engine built, {bytes_in_use(jax)} B in use, peak "
        f"{memory_peak_bytes(jax)}")
    helpers.warm(eng, cfg, traffic)
    log(f"serve: warmed, {ctx.clock.compiles} programs, "
        f"{bytes_in_use(jax)} B in use")
    loop = base.Loop(eng, base.Dealer(cfg, traffic, seed), 1, ctx.trace)
    ramp_steps = helpers.preroll(loop, traffic)
    warm_programs = ctx.clock.compiles
    log(f"serve: pre-roll done after {len(loop.steps)} steps, the ramp "
        f"after {ramp_steps}; {warm_programs} programs")

    # -- the window ----------------------------------------------------------
    ex = eng.executor
    counters_open = program_window_moe.counters(eng)
    chunks_open, steps_open = len(ex.prefill_events), len(loop.steps)
    t0 = t = time.perf_counter()
    loop.t_open = t0
    ctx.window_started(t0)
    with GcClock() as gc_clock:
        while t - t0 < ctx.seconds:
            t = loop.step()
    t1, loop.t_open = t, None
    counters_close = program_window_moe.counters(eng)
    chunks = ex.prefill_events[chunks_open:]
    steps = loop.steps[steps_open:]
    compiled_in_window = ctx.clock.compiles - warm_programs
    held_bytes = bytes_in_use(jax)
    traced = None
    if ctx.traced:      # the same loop goes on, under the profiler
        at = len(loop.steps)
        with ctx.trace:
            with ctx.trace.span("cb:window"):
                tt0 = t = time.perf_counter()
                while t - tt0 < traffic["trace_seconds"]:
                    t = loop.step()
        traced = base.step_facts(loop, tt0, t)
        traced["decode_window_keys"] = window_keys(cfg, loop.steps[at:])
    # late answers are late, not wrong: every request of the window gets
    # its first token (no new ones are sent meanwhile)
    loop.submitting = False
    while any(s.in_window and not s.stamps for s in loop.live.values()):
        loop.step()
    window_s = t1 - t0
    sent, failed, end_to_end, facts = base.numbers(loop, t0, t1, window_s)

    def moved(a, b):
        if isinstance(a, dict):
            return {k: moved(a[k], b[k]) for k in a}
        if isinstance(a, list):
            return [moved(x, y) for x, y in zip(a, b)]
        return b - a

    counted = moved(counters_open, counters_close)
    counted["pages"]["pool"] = counters_close["pages"]["pool"]
    facts.update(warm_programs=warm_programs,
                 compiled_in_window=compiled_in_window, traced=traced,
                 num_pages=ex.cache.num_pages, experts=counted["experts"],
                 pages=counted["pages"],
                 decode_window_keys=window_keys(cfg, steps),
                 seen_window_sum=seen_window_sum(cfg, steps, chunks),
                 bytes_in_use_at_close=held_bytes, gc_s=gc_clock.seconds,
                 gc_collections=gc_clock.collections)
    log(f"serve: {facts['steps']} steps, {facts['requests_finished']} "
        f"requests finished, {facts['output_tokens']} tokens in "
        f"{window_s:.2f} s; programs compiled inside the window: "
        f"{compiled_in_window}")
    peak = memory_peak_bytes(jax)
    short = sum(len(s.tokens) != s.asked for s in loop.done
                if s.state == ("finished", "length"))
    picked = base.sample(loop, traffic, seed, t0, t1)
    held = hold_rows(eng, loop, traffic, compared_layers(cfg))

    # -- release the engine, then the reference ----------------------------
    loop.eng = None
    del eng, ex
    gc.collect()
    log(f"serve: engine released, {bytes_in_use(jax)} B in use")
    t_ref = time.perf_counter()
    scored = score(cfg, traffic, seed, picked, held, jnp, helpers, control)
    log(f"serve: reference scored {scored['tokens_scored']} tokens of "
        f"{len(picked)} requests and {len(held)} held requests' rows in "
        f"{time.perf_counter() - t_ref:.1f} s: {scored}")
    facts["scored"] = scored
    names = ("served_token_gap", "kv_row_gap", "deep_row_gap")
    mine = {k: scored[k] for k in names}
    mine["short_answers"] = float(short)
    if control:     # what control.py puts through the run's own limits
        facts["readings"] = {"program": mine}
        facts["readings"].update({
            who: {"served_token_gap": scored[f"{who}_token_gap"],
                  "kv_row_gap": scored[f"{who}_kv_row_gap"],
                  "deep_row_gap": scored[f"{who}_deep_row_gap"],
                  "short_answers": 0.0}
            for who in CONTROLS})
    return {"attempted": len(sent), "failed": len(failed),
            "end_to_end": end_to_end,
            "checks": compare.checks(mine, ctx.limits),
            "memory_peak_bytes": peak, "facts": facts}
