"""Serving traffic for the latent-attention + routed-experts configuration:
the closed loop of ``closed_loop.py`` — its ``Loop``, ``Dealer``,
``numbers``, ``sample`` and ``step_facts``, by import — around an engine
built from that model, warmed and scored with this configuration's own
weights and plain reference.

What differs from ``closed_loop.py``:

- the model and its weights are made directly in the engine's dtype and
  loaded layer by layer (``program_mla_moe.py``): 9 GB of weights leave
  no room for a float32 copy, nor for a second copy of any kind;
- the warm-up submits as many requests as ``closed_loop.warm`` does (the
  span readers count them to find the window); the engine has ONE decode
  program whatever the batch, so the second group only fills every slot
  once;
- the loop's clients do not start together.  Requests of a few thousand
  tokens that all begin at once are prefilled in the same few steps and
  then decode, finish and are replaced in step with each other, so a
  50-s window sees either a burst of prefills or none (six seeds read
  647-740 tokens/s that way, PERF.md section 6).  A client starts when
  the client started before it has emitted ``ramp_tokens`` tokens (16 =
  the mean answer over the clients): after one mean answer all 64 are in
  flight at phases spread evenly over it, which is where a closed loop
  settles (``preroll``).  The window opens by ``closed_loop``'s own rule,
  which is the one ``program_spans.py`` finds it by: after the step in
  which the ``preroll_requests``-th request finished.  The mix's file sets
  that number so that the ramp is long over by then (``preroll`` there);
- ``score`` runs ``reference_mla_moe.py`` layer by layer, the weights of
  one layer made from the seed at a time, and for ``control.py`` two
  controls: every matmul on int8 operands, and ``num_experts_per_tok - 1``
  experts a token (part of the mathematics left out);
- two more numbers are compared.  ``latent_row_gap``: before the engine
  is released the harness reads the latent rows the engine HOLDS in the
  first layer for ``row_requests`` requests in flight, and the reference
  computes ``[c ; rope(k_pe)]`` over the same tokens: that layer's input is
  the embedding through one norm and one projection, so what differs is
  the projection's arithmetic, the rope and what the pool keeps of the
  row: the norm of the difference over the norm of the reference's rows,
  over all the tokens a request holds, so that a few rows gone wrong (a
  page's edge, a slot) show.  ``deep_row_gap``: the rows of the LAST
  layer, whose input has been through every expert layer but it: served
  greedy tokens hardly see one expert in eight left out, the rows do.
  There the MEDIAN over a request's tokens of each token's own gap is
  taken: a router that computes in bf16 picks another last expert than
  the float32 reference wherever two scores nearly tie (a fifth of the
  tokens by the last layer, each then 5-30 % off: ``deep_rows_off``),
  which is no fault and swamps a norm over all tokens, while leaving out
  part of the mathematics moves EVERY token.  The worst request counts;
- the executor's expert counter (rows each held expert took, decode steps
  counted, experts that took a row) is read at the window's two ends and
  its difference goes into the facts, for the two readers of it.
"""
import gc
import time

import numpy as np

from chipbench import (compare, flops_mla_moe, program, program_mla_moe,
                       reference_mla_moe, spec, weights_mla_moe)
from chipbench.harness import (GcClock, bytes_in_use, log, memory_peak_bytes)


def build_engine(cfg, seed, jnp):
    from paddle_tpu.inference.server import ServingEngine

    e = cfg["engine"]
    dtype = jnp.dtype(e["dtype"])
    model = program_mla_moe.build_model(cfg, dtype)
    model.eval()
    program_mla_moe.load_weights(model, cfg, seed, dtype)
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
    short = min(min(lens), cfg["engine"]["prefill_chunk"])
    drain([eng.submit(prompt(short), max_new_tokens=2)
           for _ in range(traffic["clients"])], 2)


def preroll(loop, traffic):
    """Steps a loop of one client until ``preroll_requests`` have
    finished, as ``closed_loop.run`` does, and starts the other clients
    on the way: the next when the one started last has emitted
    ``ramp_tokens`` tokens (a finished request is replaced at once, as
    ever, whoever sent it).  Returns the number of steps after which
    every client had started.  A mix whose ramp is not over when the
    pre-roll is has the wrong ``preroll_requests``; the run says so and
    ends, since its window would open on fewer sequences than the cell
    names."""
    last, started, ramp_steps = next(iter(loop.live.values())), 1, None
    while len(loop.done) < traffic["preroll_requests"]:
        if (started < traffic["clients"]
                and len(last.tokens) >= traffic["ramp_tokens"]):
            loop.submit()
            last = next(reversed(loop.live.values()))
            started += 1
        loop.step()
        if ramp_steps is None and started == traffic["clients"]:
            ramp_steps = len(loop.steps)
    if ramp_steps is None:
        raise SystemExit(
            f"{traffic['preroll_requests']} requests finished with "
            f"{started} of {traffic['clients']} clients started: "
            f"preroll_requests is too small for this mix's ramp")
    return ramp_steps


def hold_rows(eng, loop, traffic, layers):
    """``[(ids the engine has taken in, the rows it holds for them in
    ``layers``)]`` for ``row_requests`` requests in flight that have been
    prefilled: the longest, the shortest and those midway.  A request
    that has emitted k tokens has taken in its prompt and the first k - 1
    of them."""
    live = sorted((s for s in loop.live.values()
                   if s.tokens and eng.request(s.rid).sid is not None
                   and not eng.request(s.rid).terminal),
                  key=lambda s: (len(s.prompt) + len(s.tokens), s.rid))
    k = min(len(live), traffic["row_requests"])
    at = sorted({round(i * (len(live) - 1) / max(k - 1, 1))
                 for i in range(k)}, reverse=True)
    out = []
    for i in at:
        ids = np.concatenate([live[i].prompt,
                              np.asarray(live[i].tokens[:-1], np.int32)])
        rows = program_mla_moe.slot_rows(eng, live[i].rid, layers)
        out.append((ids, rows[:, :len(ids)]))
    return out


def _row_gap(got, want):
    """The norm of the difference between two sets of rows over the norm
    of ``want``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def _token_gaps(got, want):
    """Per token: the norm of the row's difference over the norm of the
    reference's row."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return (np.linalg.norm(got - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))


def _deep_gap(got, want):
    return float(np.median(_token_gaps(got, want)))


#: a token's last-layer row is "off" when it differs by more than this:
#: far over bf16's own rounding, so its routing differed somewhere above
ROW_OFF = 0.05


def _gap(lg, tokens):
    """The widest gap by which one of ``tokens`` lies below the best
    logit at its position."""
    k = len(tokens)
    return float((lg.max(-1) - lg[np.arange(k), tokens]).max())


def score(cfg, traffic, seed, picked, held, jnp, control=False):
    """Runs the reference once over each picked request's prompt and
    served tokens and over the tokens of each held request.  Returns the
    widest gap by which a served token's logit lies below the reference's
    best, over the RMS of the reference's logits; the worst held
    request's row gap in the first and in the last layer — and with
    ``control`` the same readings for each control in the program's
    place, and the row gap layer by layer."""
    dtype = jnp.dtype(cfg["engine"]["dtype"])
    rows = max(traffic["answer_lens"])
    first, last = 0, cfg["num_hidden_layers"] - 1
    scorers = {"reference": reference_mla_moe.Scorer(cfg, rows)}
    if control:
        scorers["int8"] = reference_mla_moe.Scorer(cfg, rows, quant="int8")
        scorers["top_k_less_1"] = reference_mla_moe.Scorer(
            cfg, rows, top_k=cfg["num_experts_per_tok"] - 1)
    top = weights_mla_moe.top(cfg, seed, dtype)
    seqs = [(np.concatenate([s.prompt,
                             np.asarray(s.tokens[:-1], np.int32)]),
             len(s.prompt) - 1 + rows) for s in picked]
    seqs += [(ids, 0) for ids, _ in held]
    keep = range(len(picked), len(seqs))
    lg, kept = {}, {}
    for who, scorer in scorers.items():
        hidden, kept[who] = scorer.forward(
            top, lambda n: weights_mla_moe.layer(cfg, seed, n, dtype),
            seqs, keep_rows=keep)
        lg[who] = [np.asarray(scorer.logits(top, hidden[i],
                                            len(s.prompt) - 1),
                              np.float32)[:len(s.tokens)]
                   for i, s in enumerate(picked)]
        del hidden
    worst = {who: 0.0 for who in scorers}
    sq, n, agree = 0.0, 0, 0
    for i, s in enumerate(picked):
        ref, served = lg["reference"][i], np.asarray(s.tokens)
        worst["reference"] = max(worst["reference"], _gap(ref, served))
        agree += int((ref.argmax(-1) == served).sum())
        sq, n = sq + float(np.square(ref).sum()), n + ref.size
        for who in scorers:
            if who != "reference":
                worst[who] = max(worst[who],
                                 _gap(ref, lg[who][i].argmax(-1)))
    rms = float(np.sqrt(sq / max(n, 1)))
    out = {"served_token_gap": (worst.pop("reference") / rms if n
                                else float("nan")),
           "tokens_scored": int(sum(len(s.tokens) for s in picked)),
           "argmax_agree": agree, "logit_rms": rms}
    out.update({f"{who}_token_gap": gap / rms for who, gap in worst.items()})

    # -- the held rows, in the first and in the last layer -------------------
    ref_rows = kept["reference"]
    for j, (name, layer, gap) in enumerate(
            (("latent_row_gap", first, _row_gap),
             ("deep_row_gap", last, _deep_gap))):
        # no row held: nothing was compared, which is no pass
        out[name] = max((gap(rows_held[j], ref_rows[i][layer])
                         for i, (_, rows_held) in zip(keep, held)),
                        default=float("nan"))
        for who in scorers:
            if who != "reference":
                out[f"{who}_{name}"] = max(
                    (gap(kept[who][i][layer], ref_rows[i][layer])
                     for i in keep), default=float("nan"))
    out["row_tokens"] = [len(ids) for ids, _ in held]
    # read, not compared: the share of held tokens whose last-layer row
    # is off, and the norm over all tokens that those tokens swamp
    out["deep_rows_off"] = [
        round(float((_token_gaps(rows_held[1], ref_rows[i][last])
                     > ROW_OFF).mean()), 4)
        for i, (_, rows_held) in zip(keep, held)]
    out["deep_row_norm_gap"] = max(
        (_row_gap(rows_held[1], ref_rows[i][last])
         for i, (_, rows_held) in zip(keep, held)), default=float("nan"))
    if control and held:        # layer by layer, the controls alone (the
        i = keep[0]             # program's rows were read in two layers)
        out["row_gap_by_layer"] = {
            who: [round(_row_gap(kept[who][i][n], ref_rows[i][n]), 5)
                  for n in range(last + 1)]
            for who in scorers if who != "reference"}
    return out


def run(ctx, control=False):
    jax, jnp = ctx.jax, ctx.jnp
    cfg, traffic, seed = ctx.cell["config"], ctx.cell["traffic"], ctx.seed
    base = spec.load_module(ctx.bench, "generators", "closed_loop")
    program.check_gates()

    log(f"serve: building depth {cfg['num_hidden_layers']}, "
        f"{weights_mla_moe.count(cfg) / 1e6:.1f} M parameters "
        f"({flops_mla_moe.params(cfg) / 1e6:.1f} M by flops_mla_moe)")
    eng = build_engine(cfg, seed, jnp)
    gc.collect()
    log(f"serve: engine built, {bytes_in_use(jax)} B in use, peak "
        f"{memory_peak_bytes(jax)}")
    warm(eng, cfg, traffic)
    log(f"serve: warmed, {ctx.clock.compiles} programs, "
        f"{bytes_in_use(jax)} B in use")
    loop = base.Loop(eng, base.Dealer(cfg, traffic, seed), 1, ctx.trace)
    ramp_steps = preroll(loop, traffic)
    warm_programs = ctx.clock.compiles
    log(f"serve: pre-roll done after {len(loop.steps)} steps, the ramp "
        f"after {ramp_steps}; {warm_programs} programs")

    # -- the window ----------------------------------------------------------
    experts_open = program_mla_moe.expert_counter(eng)
    t0 = t = time.perf_counter()
    loop.t_open = t0
    ctx.window_started(t0)
    with GcClock() as gc_clock:
        while t - t0 < ctx.seconds:
            t = loop.step()
    t1, loop.t_open = t, None
    experts_close = program_mla_moe.expert_counter(eng)
    compiled_in_window = ctx.clock.compiles - warm_programs
    held_bytes = bytes_in_use(jax)
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
    experts = {k: experts_close[k] - experts_open[k] for k in experts_open}
    facts.update(warm_programs=warm_programs,
                 compiled_in_window=compiled_in_window, traced=traced,
                 num_pages=ex.cache.num_pages, experts=experts,
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
    held = hold_rows(eng, loop, traffic,
                     (0, cfg["num_hidden_layers"] - 1))

    # -- release the engine, then the reference ----------------------------
    loop.eng = None
    del eng, ex
    gc.collect()
    log(f"serve: engine released, {bytes_in_use(jax)} B in use")
    t_ref = time.perf_counter()
    scored = score(cfg, traffic, seed, picked, held, jnp, control)
    log(f"serve: reference scored {scored['tokens_scored']} tokens of "
        f"{len(picked)} requests and {len(held)} held requests' rows in "
        f"{time.perf_counter() - t_ref:.1f} s: {scored}")
    facts["scored"] = scored
    names = ("served_token_gap", "latent_row_gap", "deep_row_gap")
    mine = {k: scored[k] for k in names}
    mine["short_answers"] = float(short)
    if control:     # what control.py puts through the run's own limits
        facts["readings"] = {"program": mine}
        facts["readings"].update({
            who: {"served_token_gap": scored[f"{who}_token_gap"],
                  "latent_row_gap": scored[f"{who}_latent_row_gap"],
                  "deep_row_gap": scored[f"{who}_deep_row_gap"],
                  "short_answers": 0.0}
            for who in ("int8", "top_k_less_1")})
    return {"attempted": len(sent), "failed": len(failed),
            "end_to_end": end_to_end,
            "checks": compare.checks(mine, ctx.limits),
            "memory_peak_bytes": peak, "facts": facts}
