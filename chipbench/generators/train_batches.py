"""Training traffic: a fresh seeded batch every step, steps back to back.

The mix's file gives ``batch``, ``seq``, how the token ids are drawn
(``uniform`` over the vocabulary) and ``check_steps``, the first steps
that the reference follows.  Set-up builds ONE compiled step with its
state, drives it through those first steps with the window's own call
and feed, and hands the same object to the window.  A traced run
measures the same window, untraced, and then goes on for
``trace_seconds`` under the profiler.
"""
import gc
import time

import numpy as np

from chipbench import compare, program, reference, weights
from chipbench.harness import GcClock, bytes_in_use, log, memory_peak_bytes


def feed(cfg, traffic, seed, index):
    """Batch ``index`` of the run: ids and next-token labels, int32
    [batch, seq], every row different, the same for the same seed."""
    rng = np.random.Generator(np.random.Philox(key=[int(seed), int(index)]))
    toks = rng.integers(0, cfg["vocab_size"],
                        (traffic["batch"], traffic["seq"] + 1), np.int32)
    return toks[:, :-1], toks[:, 1:]


def build_step(cfg):
    from paddle_tpu.models import CompiledTrainStep

    tr = cfg["train"]
    model = program.build_model(cfg, recompute=tr["recompute"],
                                scan_layers=tr["scan_layers"])
    return model, lambda: CompiledTrainStep(
        model, lr=tr["lr"], beta1=tr["beta1"], beta2=tr["beta2"],
        eps=tr["eps"], weight_decay=tr["weight_decay"],
        grad_clip_norm=tr["grad_clip_norm"],
        compute_dtype=tr["compute_dtype"],
        moments_dtype=tr["moments_dtype"], master_dtype=tr["master_dtype"])


def start_weights(cfg, seed, jnp):
    """The float32 weights both sides start from, holding values of the
    step's compute type (see weights.leaf)."""
    return weights.make(cfg, seed, jnp.float32,
                        cfg["train"]["compute_dtype"])


def state_readers(jax, jnp, cfg, traffic, seed):
    """Small programs over the step's state: the norm of every leaf of
    the first moment; the norm of every leaf's distance from the seeded
    start (made again leaf by leaf inside the program, so the start is
    never held); and host copies of both in bf16, every
    ``direction_rows``-th row of every leaf, which the reference is held
    against once the program is gone (the direction of the gradient and
    of the change, not their norms alone)."""
    leaves = list(weights.leaf_shapes(cfg))
    key, every = weights.key_of(seed), traffic["direction_rows"]

    def start(key, leaf):
        return weights.leaf(cfg, key, leaf, jnp.float32,
                            cfg["train"]["compute_dtype"])

    def of(tree, leaf):
        return tree[program.program_name(leaf)].astype(jnp.float32)

    def norms(tree):
        return {leaf: reference.leaf_norm(of(tree, leaf)) for leaf in leaves}

    def moved(tree, key):
        return {leaf: reference.leaf_norm(of(tree, leaf) - start(key, leaf))
                for leaf in leaves}

    def rows(tree, key=None):
        return {leaf: reference.some_rows(
            of(tree, leaf) - (0 if key is None else start(key, leaf)),
            every).astype(jnp.bfloat16) for leaf in leaves}

    norms, moved, rows = jax.jit(norms), jax.jit(moved), jax.jit(rows)
    return (lambda t: reference.host(norms(t)),
            lambda t: reference.host(moved(t, key)),
            lambda t, from_start: jax.device_get(
                rows(t, key) if from_start else rows(t)))


def controls(cfg, tr, traffic, seed, batches, want, jnp):
    """The reference put in the program's place, broken on purpose, each
    read against the sound reference.  Two controls, one for each
    precision the configuration states: ``int8`` computes every matmul on
    8-bit operands (below the bf16 compute), ``bf16_master`` keeps the
    master weights in bf16 (below the float32 master; the program's own
    ``master_dtype="bfloat16_sr"`` is that step).  One fault:
    ``half_batch`` leaves half of the rows out and takes the mean over
    the rest."""
    def follow(batches, **how):
        return compare.train_numbers(reference.train_steps(
            cfg, tr, lambda: start_weights(cfg, seed, jnp), batches,
            weights.is_norm, keep=traffic["direction_rows"], **how), want)

    half = [(i[: len(i) // 2], l[: len(l) // 2]) for i, l in batches]
    return {"int8": follow(batches, quant="int8"),
            "bf16_master": follow(batches, master_values="bfloat16"),
            "half_batch": follow(half)}


def run(ctx, control=False):
    jax, jnp = ctx.jax, ctx.jnp
    cfg, traffic, seed = ctx.cell["config"], ctx.cell["traffic"], ctx.seed
    tr = cfg["train"]
    program.check_gates()

    log(f"train: building depth {cfg['num_hidden_layers']}, "
        f"{weights.count(cfg) / 1e6:.1f} M parameters")
    model, make_step = build_step(cfg)
    program.load_weights(model, start_weights(cfg, seed, jnp))
    step = make_step()
    tokens_per_step = traffic["batch"] * traffic["seq"]
    n_check = traffic["check_steps"]

    # -- the first steps, through the window's own call and feed ---------
    with jax.enable_x64(False):
        m_norms, moved, copies = state_readers(jax, jnp, cfg, traffic, seed)
    got = {"losses": []}
    for i in range(n_check):
        ids, labels = feed(cfg, traffic, seed, i)
        got["losses"].append(float(step.step(ids, labels)))
        log(f"  step {i + 1}: loss {got['losses'][-1]:.5f}")
        if i == 0:      # m1 = (1 - beta1) * g1, g1 as the optimizer got it
            with jax.enable_x64(False):
                got["grad_norms"] = {k: v / (1 - tr["beta1"])
                                     for k, v in m_norms(step._m).items()}
                got["grad_leaves"] = copies(step._m, from_start=False)
                got["grad_scale"] = 1 / (1 - tr["beta1"])
    with jax.enable_x64(False):
        # the fp32 master where the step keeps one, else the parameters
        state = step._master or step.params
        got["change_norms"] = moved(state)
        got["change_leaves"] = copies(state, from_start=True)
        del state
    warm_programs = ctx.clock.compiles

    # -- the window --------------------------------------------------------
    def one_step(index):
        """feed, call, fetch: the three host times of a step."""
        a = time.perf_counter()
        with ctx.trace.span("cb:feed"):
            ids, labels = feed(cfg, traffic, seed, index)
        b = time.perf_counter()
        with ctx.trace.span("cb:step"):
            out = step.step(ids, labels)
            c = time.perf_counter()
            loss = float(out)                   # the fence
        return loss, (b - a, c - b, time.perf_counter() - c)

    losses, parts = [], []
    t0 = t = time.perf_counter()
    ctx.window_started(t0)
    with GcClock() as gc_clock:
        while t - t0 < ctx.seconds:
            loss, part = one_step(n_check + len(losses))
            losses.append(loss)
            parts.append(part)
            t = time.perf_counter()
    window_s = t - t0
    compiled_in_window = ctx.clock.compiles - warm_programs
    log(f"train: {len(losses)} steps in {window_s:.2f} s, last loss "
        f"{losses[-1]:.4f}, programs compiled inside the window: "
        f"{compiled_in_window}")
    if ctx.traced:      # the same steps go on, under the profiler
        with ctx.trace:
            with ctx.trace.span("cb:window"):
                tt0 = t = time.perf_counter()
                n = n_check + len(losses)
                while t - tt0 < traffic["trace_seconds"]:
                    one_step(n)
                    n, t = n + 1, time.perf_counter()
    peak = memory_peak_bytes(jax)
    failed = sum(not np.isfinite(x) for x in losses)
    walls = [sum(p) for p in parts]
    slowest = sorted(range(len(walls)), key=walls.__getitem__)[-3:]

    # -- release the program, then the reference ---------------------------
    del step, model
    gc.collect()
    log(f"train: program released, {bytes_in_use(jax)} B in use")
    t_ref = time.perf_counter()
    batches = [feed(cfg, traffic, seed, i) for i in range(n_check)]
    want = reference.train_steps(
        cfg, tr, lambda: start_weights(cfg, seed, jnp), batches,
        weights.is_norm, keep=traffic["direction_rows"])
    readings = {"program": compare.train_numbers(got, want)}
    log(f"train: reference followed {n_check} steps and was compared in "
        f"{time.perf_counter() - t_ref:.1f} s")
    checks = compare.checks(
        {k: v[0] for k, v in readings["program"].items()}, ctx.limits)
    if control:
        readings.update(controls(cfg, tr, traffic, seed, batches, want, jnp))

    rate = len(losses) * tokens_per_step / window_s
    return {
        "attempted": len(losses), "failed": int(failed),
        "end_to_end": {"train_tokens_per_s": rate},
        "checks": checks, "memory_peak_bytes": peak,
        "facts": {
            "kind": "train", "window_s": window_s, "steps": len(losses),
            "tokens_per_step": tokens_per_step, "tokens_per_s": rate,
            "step_s_median": float(np.median(walls)),
            # what stalls cost: time over 1.5 x the median step, summed;
            # the three slowest steps as [index, feed, call, fetch]; and
            # the collector's time inside the window
            "step_s_max": float(max(walls)),
            "stall_s": float(sum(max(0.0, w - 1.5 * np.median(walls))
                                 for w in walls)),
            "slowest_steps": [[i, *parts[i]] for i in slowest],
            "gc_s": gc_clock.seconds, "gc_collections": gc_clock.collections,
            "batch": traffic["batch"], "seq": traffic["seq"],
            "warm_programs": warm_programs,
            "compiled_in_window": compiled_in_window,
            "losses_first": got["losses"], "loss_last": losses[-1],
            "readings": {who: {k: v[0] for k, v in nums.items()}
                         for who, nums in readings.items()},
            "worst_leaves": {k: v[1] for k, v in readings["program"].items()
                             if k.endswith("_gap")},
        },
    }
