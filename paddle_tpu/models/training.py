"""Compiled (and sharded) train steps.

The TPU-native answer to the reference's hybrid-parallel runtime
(SURVEY.md §3.3): instead of per-op dispatch + stream collectives, the
WHOLE train step (forward, backward, optimizer update, grad clip) is one
XLA program.  Parallelism is declared as shardings:

- dp: batch dim sharded over the 'dp' mesh axis; GSPMD turns the grad
  reduction into fused all-reduces over ICI (the EagerReducer analog —
  reference fluid/distributed/collective/reducer.cc).
- tp (mp axis): parameters sharded per Megatron rules
  (models/llama.py llama_shard_rules mirrors fleet/layers/mpu/mp_layers.py);
  GSPMD inserts the row/column-parallel collectives.
- ZeRO-ish sharding: optimizer moments additionally sharded over 'dp'
  (the DygraphShardingOptimizer analog — optimizer states partitioned,
  reference fleet/meta_optimizers/dygraph_optimizer/
  dygraph_sharding_optimizer.py:44).
- remat: jax.checkpoint over decoder layers = the reference's recompute
  (fleet/recompute/recompute.py) without the PyLayer machinery.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..distributed.auto_parallel import ProcessMesh
from ..jit.functional import functional_call, param_tree


def _clip_by_global_norm(grads, grad_clip_norm):
    global_sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads))
    gnorm = jnp.sqrt(global_sq)
    scale = jnp.minimum(1.0, grad_clip_norm / jnp.maximum(gnorm, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)


def _adamw_tree_update(params, grads, m, v, t, lr, beta1, beta2, eps,
                       weight_decay, no_decay_fn, grad_clip_norm=None):
    if grad_clip_norm is not None:
        grads = _clip_by_global_norm(grads, grad_clip_norm)
    b1p = beta1 ** t
    b2p = beta2 ** t
    new_params, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k].astype(jnp.float32)
        mk = beta1 * m[k].astype(jnp.float32) + (1 - beta1) * g
        vk = beta2 * v[k].astype(jnp.float32) + (1 - beta2) * g * g
        mhat = mk / (1 - b1p)
        vhat = vk / (1 - b2p)
        wd = 0.0 if no_decay_fn(k) else weight_decay
        p32 = p.astype(jnp.float32)
        p32 = p32 * (1.0 - lr * wd)
        p32 = p32 - lr * mhat / (jnp.sqrt(vhat) + eps)
        new_params[k] = p32.astype(p.dtype)
        new_m[k] = mk.astype(m[k].dtype)
        new_v[k] = vk.astype(v[k].dtype)
    return new_params, new_m, new_v


def _default_no_decay(name):
    return "norm" in name or name.endswith(".bias") or "layernorm" in name


def _stochastic_round_bf16(x32, key):
    """fp32 -> bf16 with stochastic rounding: add 16 random bits below
    the bf16 mantissa and truncate.  Makes single-copy bf16 training
    unbiased (E[round(x)] = x) — the standard TPU recipe for fitting
    models whose fp32 master weights would not fit HBM."""
    u = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    r = jax.random.randint(key, x32.shape, 0, 1 << 16, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(
        ((u + r) >> 16).astype(jnp.uint16), jnp.bfloat16)


def rules_from_annotations(model, mesh: ProcessMesh):
    """Derive per-param shard rules from the placements already on the
    model's parameters (as stamped by ``shard_tensor`` — e.g. the mpu
    Column/Row/VocabParallel layers), replacing hand-written rule tables.

    The reference's completion pass propagates dist_attrs over the whole
    graph (``auto_parallel/static/completion.py``); on TPU that propagation
    is GSPMD's job — reading the author-placed annotations here is the
    analog of collecting the user's ``shard_tensor`` marks before it runs.
    """
    from jax.sharding import NamedSharding as _NS

    specs = {}
    for name, p in model.named_parameters():
        sh = getattr(p._data, "sharding", None)
        if isinstance(sh, _NS) and sh.mesh == mesh.jax_mesh:
            spec = tuple(sh.spec) + (None,) * (p._data.ndim - len(sh.spec))
            specs[name] = spec
        else:
            specs[name] = (None,) * p._data.ndim

    def rules(name, shape):
        return specs.get(name, (None,) * len(shape))

    return rules


class CompiledTrainStep:
    """One-XLA-program AdamW train step over a Layer.

    step(batch) -> loss; parameters/optimizer state live as jax arrays
    (sharded when a mesh is given) and are written back to the Layer on
    ``sync_to_model()``.
    """

    def __init__(self, model, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
                 weight_decay=0.01, grad_clip_norm=1.0, mesh: ProcessMesh
                 = None, shard_rules=None, dp_axis="dp", zero_opt_states=True,
                 compute_dtype=None, no_decay_fn=_default_no_decay,
                 donate=True, moments_dtype="float32", update_fn=None,
                 loss_fn=None, n_labels=1, moments="mv",
                 master_dtype="float32", state_device=None,
                 remat=False):
        """update_fn(master, grads, m, v, t, lr) -> (new_master, m, v)
        overrides the default AdamW update (grads arrive already clipped).
        loss_fn, when given, makes the step treat the last ``n_labels``
        batch elements as labels: loss = loss_fn(model(*inputs), *labels);
        without it the model itself must return the loss.

        master_dtype="bfloat16_sr" drops the fp32 master copy entirely:
        ONE bf16 parameter tree serves as both compute params and master,
        update math runs fp32 in-step and writes back with stochastic
        rounding (unbiased).  State shrinks from 12 to 8 bytes/param with
        bf16 moments — how a ~1.6B model trains on one 16G chip.
        Reference analog: multi_precision=False adamw, made safe by SR."""
        self.model = model
        self.mesh = mesh
        self.lr = lr
        self._hyper = (beta1, beta2, eps, weight_decay)
        self._t = 0

        params = param_tree(model)
        if compute_dtype is not None:
            from ..core import dtype as dt

            cd = dt.convert_dtype(compute_dtype)
            # Keep only norm-scale params out of the low-precision cast
            # (the norm ops cast them into the stream dtype per-op, so
            # fp32 storage is free precision).  Biases ARE cast: a fp32
            # bias added to a bf16 stream would silently promote every
            # downstream matmul/conv to fp32.
            keep_fp32 = lambda k: "norm" in k  # noqa: E731
            params = {k: (v.astype(cd)
                          if jnp.issubdtype(v.dtype, jnp.floating)
                          and not keep_fp32(k) else v)
                      for k, v in params.items()}
        # jnp.array (not astype): a no-op astype aliases the param buffer,
        # which breaks double-donation in the jitted step.
        from ..core import dtype as _dt

        mdt = _dt.convert_dtype(moments_dtype)
        self._single_copy = master_dtype == "bfloat16_sr"
        if self._single_copy and mesh is not None:
            raise ValueError(
                "master_dtype='bfloat16_sr' is the single-chip "
                "memory-fit mode; with a mesh, shard the fp32 master "
                "over dp instead (zero_opt_states=True) — it is both "
                "cheaper and more precise")
        if self._single_copy:
            # No separate master tree: params ARE the (bf16) master.
            self._master = {}
        else:
            self._master = {k: jnp.array(v, dtype=jnp.float32)
                            for k, v in params.items()}
        # moments_dtype="bfloat16" halves optimizer-state HBM (the
        # reference's multi_precision=False adamw analog); the update math
        # still runs in fp32 (_adamw_tree_update casts per step).
        # Allocate only the moment trees the update rule reads ("mv" for
        # adam-family, "m" for momentum, "none" for sgd) — dead fp32
        # moments on a large model are real HBM.
        self._m = ({k: jnp.zeros_like(v, dtype=mdt)
                    for k, v in params.items()} if moments in ("mv", "m")
                   else {})
        self._v = ({k: jnp.zeros_like(v, dtype=mdt)
                    for k, v in params.items()} if moments == "mv" else {})
        # Copy: self.params must not alias the Layer's live buffers, or
        # donation would delete them out from under the eager model.
        self.params = {k: jnp.array(v) for k, v in params.items()}
        params = self.params

        # -- shardings -----------------------------------------------------
        if mesh is not None:
            if shard_rules == "auto":
                shard_rules = rules_from_annotations(model, mesh)
            rules = shard_rules or (lambda name, shape: (None,) * len(shape))
            self._param_sharding = {
                k: NamedSharding(mesh.jax_mesh,
                                 PartitionSpec(*rules(k, v.shape)))
                for k, v in params.items()}
            self._opt_sharding = {
                k: self._zero_sharding(k, v, rules, dp_axis)
                if zero_opt_states else self._param_sharding[k]
                for k, v in params.items()}
            self._batch_spec = NamedSharding(mesh.jax_mesh,
                                            PartitionSpec(dp_axis))
            self._kernel_shard = self._attention_shard()
            # Place the state.
            self.params = {k: jax.device_put(v, self._param_sharding[k])
                           for k, v in params.items()}
            self._m = {k: jax.device_put(v, self._opt_sharding[k])
                       for k, v in self._m.items()}
            self._v = {k: jax.device_put(v, self._opt_sharding[k])
                       for k, v in self._v.items()}
            self._master = {k: jax.device_put(v, self._opt_sharding[k])
                            for k, v in self._master.items()}
        else:
            self._param_sharding = None
            if state_device is not None:
                # Staged init for models near the HBM limit: the Layer was
                # built on host (jax.default_device(cpu)); move only the
                # training state to the accelerator.  Transfer one tree at
                # a time so host copies can be freed in between.
                put = lambda tree: {k: jax.device_put(v, state_device)  # noqa: E731
                                    for k, v in tree.items()}
                self.params = put(self.params)
                self._m = put(self._m)
                self._v = put(self._v)
                self._master = put(self._master)

        beta1_, beta2_, eps_, wd_ = self._hyper
        model_ref = model
        clip = grad_clip_norm

        if loss_fn is not None:
            def loss_of(p, *batch):
                if n_labels:
                    ins, labs = batch[:-n_labels], batch[-n_labels:]
                else:
                    ins, labs = batch, ()
                out = functional_call(model_ref, p, *ins)
                from ..autograd import engine as _engine
                from ..core.tensor import Tensor as _T

                wrapped = [_T(o) for o in (out if isinstance(
                    out, (tuple, list)) else [out])]
                lab_t = [_T(l) for l in labs]
                with _engine.no_grad():  # jax.grad differentiates, not the tape
                    res = loss_fn(*(wrapped + lab_t))
                return jnp.asarray(res._data
                                   if isinstance(res, _T) else res)
        else:
            def loss_of(p, *batch):
                out = functional_call(model_ref, p, *batch)
                return jnp.asarray(out)

        if remat:
            # Whole-forward rematerialization for models without their
            # own recompute config (BERT/UNet/...): trades a second
            # forward for activation memory, unlocking larger batches.
            loss_of = jax.checkpoint(loss_of)
        self.loss_of = loss_of  # pure (params, *batch) -> scalar loss

        single_copy = self._single_copy

        def apply_update(params, master, m, v, t, lr_val, grads):
            """Shared optimizer body: grads -> new state trees.  Used by
            the plain step and the guarded (anomaly-gated) step."""
            if single_copy:
                # Single-copy bf16 training: fp32 math in-step, write
                # back with stochastic rounding (unbiased), no fp32
                # master tree in HBM.
                master = {k: p.astype(jnp.float32)
                          for k, p in params.items()}
            if update_fn is not None:
                if clip is not None:
                    grads = _clip_by_global_norm(grads, clip)
                newp, new_m, new_v = update_fn(master, grads, m, v, t,
                                               lr_val)
            else:
                # AdamW on fp32 master weights (multi-precision semantics:
                # reference phi/kernels adamw multi_precision path).
                newp, new_m, new_v = _adamw_tree_update(
                    master, grads, m, v, t, lr_val, beta1_, beta2_, eps_,
                    wd_, no_decay_fn, grad_clip_norm=clip)
            if single_copy:
                key = jax.random.fold_in(jax.random.PRNGKey(0x5A),
                                         t.astype(jnp.int32))
                cast_back = {}
                for i, k in enumerate(sorted(newp)):
                    p32 = newp[k].astype(jnp.float32)
                    if params[k].dtype == jnp.bfloat16:
                        cast_back[k] = _stochastic_round_bf16(
                            p32, jax.random.fold_in(key, i))
                    else:
                        cast_back[k] = p32.astype(params[k].dtype)
                return cast_back, {}, new_m, new_v
            cast_back = {k: newp[k].astype(params[k].dtype)
                         for k in params}
            return cast_back, newp, new_m, new_v

        def step(params, master, m, v, t, lr_val, *batch):
            loss, grads = jax.value_and_grad(loss_of)(params, *batch)
            newp, newmaster, new_m, new_v = apply_update(
                params, master, m, v, t, lr_val, grads)
            return newp, newmaster, new_m, new_v, loss

        def guarded(params, master, m, v, t, lr_val, gate, *batch):
            """Anomaly-gated step (training guardian).  ``gate`` is a
            [3] f32 vector: [loss ceiling, loss inject, grad inject]
            (injects are 0.0 when inert — the guard.* fault points).
            The update is applied only where the loss and the global
            grad norm are finite AND the loss stays under the ceiling;
            otherwise every state tree keeps its input value — the
            skip-step is part of the same XLA program, no extra host
            sync."""
            loss, grads = jax.value_and_grad(loss_of)(params, *batch)
            loss = loss + gate[1].astype(loss.dtype)
            grads = {k: g + gate[2].astype(g.dtype)
                     for k, g in grads.items()}
            gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in grads.values())
            gnorm = jnp.sqrt(gsq)
            ok = (jnp.isfinite(loss) & jnp.isfinite(gnorm)
                  & (loss.astype(jnp.float32) <= gate[0]))
            newp, newmaster, new_m, new_v = apply_update(
                params, master, m, v, t, lr_val, grads)

            def sel(new, old):
                # jnp.where never propagates the discarded branch's
                # NaNs, so a poisoned update can't leak through a skip.
                return {k: jnp.where(ok, new[k], old[k]) for k in old}

            return (sel(newp, params), sel(newmaster, master),
                    sel(new_m, m), sel(new_v, v), loss, gnorm, ok)

        self._step_fn = step  # raw body, reused by multi_step
        self._multi = {}

        jit_kwargs = {}
        if mesh is not None:
            # Inputs carry their shardings (device_put above); pin outputs
            # so updated state keeps the declared layout.
            state_sh = (self._param_sharding, self._opt_sharding,
                        self._opt_sharding, self._opt_sharding)
            jit_kwargs["out_shardings"] = state_sh + (None,)
            if donate:
                jit_kwargs["donate_argnums"] = (0, 1, 2, 3)
        elif donate:
            jit_kwargs["donate_argnums"] = (0, 1, 2, 3)
        # multi_step reuses the same donation/out-sharding contract
        self._step_jit_kwargs = dict(jit_kwargs)
        self._step = jax.jit(step, **jit_kwargs)
        guarded_kwargs = dict(jit_kwargs)
        if "out_shardings" in guarded_kwargs:
            # gated state keeps the declared layout; loss/gnorm/ok are
            # replicated scalars
            guarded_kwargs["out_shardings"] = \
                guarded_kwargs["out_shardings"][:-1] + (None, None, None)
        self._guarded = jax.jit(guarded, **guarded_kwargs)

        # -- graph contracts (analysis/) ---------------------------------
        # Registered at build; batch shapes are captured lazily on the
        # first real step (the contract thunk returns None until then,
        # which lint reports as "skipped").  The donation-miss check
        # audits params + fp32 master + BOTH optimizer-moment trees:
        # with donate=False every re-emitted state tree is flagged.
        from ..analysis import ProgramContract, register_program

        self._lint_batch = None
        self._guarded_fn = guarded  # keep the raw fn alive for weakref
        donated = jit_kwargs.get("donate_argnums", ())

        # The registry outlives this object, and it holds the thunks
        # strongly: they reach the step through a weak reference, or the
        # contract would pin params + master + moments in HBM after the
        # last user reference is gone (a second model in the same
        # process then runs out of memory — seen on the v5e).
        import weakref

        me = weakref.ref(self)

        def _args(with_gate):
            def thunk():
                step = me()
                if step is None or step._lint_batch is None:
                    return None

                def tree(t):
                    return jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        t)
                scalar = jax.ShapeDtypeStruct((), jnp.float32)
                gate = ((jax.ShapeDtypeStruct((3,), jnp.float32),)
                        if with_gate else ())
                return (tree(step.params), tree(step._master),
                        tree(step._m), tree(step._v), scalar, scalar) \
                    + gate + step._lint_batch
            return thunk

        register_program(ProgramContract(
            name="train.step", fn=step, args=_args(False),
            donate_argnums=donated))
        register_program(ProgramContract(
            name="train.guarded_step", fn=guarded, args=_args(True),
            donate_argnums=donated))

    def _attention_shard(self):
        """``(mesh, batch_axis, head_axis)`` for ops.nn_ops.kernel_mesh:
        what the Pallas attention kernels must be told while a sharded
        step traces.  Read off what the step holds, not assumed by
        name: the batch axis is the batch sharding's, and the head axis
        is the mesh axis the shard rules put on parameters (tensor
        parallelism splits the attention projections' output dim, i.e.
        the heads), whatever the rules call it.  Axes of size 1 split
        nothing and are left out."""
        def live(entry):
            names = (entry,) if isinstance(entry, str) else (entry or ())
            return tuple(n for n in names
                         if self.mesh.get_dim_size(n) > 1)

        batch = live(self._batch_spec.spec[0])
        heads = sorted({n for sh in self._param_sharding.values()
                        for entry in sh.spec for n in live(entry)}
                       - set(batch))
        if len(heads) > 1:
            raise NotImplementedError(
                f"the shard rules split parameters over mesh axes "
                f"{heads}: the attention kernels cannot tell which one "
                f"splits the heads")
        return (self.mesh.jax_mesh,
                batch[0] if len(batch) == 1 else (batch or None),
                heads[0] if heads else None)

    def _kernel_mesh(self):
        import contextlib

        if self.mesh is None:
            return contextlib.nullcontext()
        from ..ops.nn_ops import kernel_mesh

        return kernel_mesh(*self._kernel_shard)

    def _zero_sharding(self, name, value, rules, dp_axis):
        """Opt-state sharding: param's TP sharding + dp over the first
        still-replicated dim that divides evenly (ZeRO partitioning);
        warns when nothing divides (state stays replicated)."""
        from ..distributed.fleet.sharding import _zero_dim

        spec = list(rules(name, value.shape))
        dp = self.mesh.get_dim_size(dp_axis) \
            if dp_axis in self.mesh.dim_names else 1
        if dp > 1:
            free = [s if pl is None else -1
                    for s, pl in zip(value.shape, spec)]
            dim = _zero_dim(dp, [max(s, 0) for s in free], dp_axis, name)
            if dim is not None and free[dim] > 0:
                spec[dim] = dp_axis
        return NamedSharding(self.mesh.jax_mesh, PartitionSpec(*spec))

    def _capture_lint_batch(self, batch):
        """First-step shape capture for the lazily-argumented train
        contracts (the placed batch is already jnp arrays)."""
        if self._lint_batch is None:
            self._lint_batch = tuple(
                jax.ShapeDtypeStruct(jnp.shape(b), jnp.asarray(b).dtype)
                for b in batch)

    def _place_batch(self, arr):
        arr = jnp.asarray(arr)
        if self.mesh is not None:
            ndim = arr.ndim
            spec = [self._batch_spec.spec[0]] + [None] * (ndim - 1)
            return jax.device_put(
                arr, NamedSharding(self.mesh.jax_mesh,
                                   PartitionSpec(*spec)))
        return arr

    def multi_step(self, k, *batch, stacked=False):
        """Run ``k`` optimizer steps in ONE dispatched XLA program
        (lax.scan over the step body).  Amortizes the per-dispatch
        host cost, which short-step models (ResNet-class) pay once per
        step otherwise.
        ``stacked`` (bool, or one bool per batch element) marks inputs
        carrying a leading ``k`` axis of distinct per-step data; by
        default every element is reused each step (explicit, not
        shape-guessed: a batch whose size equals ``k`` must not be
        silently unstacked).  Returns the last step's loss.  Donation
        and mesh out-shardings follow the constructor's contract
        exactly like ``step``.

        LR schedulers compose: the next ``k`` per-step rates are computed
        on host (advancing the scheduler exactly as ``step`` would) and
        threaded into the scanned body as a step-indexed [k] array, so a
        warmup+decay recipe through ``multi_step`` matches per-step
        execution bit-for-bit.  Loss-dependent schedulers
        (ReduceOnPlateau) cannot be precomputed and still raise."""
        from ..core.tensor import Tensor
        from ..optimizer.lr import LRScheduler, ReduceOnPlateau

        if isinstance(self.lr, ReduceOnPlateau):
            raise ValueError(
                "multi_step cannot precompute a loss-dependent schedule "
                "(ReduceOnPlateau) — use step()")
        batch = [b._data if isinstance(b, Tensor) else b for b in batch]
        if isinstance(stacked, bool):
            stacked = (stacked,) * len(batch)
        else:
            stacked = tuple(bool(s) for s in stacked)
        if len(stacked) != len(batch):
            raise ValueError(f"stacked has {len(stacked)} entries for "
                             f"{len(batch)} batch elements")
        for b, s in zip(batch, stacked):
            if s and (getattr(b, "ndim", 0) == 0 or b.shape[0] != k):
                raise ValueError(
                    f"stacked batch element must have leading dim "
                    f"{k}, got {getattr(b, 'shape', ())}")
        # Advance the scheduler only after every argument check passed — a
        # rejected call must not leave the schedule k steps ahead.
        if isinstance(self.lr, LRScheduler):
            lrs = []
            for _ in range(k):
                lrs.append(float(self.lr()))
                self.lr.step()
            lr_val = jnp.asarray(lrs, jnp.float32)
        else:
            # uniform [k] array keeps one compiled program for both cases
            lr_val = jnp.full((k,), float(self.lr), jnp.float32)
        with jax.enable_x64(False), self._kernel_mesh():
            batch = [self._place_batch(b) for b in batch]
            jitted = self._multi.get((k, stacked))
            if jitted is None:
                raw = self._step_fn

                def k_steps(params, master, m, v, t, lr, *batch):
                    def body(carry, i):
                        params, master, m, v, t = carry
                        per = [jax.lax.dynamic_index_in_dim(
                            b, i, keepdims=False) if s else b
                            for b, s in zip(batch, stacked)]
                        params, master, m, v, loss = raw(
                            params, master, m, v, t, lr[i], *per)
                        return (params, master, m, v, t + 1), loss

                    (params, master, m, v, t), losses = jax.lax.scan(
                        body, (params, master, m, v, t),
                        jnp.arange(k))
                    return params, master, m, v, losses[-1]

                jitted = jax.jit(k_steps, **self._step_jit_kwargs)
                self._multi[(k, stacked)] = jitted
            self._t += k
            # step() pre-increments: iteration i runs with t = t0 + i
            # where t0 is the first step's (1-based) count.
            (self.params, self._master, self._m, self._v, loss) = \
                jitted(self.params, self._master, self._m, self._v,
                       jnp.asarray(self._t - k + 1, jnp.float32),
                       lr_val, *batch)
        return loss

    def step(self, *batch):
        from .. import obs
        from ..core.tensor import Tensor
        from ..optimizer.lr import LRScheduler
        from ..testing import faults

        # Host-boundary fault point: kill-and-resume tests arm this to
        # preempt the train loop between (not inside) XLA dispatches.
        faults.fire("train.step", "before")
        h = obs.handle()
        t0 = h.clock() if h is not None else None
        self._t += 1
        if isinstance(self.lr, LRScheduler):
            lr_val = float(self.lr())
            self.lr.step()
        else:
            lr_val = float(self.lr)
        batch = [b._data if isinstance(b, Tensor) else b for b in batch]
        # The train step needs no 64-bit types; tracing it with x64 off
        # keeps weak-typed ints int32 (XLA-friendly) and lets the pallas
        # flash-attention kernel lower (its mosaic pipeline chokes on the
        # int64 indices that global x64 mode would introduce).
        with obs.span("train.step", cat="train", t=self._t), \
                jax.enable_x64(False), self._kernel_mesh():
            with obs.span("train.place", cat="train"):
                batch = [self._place_batch(b) for b in batch]
                self._capture_lint_batch(batch)
            with obs.span("jit.dispatch", cat="train",
                          program="train.step"):
                (self.params, self._master, self._m, self._v, loss) = \
                    self._step(self.params, self._master, self._m,
                               self._v, jnp.asarray(self._t, jnp.float32),
                               lr_val, *batch)
        if h is not None:
            wall = h.clock() - t0
            h.registry.counter(
                "train_steps_total", "Optimizer steps dispatched").inc()
            h.registry.histogram(
                "train_step_wall_s",
                "Host wall time of one train step").observe(wall)
            obs.perf.sample_hbm("train.step", h)
        faults.fire("train.step", "after")
        return loss

    def guarded_step(self, threshold, *batch):
        """One train step through the in-graph anomaly gate: the update
        is APPLIED only where the loss and the global grad norm are
        finite and the loss does not exceed ``threshold`` (the
        guardian's rolling median+MAD ceiling, ``inf`` to disable);
        otherwise every state tree keeps its previous value — GradScaler
        found_inf semantics: a skipped step leaves params, moments, AND
        the Adam step counter untouched.

        Returns ``(loss, grad_norm, ok)`` as host float/float/bool.
        Fetching them is the one host sync the training loop already
        pays for the loss; the skip decision itself runs inside the
        same XLA program.

        The ``guard.nan_loss`` / ``guard.nan_grad`` / ``guard.loss_spike``
        fault points are polled here (``inject`` action): when armed they
        poison the loss/grads INSIDE the gated program, so harness tests
        exercise the exact production skip path.
        """
        from .. import obs
        from ..core.tensor import Tensor
        from ..optimizer.lr import LRScheduler
        from ..testing import faults

        faults.fire("train.step", "before")
        h = obs.handle()
        t0 = h.clock() if h is not None else None
        l_inj = 0.0
        if faults.poll("guard.nan_loss") is not None:
            l_inj = float("nan")
        else:
            spike = faults.poll("guard.loss_spike")
            if spike is not None:
                l_inj = 1e6 if spike is True else float(spike)
        g_inj = float("nan") \
            if faults.poll("guard.nan_grad") is not None else 0.0
        self._t += 1
        if isinstance(self.lr, LRScheduler):
            lr_val = float(self.lr())
            self.lr.step()
        else:
            lr_val = float(self.lr)
        batch = [b._data if isinstance(b, Tensor) else b for b in batch]
        with obs.span("train.guarded_step", cat="train",
                      t=self._t) as sp, \
                jax.enable_x64(False), self._kernel_mesh():
            with obs.span("train.place", cat="train"):
                batch = [self._place_batch(b) for b in batch]
                self._capture_lint_batch(batch)
            gate = jnp.asarray([threshold, l_inj, g_inj], jnp.float32)
            with obs.span("jit.dispatch", cat="train",
                          program="train.guarded_step"):
                (self.params, self._master, self._m, self._v, loss,
                 gnorm, ok) = self._guarded(
                    self.params, self._master, self._m, self._v,
                    jnp.asarray(self._t, jnp.float32), lr_val, gate,
                    *batch)
        faults.fire("train.step", "after")
        loss_f, gnorm_f, ok_b = float(loss), float(gnorm), bool(ok)
        sp.set(loss=loss_f, ok=ok_b)
        if h is not None:
            wall = h.clock() - t0
            h.registry.counter(
                "train_steps_total", "Optimizer steps dispatched").inc()
            h.registry.histogram(
                "train_step_wall_s",
                "Host wall time of one train step").observe(wall)
            obs.perf.sample_hbm("train.guarded_step", h)
        if not ok_b:
            # The gate kept the old state; the Adam step counter must
            # not advance either (found_inf semantics).
            self._t -= 1
        return loss_f, gnorm_f, ok_b

    def sync_to_model(self):
        """Write current (possibly sharded) params back into the Layer."""
        from ..jit.functional import load_param_tree

        load_param_tree(self.model, self.params)

    def state_dict(self):
        # Copy (sharding-preserving): the live arrays are donated to the
        # next jitted step, which would delete a checkpoint that merely
        # aliased them.
        cp = lambda tree: {k: v.copy() for k, v in tree.items()}  # noqa: E731
        state = {"params": cp(self.params), "master": cp(self._master),
                 "m": cp(self._m), "v": cp(self._v), "t": self._t}
        from ..optimizer.lr import LRScheduler

        if isinstance(self.lr, LRScheduler):
            state["lr_scheduler"] = self.lr.state_dict()
        return state

    def set_state_dict(self, state):
        cp = lambda tree: {k: v.copy() for k, v in tree.items()}  # noqa: E731
        self.params = cp(state["params"])
        self._master = cp(state["master"])
        self._m = cp(state["m"])
        self._v = cp(state["v"])
        self._t = state["t"]
        from ..optimizer.lr import LRScheduler

        if "lr_scheduler" in state and isinstance(self.lr, LRScheduler):
            self.lr.set_state_dict(state["lr_scheduler"])
