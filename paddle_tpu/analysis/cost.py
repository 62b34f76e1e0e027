"""Analytical jaxpr cost model — FLOPs, HBM bytes, arithmetic intensity.

The reference profiler ships op-level FLOP/memory statistics; the
jax_graft analog walks a program's jaxpr (through ``pjit``/``scan``/
``custom_vjp``/``shard_map`` sub-jaxprs, same recursion contract as the
linter checks) and prices every equation:

* ``dot_general`` — ``2 · batch · lhs_free · rhs_free · contract`` from
  ``dimension_numbers`` and the operand avals;
* ``conv_general_dilated`` — ``2 · out_elems · kernel_elems / C_out``
  (each output element contracts one kernel's worth of inputs per
  output channel);
* elementwise arithmetic / reductions — one FLOP per element (output
  elements for maps, input elements for reductions), over an explicit
  primitive set so the count is deterministic across refactors;
* ``scan`` bodies are priced once and multiplied by the trip count
  (``length``); ``while`` bodies are priced for a single iteration (the
  trip count is not static); ``cond`` takes the most expensive branch.

Byte accounting is the roofline numerator: program inputs + outputs
(every train/serve step streams its operands through HBM once) plus the
largest intermediate as a working-set estimate — all via the walker's
``_aval_nbytes``.  ``shard_map`` bodies carry per-shard shapes, so every
figure is per chip.

``transformer_flops_per_token`` hosts the closed-form 6N + attention
estimate the models' ``flops_per_token`` share; keeping one copy here
is what lets tests assert model-vs-cost-model agreement to the digit.
"""
from __future__ import annotations

import dataclasses

from .walker import _as_jaxpr, _aval_nbytes, sub_jaxprs

#: One FLOP per OUTPUT element.
ELEMENTWISE_FLOP_PRIMS = {
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs",
    "sign", "floor", "ceil", "round", "exp", "exp2", "expm1", "log",
    "log1p", "tanh", "logistic", "sqrt", "rsqrt", "cbrt", "square",
    "pow", "integer_pow", "erf", "erfc", "erf_inv", "sin", "cos",
    "tan", "asin", "acos", "atan", "atan2", "sinh", "cosh", "atanh",
    "asinh", "acosh", "nextafter", "clamp", "select_n",
}

#: One FLOP per INPUT element (an n-ary tree reduce is n-1 ops ~= n).
REDUCTION_FLOP_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "argmax", "argmin", "cumsum", "cumprod", "cumlogsumexp", "cummax",
    "cummin", "reduce_precision", "psum", "psum_invariant",
}


def transformer_flops_per_token(num_params, num_layers, hidden_size,
                                seq_len):
    """Megatron-style fwd+bwd FLOPs per token: ``6·N`` for the parameter
    GEMMs plus ``12·L·H·S`` for attention score/value matmuls.  This is
    the single home of the estimate the models' ``flops_per_token``
    share (remat's extra forward is hardware overhead, deliberately not
    counted as useful FLOPs)."""
    return (6 * int(num_params)
            + 12 * int(num_layers) * int(hidden_size) * int(seq_len))


@dataclasses.dataclass
class CostReport:
    """Analytical cost of one program at fixed shapes.

    ``flops`` decomposes into matmul/conv/elementwise; ``hbm_bytes`` is
    inputs + outputs + the largest-intermediate working-set estimate.
    """

    flops: int = 0
    matmul_flops: int = 0
    conv_flops: int = 0
    elementwise_flops: int = 0
    bytes_in: int = 0
    bytes_out: int = 0
    bytes_peak_intermediate: int = 0
    eqns: int = 0
    by_primitive: dict = dataclasses.field(default_factory=dict)

    @property
    def hbm_bytes(self) -> int:
        return self.bytes_in + self.bytes_out + self.bytes_peak_intermediate

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per HBM byte — against the machine ridge point this
        classifies the program compute- vs bandwidth-bound."""
        return self.flops / max(self.hbm_bytes, 1)

    def asdict(self) -> dict:
        d = dataclasses.asdict(self)
        d["hbm_bytes"] = self.hbm_bytes
        d["arithmetic_intensity"] = round(self.arithmetic_intensity, 4)
        return d

    def __str__(self):
        return (f"CostReport(flops={self.flops:.3e}, "
                f"hbm_bytes={self.hbm_bytes:.3e}, "
                f"intensity={self.arithmetic_intensity:.1f} flop/B, "
                f"eqns={self.eqns})")


def _prod(it):
    out = 1
    for v in it:
        out *= int(v)
    return out


def _out_elems(eqn):
    aval = getattr(eqn.outvars[0], "aval", None)
    shape = getattr(aval, "shape", None)
    return _prod(shape) if shape is not None else 0


def _in_elems(eqn):
    aval = getattr(eqn.invars[0], "aval", None)
    shape = getattr(aval, "shape", None)
    return _prod(shape) if shape is not None else 0


def _dot_general_flops(eqn):
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = _prod(lhs[i] for i in lb)
    contract = _prod(lhs[i] for i in lc)
    lhs_free = _prod(lhs[i] for i in range(len(lhs))
                     if i not in set(lb) | set(lc))
    rhs_free = _prod(rhs[i] for i in range(len(rhs))
                     if i not in set(_rb) | set(rc))
    return 2 * batch * lhs_free * rhs_free * contract


def _conv_flops(eqn):
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    c_out = rhs[dn.rhs_spec[0]]
    kernel_elems = _prod(rhs)
    # Each output element contracts C_in/groups · prod(kernel_spatial)
    # inputs = kernel_elems / C_out (feature_group_count already shrinks
    # the kernel's in-channel dim).
    return 2 * _out_elems(eqn) * (kernel_elems // max(c_out, 1))


class _Acc:
    __slots__ = ("matmul", "conv", "elem", "eqns", "by_prim")

    def __init__(self):
        self.matmul = 0
        self.conv = 0
        self.elem = 0
        self.eqns = 0
        self.by_prim = {}

    def add(self, prim, kind, flops, mult):
        flops = int(flops) * mult
        if kind == "matmul":
            self.matmul += flops
        elif kind == "conv":
            self.conv += flops
        else:
            self.elem += flops
        if flops:
            self.by_prim[prim] = self.by_prim.get(prim, 0) + flops

    @property
    def total(self):
        return self.matmul + self.conv + self.elem

    def merge(self, other):
        self.matmul += other.matmul
        self.conv += other.conv
        self.elem += other.elem
        self.eqns += other.eqns
        for k, v in other.by_prim.items():
            self.by_prim[k] = self.by_prim.get(k, 0) + v


def _walk(jaxpr, mult, acc):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        acc.eqns += 1
        if name == "scan":
            trip = int(eqn.params.get("length", 1))
            for sub in sub_jaxprs(eqn):
                _walk(sub, mult * trip, acc)
            continue
        if name == "cond":
            # Worst-case branch: price each standalone, keep the max.
            best = None
            for sub in sub_jaxprs(eqn):
                branch = _Acc()
                _walk(sub, mult, branch)
                if best is None or branch.total > best.total:
                    best = branch
            if best is not None:
                acc.merge(best)
            continue
        if name == "dot_general":
            acc.add(name, "matmul", _dot_general_flops(eqn), mult)
        elif name == "conv_general_dilated":
            acc.add(name, "conv", _conv_flops(eqn), mult)
        elif name in ELEMENTWISE_FLOP_PRIMS:
            acc.add(name, "elem", _out_elems(eqn), mult)
        elif name in REDUCTION_FLOP_PRIMS:
            acc.add(name, "elem", _in_elems(eqn), mult)
        # pjit / custom_vjp / shard_map / remat / while bodies: same
        # multiplier (a while trip count is not static — priced once).
        for sub in sub_jaxprs(eqn):
            _walk(sub, mult, acc)


def estimate_cost(jaxpr) -> CostReport:
    """Price a ClosedJaxpr (or raw Jaxpr) into a :class:`CostReport`."""
    from .walker import max_intermediate_bytes

    j = _as_jaxpr(jaxpr)
    if j is None:
        raise TypeError(f"not a jaxpr: {type(jaxpr)!r}")
    acc = _Acc()
    _walk(j, 1, acc)
    bytes_in = sum(_aval_nbytes(v.aval)
                   for v in list(j.invars) + list(j.constvars))
    bytes_out = sum(_aval_nbytes(v.aval) for v in j.outvars)
    peak = int(max_intermediate_bytes(jaxpr)[0])
    return CostReport(
        flops=acc.total, matmul_flops=acc.matmul, conv_flops=acc.conv,
        elementwise_flops=acc.elem, bytes_in=int(bytes_in),
        bytes_out=int(bytes_out), bytes_peak_intermediate=peak,
        eqns=acc.eqns, by_primitive=dict(sorted(acc.by_prim.items())))


def estimate_fn_cost(fn, *args, **kwargs) -> CostReport:
    """Convenience: trace ``fn`` at the given example args (arrays or
    ShapeDtypeStructs) and price the resulting jaxpr."""
    import functools

    import jax

    if kwargs:
        fn = functools.partial(fn, **kwargs)
    return estimate_cost(jax.make_jaxpr(fn)(*args))
