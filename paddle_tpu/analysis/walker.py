"""Recursive jaxpr traversal + inventory primitives.

One walker for the whole subsystem: every check (and the tests that
migrated off their private copies) goes through :func:`iter_eqns`, which
descends into sub-jaxprs wherever they hide in ``eqn.params`` —
``ClosedJaxpr`` values (pjit/scan/custom_vjp/shard_map/remat), raw
``Jaxpr`` values, and lists/tuples of either (cond branches).
"""
from __future__ import annotations

import numpy as np

# Collective primitives audited per shard_map body.  jax lowers pmean
# to psum+div, and inside a shard_map body (the installed jax 0.9, with
# varying-manual-axes checking on) a psum traces as "psum_invariant";
# the inventory reports it as "psum", the name contracts are written in.
COLLECTIVE_PRIMS = {
    "psum", "psum_invariant", "all_to_all", "all_gather",
    "all_gather_invariant", "reduce_scatter", "ppermute", "pmax", "pmin",
    # NB: shard_map's `pbroadcast`/`pvary` are replication-annotation
    # casts, not wire collectives — deliberately excluded.
}
_NORMALIZE = {"psum_invariant": "psum"}

# Primitives that force a host round-trip inside a device program.
HOST_SYNC_PRIMS = {
    "debug_callback", "pure_callback", "io_callback", "callback",
    "outside_call", "host_callback", "infeed", "outfeed", "debug_print",
}


def _as_jaxpr(obj):
    """Jaxpr-or-None from a params value (ClosedJaxpr has .jaxpr.eqns,
    raw Jaxpr has .eqns directly)."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns"):
        return obj
    return None


def sub_jaxprs(eqn):
    """Yield every sub-jaxpr reachable from one equation's params."""
    for v in eqn.params.values():
        for cand in (v if isinstance(v, (list, tuple)) else [v]):
            j = _as_jaxpr(cand)
            if j is not None:
                yield j


def iter_eqns(jaxpr):
    """Depth-first over ALL equations, descending into sub-jaxprs."""
    j = _as_jaxpr(jaxpr)
    if j is None:
        return
    for eqn in j.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _aval_elems(aval):
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape or (1,)))


def _aval_nbytes(aval):
    dt = getattr(aval, "dtype", None)
    itemsize = np.dtype(dt).itemsize if dt is not None else 1
    return _aval_elems(aval) * itemsize


def iter_vars(jaxpr):
    """(eqn, var, aval) for every in/out variable of every equation."""
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                yield eqn, v, aval


def max_intermediate_elems(jaxpr):
    """Largest array (element count) anywhere in the jaxpr tree — the
    generalization of the old test-local ``_max_var_size`` walkers."""
    best = 0
    for _, _, aval in iter_vars(jaxpr):
        best = max(best, _aval_elems(aval))
    return best


def max_intermediate_bytes(jaxpr):
    """(nbytes, shape, dtype, primitive_name) of the largest array."""
    best = (0, (), None, None)
    for eqn, _, aval in iter_vars(jaxpr):
        nb = _aval_nbytes(aval)
        if nb > best[0]:
            best = (nb, tuple(aval.shape), aval.dtype, eqn.primitive.name)
    return best


def primitive_inventory(jaxpr):
    """{primitive_name: count} over the whole tree."""
    inv: dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        n = eqn.primitive.name
        inv[n] = inv.get(n, 0) + 1
    return inv


def collective_inventory(jaxpr):
    """{collective: count}, psum_invariant counted as psum."""
    inv: dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        n = eqn.primitive.name
        if n in COLLECTIVE_PRIMS:
            n = _NORMALIZE.get(n, n)
            inv[n] = inv.get(n, 0) + 1
    return inv


def pallas_kernels(jaxpr):
    """``[(kernel, interpreted)]`` for every ``pallas_call`` in the
    tree: ``kernel`` is the body's ``"fn at file:line"`` source marker
    (how a Pallas kernel announces itself under the installed jax) and
    ``interpreted`` says whether the call runs in the Pallas
    interpreter instead of being compiled by Mosaic."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            info = eqn.params["jaxpr"].debug_info
            out.append((str(info.func_src_info),
                        bool(eqn.params["interpret"])))
    return out


def name_inventory(jaxpr):
    """Set of name-ish strings in the tree: primitive names, ``name``
    params (pjit bodies), and pallas kernel src markers — the structured
    replacement for ``assert "..." in str(jaxpr)``."""
    names: set[str] = set()
    for eqn in iter_eqns(jaxpr):
        names.add(eqn.primitive.name)
        v = eqn.params.get("name")
        if v is not None:
            names.add(v if isinstance(v, str) else str(v))
    names.update(k for k, _ in pallas_kernels(jaxpr))
    return names
