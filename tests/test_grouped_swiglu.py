"""The grouped product a prefill chunk's routed experts run as
(``ops/pallas_kernels/grouped_swiglu.py`` under ``models/moe.py``): the
kernel in interpret mode against ``jax.lax.ragged_dot`` over the same
sorted rows, against a float64 loop written out, and against the batched
product of the decode branch, on routings picked to hit every edge of the
row layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.models import moe
from paddle_tpu.ops.pallas_kernels import grouped_swiglu as gs

H = F = 128
TILE = gs.ROW_TILE


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    # (a layer that holds none of the chosen experts gives zeros)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) or 1.0))


def routing(T, k, experts, seed, first=None):
    """``k`` distinct experts of ``experts`` a token; ``first`` (token ->
    expert or None) forces a token's first choice."""
    rng = np.random.default_rng(seed)
    sel = np.stack([rng.permutation(experts)[:k] for _ in range(T)])
    for t in range(T):
        e = first(t) if first else None
        if e is not None and e not in sel[t]:
            sel[t, 0] = e
    return sel.astype(np.int32)


def without(sel, gone, experts):
    """Every choice of expert ``gone`` moved to one the token has not."""
    sel = sel.copy()
    for t, j in zip(*np.nonzero(sel == gone)):
        sel[t, j] = next(e for e in range(experts)
                         if e != gone and e not in sel[t])
    return sel


# name -> (tokens, experts a token, experts, held, routing, stacked, F)
CASES = {
    "an_expert_with_no_row": dict(
        T=320, sel=lambda: without(routing(320, 2, 16, 0), 3, 16)),
    "an_expert_with_fewer_rows_than_a_tile": dict(
        T=320, sel=lambda: routing(320, 2, 16, 1)),
    "an_expert_with_more_than_two_tiles": dict(
        T=512, sel=lambda: routing(512, 2, 16, 2,
                                   lambda t: 5 if t < 2 * TILE + 9 else None)),
    "rows_whose_expert_is_not_held": dict(
        T=320, held=(4, 5, 6, 7), sel=lambda: routing(320, 4, 16, 3)),
    "no_row_on_any_held_expert": dict(
        T=300, held=(12, 13), sel=lambda: routing(300, 2, 12, 4)),
    "all_rows_on_one_expert": dict(
        T=300, sel=lambda: np.full((300, 1), 9, np.int32)),
    "tokens_not_a_multiple_of_the_tile": dict(
        T=261, sel=lambda: routing(261, 3, 16, 5)),
    "layer_1_of_a_stacked_run": dict(
        T=320, stacked=True, sel=lambda: routing(320, 2, 16, 6)),
    "two_panels_of_the_width": dict(
        T=320, F=2 * F, sel=lambda: routing(320, 2, 16, 7)),
}


def written_out(h, sel, w, held, gate_up, down):
    """The expert layer's held part in numpy float64, pair by pair."""
    h, w, gate_up, down = (np.asarray(a, np.float64)
                           for a in (h, w, gate_up, down))
    out = np.zeros_like(h)
    for t, j in np.ndindex(*sel.shape):
        if sel[t, j] in held:
            e = held.index(sel[t, j])
            g, u = np.split(h[t] @ gate_up[e], 2)
            out[t] += w[t, j] * ((g / (1 + np.exp(-g)) * u) @ down[e])
    return out


@pytest.mark.parametrize("name", list(CASES))
def test_the_grouped_product_is_the_expert_layer(name, monkeypatch):
    case = CASES[name]
    T, sel = case["T"], case["sel"]()
    held = tuple(case.get("held", range(16)))
    width = case.get("F", F)
    keys = jax.random.split(jax.random.PRNGKey(len(name)), 4)
    h = jax.random.normal(keys[0], (T, H), jnp.float32)
    w = jax.random.uniform(keys[1], sel.shape, jnp.float32, 0.1, 1.0)
    gate_up = jax.random.normal(keys[2], (len(held), H, 2 * width)) / 8
    down = jax.random.normal(keys[3], (len(held), width, H)) / 8
    leaves = gate_up, down
    if case.get("stacked"):
        leaves = tuple((jnp.stack([a * 0 + 7, a]), jnp.int32(1))
                       for a in leaves)
    if name == "two_panels_of_the_width":
        monkeypatch.setattr(gs, "_PANEL_BYTES", 2 * 3 * H * F * 4)
        assert gs.panel(H, width, 4) == F
    obs.tracer().configure()
    ragged = moe.routed_experts(h, jnp.asarray(sel), w, held, *leaves)
    monkeypatch.setattr(gs, "_on_tpu", lambda: True)   # interpreted here
    kernel = moe.routed_experts(h, jnp.asarray(sel), w, held, *leaves)
    monkeypatch.setattr(moe, "_BATCHED_EXPERT_ROWS", T)
    batched = moe.routed_experts(h, jnp.asarray(sel), w, held, *leaves)
    want = written_out(h, sel, w, held, gate_up, down)
    assert kernel.dtype == ragged.dtype == jnp.float32
    assert rel(kernel, want) < 1e-6 and rel(ragged, want) < 1e-6
    assert rel(kernel, batched) < 1e-6 and rel(ragged, batched) < 1e-6
    # each grouped call leaves its static sizes behind, the batched none
    sizes = [s.args for s in obs.tracer().spans
             if s.name == "experts.grouped"]
    tiles = (sel.size + len(held) * (TILE - 1)) // TILE
    assert sizes == [
        dict(pairs=sel.size, row_tile=1, tiles=sel.size, kernel=False),
        dict(pairs=sel.size, row_tile=TILE, tiles=tiles, kernel=True)]


@pytest.mark.parametrize("name", list(CASES))
def test_every_row_tile_belongs_to_one_expert(name):
    """The layout the kernel is handed: an expert's rows lie together from
    a tile's first row on, token by token, filled up to whole tiles with
    rows no pair points at; a tile past the live ones holds none; a pair
    whose expert is not held lies one past the last row."""
    case = CASES[name]
    sel = case["sel"]()
    held = tuple(case.get("held", range(16)))
    lay = {k: np.asarray(v) for k, v in
           moe.sorted_rows(jnp.asarray(sel), held, TILE).items()}
    rows = len(lay["token"])
    count = np.array([(sel == e).sum() for e in held])
    assert rows == (sel.size + len(held) * (TILE - 1)) // TILE * TILE
    assert len(lay["tile_expert"]) == rows // TILE
    assert np.array_equal(lay["group_rows"], -(-count // TILE) * TILE)
    assert lay["live"] == lay["group_rows"].sum() // TILE
    assert np.array_equal(
        lay["tile_expert"][:lay["live"]],
        np.repeat(np.arange(len(held)), lay["group_rows"] // TILE))
    kept = np.isin(sel, held)
    assert (lay["place"][~kept] == rows).all()
    t, j = np.nonzero(kept)
    place = lay["place"][t, j]
    first = np.cumsum(lay["group_rows"]) - lay["group_rows"]
    for n, e in enumerate(held):
        mine = place[sel[t, j] == e]
        assert np.array_equal(mine, first[n] + np.arange(count[n]))
    assert np.array_equal(lay["token"][place], t)
    filling = np.setdiff1d(np.arange(rows), place)
    assert not lay["token"][filling].any()


def test_a_panel_fits_vmem_twice_at_both_cells_widths():
    """Trinity-Mini's experts whole (2048 x 2 x 1024 and 1024 x 2048: 25 MB
    double-buffered), sarvam's in four panels of 512 (4096-wide rows)."""
    assert gs.panel(2048, 1024, 2) == 1024
    assert gs.panel(4096, 2048, 2) == 512
    assert gs.supported(2048, 1024, True) and gs.supported(4096, 2048, True)
    assert not gs.supported(2048, 1024, False)
    assert not gs.supported(64, 32, True)
