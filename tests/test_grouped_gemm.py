"""Grouped expert GEMM kernel (ops/pallas_kernels/grouped_gemm.py).

Both expert matmuls for all experts in one Pallas kernel over
sort-dispatched [E, C, H] buckets (MegaBlocks-style).  On CPU the
kernel runs in interpreter mode — numerics, routing, and the custom
VJP are validated here; no cell of the benchmark runs it, so it has
no chip number.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import autotune
from paddle_tpu.ops.pallas_kernels import grouped_gemm as gg


def _operands(E=4, C=24, H=32, F=64, dtype=jnp.float32, seed=0):
    r = np.random.default_rng(seed)
    mk = lambda *s, scale=1.0: jnp.asarray(  # noqa: E731
        r.normal(size=s) * scale, dtype)
    return (mk(E, C, H), mk(E, H, F, scale=0.1), mk(E, 1, F, scale=0.1),
            mk(E, F, H, scale=0.1), mk(E, 1, H, scale=0.1))


@pytest.mark.parametrize("shape,act", [
    ((4, 24, 32, 64), "gelu"),
    ((8, 130, 16, 48), "relu"),   # C not a multiple of the row block
    ((2, 7, 8, 8), "silu"),       # tiny everything
])
def test_kernel_matches_einsum_forward(shape, act):
    E, C, H, F = shape
    ops_in = _operands(E, C, H, F)
    ref = gg.einsum_ffn(*ops_in, act)
    out = gg.grouped_ffn(*ops_in, activation=act, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_kernel_matches_einsum_gradients():
    ops_in = _operands(4, 24, 32, 64)

    def loss(impl):
        def f(args):
            return jnp.sum(gg.grouped_ffn(*args, activation="gelu",
                                          impl=impl) ** 2)
        return f

    ge = jax.grad(loss("einsum"))(ops_in)
    gp = jax.grad(loss("pallas"))(ops_in)
    for i, (a, b) in enumerate(zip(ge, gp)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6, err_msg=str(i))


def test_kernel_bf16():
    ops_in = _operands(2, 16, 32, 64, dtype=jnp.bfloat16)
    ref = np.asarray(gg.einsum_ffn(*ops_in, "gelu")).astype(np.float32)
    out = np.asarray(gg.grouped_ffn(*ops_in, activation="gelu",
                                    impl="pallas")).astype(np.float32)
    np.testing.assert_allclose(out, ref, rtol=5e-2, atol=5e-2)


def test_blocks_discards_stale_non_dividing_winner(tmp_path, monkeypatch):
    monkeypatch.setenv("PT_AUTOTUNE_CACHE", str(tmp_path / "cache.json"))
    autotune.clear_memory_cache()
    # A cached winner whose f-block doesn't divide F must be repaired,
    # not obeyed (grid would otherwise drop F blocks / crash).
    autotune.record("grouped_gemm_blocks", (32, 48), (128, 256))
    bc, bf = gg.blocks(32, 48)
    assert 48 % bf == 0
    autotune.clear_memory_cache()


def test_resolve_impl_env_routing(monkeypatch):
    # CPU: auto must fall back to einsum; explicit pallas is honored
    # (interpreter mode); garbage rejected.
    monkeypatch.delenv("PT_GROUPED_GEMM", raising=False)
    assert gg.resolve_impl(128, 256) == "einsum"
    monkeypatch.setenv("PT_GROUPED_GEMM", "pallas")
    assert gg.resolve_impl(128, 256) == "pallas"
    monkeypatch.setenv("PT_GROUPED_GEMM", "bogus")
    with pytest.raises(ValueError, match="PT_GROUPED_GEMM"):
        gg.resolve_impl(128, 256)


def test_supported_shape_gate():
    assert gg.supported(128, 256, on_tpu=True)
    assert not gg.supported(100, 256, on_tpu=True)   # H % 128 != 0
    assert not gg.supported(128, 200, on_tpu=True)   # F % 128 != 0
    assert not gg.supported(128, 256, on_tpu=False)


def test_custom_op_handle_tape_gradients():
    """grouped_expert_gemm as a registered custom op: Tensor call +
    eager tape backward (the MoELayer dense fused path's route)."""
    import paddle_tpu as paddle

    h = gg.handle()
    assert h.spmd_rule is not None
    arrs = _operands(2, 8, 16, 32)
    ts = [paddle.to_tensor(np.asarray(a)) for a in arrs]
    for t in ts:
        t.stop_gradient = False
    out = h(*ts, activation="gelu")
    ref = gg.einsum_ffn(*arrs, "gelu")
    np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    out.sum().backward()
    for t in ts:
        assert t.grad is not None
        assert np.isfinite(t.grad.numpy()).all()


def test_spmd_rule_shards_expert_dim_only():
    spec = gg.grouped_ffn_spmd_rule(None, ("ep",), ("ep",), ("ep",),
                                    ("ep",), ("ep",))
    assert spec == ("ep", None, None)
