"""Self-authored q-blocked VMEM-resident attention kernel
(ops/pallas_kernels/long_attention.py) — llama-regime companion to
short_attention.  Runs on hardware via PT_TESTS_TPU=1.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_kernels.long_attention import (
    _rope_tables, long_attention)

ON_TPU = jax.devices()[0].platform == "tpu"

pytestmark = pytest.mark.skipif(not ON_TPU,
                                reason="pallas TPU kernel")


def _qkv(B=2, H=3, S=1024, D=128):
    key = jax.random.PRNGKey(0)
    mk = lambda i: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), (B, H, S, D), jnp.bfloat16) * 0.3
    return mk(0), mk(1), mk(2)


def _ref(q, k, v, rope=False):
    B, H, S, D = q.shape
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    if rope:
        cos, sin = _rope_tables(S, D, 10000.0, jnp.float32)

        def rot(x):
            d2 = D // 2
            x1, x2 = x[..., :d2], x[..., d2:]
            return jnp.concatenate([x1 * cos[0] - x2 * sin[0],
                                    x1 * sin[0] + x2 * cos[0]], -1)

        qf, kf = rot(qf), rot(kf)
    s = jnp.einsum("bhsd,bhtd->bhst", qf, kf) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhst,bhtd->bhsd", p, vf)


@pytest.mark.parametrize("rope", [False, True])
def test_forward_and_grads_match_einsum(rope):
    q, k, v = _qkv()
    rb = 10000.0 if rope else None
    out = long_attention(q, k, v, None, 256, True, rb)
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)),
        np.asarray(_ref(q, k, v, rope)), atol=6e-3)

    g1 = jax.grad(lambda q, k, v: long_attention(
        q, k, v, None, 256, True, rb).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: _ref(q, k, v, rope).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)), atol=5e-2,
            err_msg=f"d{n}")


def test_block_sizes_agree():
    q, k, v = _qkv(S=512)
    outs = [np.asarray(long_attention(q, k, v, None, bq, True,
                                      None).astype(jnp.float32))
            for bq in (128, 256, 512)]
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-3)
    np.testing.assert_allclose(outs[0], outs[2], atol=2e-3)


def test_sdpa_auto_routes_long_kernel():
    """The dispatch picks the resident-K/V kernel for causal S>=1024
    and matches the einsum path."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    B, S, H, D = 1, 1024, 2, 128
    key = jax.random.PRNGKey(1)
    mk = lambda i: paddle.Tensor(jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), (B, S, H, D), jnp.bfloat16) * 0.3)
    q, k, v = mk(0), mk(1), mk(2)
    from paddle_tpu.ops.nn_ops import _sdpa_plain

    from paddle_tpu.analysis import walker

    jaxpr = jax.make_jaxpr(
        lambda qd, kd, vd: _sdpa_plain(qd, kd, vd, causal=True,
                                       impl="auto"))(
        q._data, k._data, v._data)
    # The kernel announces itself via its body's "fn at file:line"
    # marker; walker.name_inventory surfaces it without string-ifying
    # the jaxpr.
    names = walker.name_inventory(jaxpr)
    assert any("long_attention" in s for s in names), sorted(names)
    out_auto = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    out_ein = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             impl="einsum")
    np.testing.assert_allclose(out_auto.numpy().astype(np.float32),
                               out_ein.numpy().astype(np.float32),
                               atol=6e-3)


def test_llama_save_attn_policy_matches_full():
    """recompute_policy='save_attn' computes the same loss/grads as
    full remat (it only changes what is saved)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import (
        CompiledTrainStep, LlamaConfig, LlamaForCausalLM)

    losses = {}
    for policy in ("full", "save_attn"):
        cfg = LlamaConfig(vocab_size=256, hidden_size=256,
                          intermediate_size=512, num_hidden_layers=2,
                          num_attention_heads=2,
                          num_key_value_heads=2,
                          max_position_embeddings=1024,
                          recompute=True, recompute_policy=policy,
                          scan_layers=True)
        paddle.seed(3)
        model = LlamaForCausalLM(cfg)
        step = CompiledTrainStep(model, lr=1e-3, donate=False)
        ids = np.random.RandomState(0).randint(
            0, 256, (2, 1024)).astype(np.int32)
        losses[policy] = float(step.step(ids, ids))
    np.testing.assert_allclose(losses["full"], losses["save_attn"],
                               rtol=1e-5)
