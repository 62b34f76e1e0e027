"""The plain reference against the program's own model at a tiny
grouped-query size on the CPU, both in float32; and the int8 control
is the same arithmetic with rounded operands."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program, reference, spec, weights

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = json.load(open(os.path.join(HERE, "bench", "configs", "tiny-train.json")))


@pytest.fixture(scope="module")
def setup():
    import paddle_tpu as paddle

    w = weights.make(CFG, 3_000_000_001, jnp.float32)
    model = program.build_model(CFG)
    program.load_weights(model, w)
    model.eval()
    ids = np.random.RandomState(0).randint(0, CFG["vocab_size"], (2, 24))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int64)))._data)
    return w, ids, got


def test_config_is_grouped_query():
    assert CFG["num_key_value_heads"] < CFG["num_attention_heads"]


def test_forward_logits_agree(setup):
    w, ids, got = setup
    with jax.enable_x64(False):
        for row in range(ids.shape[0]):
            hid = reference.hidden_states(CFG, w, jnp.asarray(ids[row], jnp.int32))
            want = np.asarray(reference.logits(w, hid))
            np.testing.assert_allclose(got[row], want, atol=2e-5, rtol=1e-4)


def test_loss_agrees(setup):
    import paddle_tpu as paddle

    w, ids, _ = setup
    labels = np.roll(ids, -1, axis=1)
    model = program.build_model(CFG)
    program.load_weights(model, w)
    with paddle.no_grad():
        got = float(model(paddle.to_tensor(ids.astype(np.int64)),
                          labels=paddle.to_tensor(labels.astype(np.int64))))
    with jax.enable_x64(False):
        want = float(reference.batch_loss(
            CFG, w, jnp.asarray(ids, jnp.int32), jnp.asarray(labels, jnp.int32)))
    assert got == pytest.approx(want, rel=1e-5)


def test_weights_repeat_and_differ_by_seed():
    a = weights.make(CFG, 5, jnp.float32)
    b = weights.make(CFG, 5, jnp.float32)
    c = weights.make(CFG, 2**31 + 5, jnp.float32)
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["embed"] == c["embed"]).all()
    assert float(jnp.std(a["head"])) == pytest.approx(0.02, rel=0.05)
    assert (a["norm"] == 1).all()
    with jax.enable_x64(False):
        one = jax.jit(lambda k: weights.leaf(CFG, k, "layers.1.up", jnp.float32))(
            weights.key_of(5))
    assert (one == a["layers.1.up"]).all()
    assert weights.count(CFG) == sum(int(np.prod(v.shape)) for v in a.values())


def test_every_parameter_gets_a_leaf():
    model = program.build_model(CFG)
    names = {program.program_name(k) for k in weights.leaf_shapes(CFG)}
    assert names == {n for n, _ in model.named_parameters()}


def test_int8_control_rounds_the_operands():
    x = jnp.asarray(np.random.RandomState(1).randn(8, 64), jnp.float32)
    w = jnp.asarray(np.random.RandomState(2).randn(64, 32), jnp.float32)
    exact, low = reference.mm(x, w), reference.mm(x, w, "int8")
    err = float(jnp.abs(exact - low).max() / jnp.abs(exact).max())
    assert 1e-4 < err < 5e-2
    with pytest.raises(ValueError):
        reference.mm(x, w, "fp4")


def test_adamw_is_the_textbook_update():
    hyper = {"lr": 0.1, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
             "weight_decay": 0.01}
    w, g = {"a": jnp.float32(2.0), "norm": jnp.float32(1.0)}, \
        {"a": jnp.float32(0.5), "norm": jnp.float32(-0.25)}
    zeros = {k: jnp.float32(0) for k in w}
    new, m, v = reference.adamw(hyper, lambda k: k == "norm", jnp.float32(1),
                                w, zeros, zeros, g)
    # first step: mhat = g, vhat = g^2, so the move is lr * sign(g)
    assert float(new["a"]) == pytest.approx(2.0 * (1 - 0.1 * 0.01) - 0.1, rel=1e-6)
    assert float(new["norm"]) == pytest.approx(1.0 + 0.1, rel=1e-6)   # no decay
    assert float(m["a"]) == pytest.approx(0.05) and float(v["a"]) == pytest.approx(0.00025)
