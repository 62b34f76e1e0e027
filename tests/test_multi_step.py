"""CompiledTrainStep.multi_step: k steps in one dispatched scan
(amortizes the per-dispatch host cost on short-step models)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.training import CompiledTrainStep
from paddle_tpu.nn import functional as F


def _net():
    return paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                                paddle.nn.ReLU(),
                                paddle.nn.Linear(16, 4))


def _clone_state(dst, src):
    dst.params = {k: v.copy() for k, v in src.params.items()}
    dst._master = {k: v.copy() for k, v in src._master.items()}
    dst._m = {k: v.copy() for k, v in src._m.items()}
    dst._v = {k: v.copy() for k, v in src._v.items()}
    dst._t = src._t


def test_multi_step_matches_k_single_steps():
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = rng.randint(0, 4, (16,)).astype(np.int32)
    a = CompiledTrainStep(_net(), lr=1e-2, loss_fn=F.cross_entropy)
    b = CompiledTrainStep(_net(), lr=1e-2, loss_fn=F.cross_entropy)
    _clone_state(b, a)
    for _ in range(5):
        la = a.step(x, y)
    lb = b.multi_step(5, x, y)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)
    for k in a.params:
        np.testing.assert_allclose(np.asarray(a.params[k]),
                                   np.asarray(b.params[k]),
                                   rtol=1e-5, atol=1e-6)


def test_multi_step_stacked_is_explicit():
    """Per-step batches need stacked=True; a batch whose size happens
    to equal k must NOT be silently unstacked (code-review r4)."""
    rng = np.random.RandomState(1)
    step = CompiledTrainStep(_net(), lr=1e-2, loss_fn=F.cross_entropy)
    # batch size == k: trains on the full batch each step
    x = rng.randn(3, 8).astype(np.float32)
    y = rng.randint(0, 4, (3,)).astype(np.int32)
    loss = step.multi_step(3, x, y)
    assert np.isfinite(float(loss))

    xs = rng.randn(4, 6, 8).astype(np.float32)
    ys = rng.randint(0, 4, (4, 6)).astype(np.int32)
    loss = step.multi_step(4, xs, ys, stacked=True)
    assert np.isfinite(float(loss))
    # stacked parity vs single steps over the same 4 batches
    a = CompiledTrainStep(_net(), lr=1e-2, loss_fn=F.cross_entropy)
    b = CompiledTrainStep(_net(), lr=1e-2, loss_fn=F.cross_entropy)
    _clone_state(b, a)
    for i in range(4):
        la = a.step(xs[i], ys[i])
    lb = b.multi_step(4, xs, ys, stacked=True)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)

    with pytest.raises(ValueError):
        step.multi_step(5, xs, ys, stacked=True)  # leading dim != k
    with pytest.raises(ValueError):
        step.multi_step(4, xs, ys, stacked=(True,))  # arity mismatch


def test_multi_step_respects_donate_false():
    """donate=False keeps prior state references alive (code-review
    r4: multi_step used to donate unconditionally)."""
    rng = np.random.RandomState(2)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 4, (8,)).astype(np.int32)
    step = CompiledTrainStep(_net(), lr=1e-2, loss_fn=F.cross_entropy,
                             donate=False)
    before = {k: v for k, v in step.params.items()}
    step.multi_step(3, x, y)
    # the old buffers must still be readable
    for k, v in before.items():
        assert np.isfinite(np.asarray(v)).all()


def test_multi_step_with_lr_scheduler_matches_per_step():
    """Warmup+cosine recipe through multi_step must match per-step
    execution numerically (VERDICT r4 weak #8): the schedule is threaded
    into the scanned body as a step-indexed lr array."""
    from paddle_tpu.optimizer.lr import CosineAnnealingDecay, LinearWarmup

    rng = np.random.RandomState(2)
    x = rng.randn(16, 8).astype(np.float32)
    y = rng.randint(0, 4, (16,)).astype(np.int32)

    def sched():
        return LinearWarmup(CosineAnnealingDecay(0.05, T_max=20),
                            warmup_steps=4, start_lr=0.0, end_lr=0.05)

    a = CompiledTrainStep(_net(), lr=sched(), loss_fn=F.cross_entropy)
    b = CompiledTrainStep(_net(), lr=sched(), loss_fn=F.cross_entropy)
    _clone_state(b, a)
    for _ in range(8):
        la = a.step(x, y)
    lb = b.multi_step(8, x, y)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)
    for k in a.params:
        np.testing.assert_allclose(np.asarray(a.params[k]),
                                   np.asarray(b.params[k]),
                                   rtol=1e-5, atol=1e-6)
    # scheduler state advanced identically on both paths
    np.testing.assert_allclose(float(a.lr()), float(b.lr()), rtol=1e-7)


def test_multi_step_reduce_on_plateau_still_raises():
    from paddle_tpu.optimizer.lr import ReduceOnPlateau

    step = CompiledTrainStep(_net(), lr=ReduceOnPlateau(0.01),
                             loss_fn=F.cross_entropy)
    rng = np.random.RandomState(3)
    with pytest.raises(ValueError, match="loss-dependent"):
        step.multi_step(2, rng.randn(4, 8).astype(np.float32),
                        rng.randint(0, 4, (4,)).astype(np.int32))
