"""Training-loop guardian: anomaly detection, skip-step escalation, and
automatic rollback to the last committed checkpoint.

Three altitudes:

1. State-machine units — GuardianPolicy validation, the rolling
   median+MAD spike monitor, classify/observe escalation (skip budget,
   exponential backoff on rollback, abort bundle).
2. Compiled path — ``CompiledTrainStep.guarded_step`` gates the update
   in-graph: a poisoned step must leave params, moments, AND the Adam
   step counter bit-identical (GradScaler found_inf semantics), and an
   injected anomaly burst (``PT_FAULTS`` value faults) must end in a
   rollback after which the run finishes IDENTICAL to an uninjected
   run (the recovery-parity acceptance test).
3. Eager (hapi) path — ``Model.fit(..., guardian=...)`` skip/rollback,
   and the GradScaler interplay: a skipped step moves the scale
   schedule exactly like a found-inf step while touching nothing else.

Fault-arming note: ``poll`` keeps a per-spec hit counter and returns on
the first firing spec, so N DUPLICATE specs ``point:before:k=inject``
fire on N consecutive polls k, k+1, ..., k+N-1 — how the e2e tests
inject a deterministic anomaly *burst* from one PT_FAULTS string.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.ckpt_commit import CheckpointManager
from paddle_tpu.models.training import CompiledTrainStep
from paddle_tpu.testing import faults
from paddle_tpu.training import (
    Decision, GuardedTrainStep, GuardianAbort, GuardianPolicy,
    TrainingGuardian,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm_all()
    yield
    faults.disarm_all()


# -- state-machine units -----------------------------------------------------

def test_policy_validation():
    with pytest.raises(ValueError):
        GuardianPolicy(window=1)
    with pytest.raises(ValueError):
        GuardianPolicy(min_history=0)
    with pytest.raises(ValueError):
        GuardianPolicy(budget_backoff=0.0)
    with pytest.raises(ValueError):
        GuardianPolicy(budget_backoff=1.5)


def test_spike_threshold_warmup_and_flat_window():
    g = TrainingGuardian(GuardianPolicy(window=8, min_history=4,
                                        spike_factor=10.0))
    # warmup: no history -> monitor open (inf ceiling)
    assert g.spike_threshold() == float("inf")
    for v in (2.0, 2.1, 1.9):
        assert g.observe(v) is Decision.OK
    assert g.spike_threshold() == float("inf")  # 3 < min_history
    assert g.observe(2.0) is Decision.OK
    thr = g.spike_threshold()
    assert np.isfinite(thr)
    # robust-z ceiling sits well above the window but catches a 10x jump
    assert 2.1 < thr < 21.0
    # perfectly flat window: MAD collapses to 0, the relative floor
    # keeps the ceiling finite and off the median
    gf = TrainingGuardian(GuardianPolicy(window=8, min_history=4))
    for _ in range(4):
        gf.observe(5.0)
    t = gf.spike_threshold()
    assert np.isfinite(t) and t > 5.0


def test_classify_names_the_offending_monitor():
    g = TrainingGuardian(GuardianPolicy(window=8, min_history=2))
    assert g.classify(float("nan")) == "nan_loss"
    assert g.classify(float("inf")) == "nan_loss"
    assert g.classify(1.0, grad_norm=float("nan")) == "nan_grad"
    assert g.classify(100.0, threshold=10.0) == "loss_spike"
    assert g.classify(1.0, grad_norm=2.0, threshold=10.0) is None


def _np_state(seed):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(4, 6).astype(np.float32),
            "b": rng.randn(6).astype(np.float32)}


def test_escalation_skip_rollback_backoff_abort(tmp_path):
    """The full ladder on a plain numpy 'model': skips up to the
    budget, rollback restores the committed state and TIGHTENS the
    budget (exponential backoff on tolerance), second exhaustion with
    the rollback budget spent aborts with the diagnostic bundle."""
    live = _np_state(7)          # drifted live state
    committed = _np_state(1)     # what the checkpoint holds
    mgr = CheckpointManager(str(tmp_path), world_size=1, rank=0)
    mgr.save(dict(committed), 3)
    applied = {}
    g = TrainingGuardian(
        GuardianPolicy(window=8, min_history=2, skip_budget=2,
                       budget_backoff=0.5, rollback_budget=1),
        manager=mgr,
        state_fn=lambda: {k: np.zeros_like(v) for k, v in live.items()},
        apply_fn=applied.update,
        reseed_fn=lambda step: applied.setdefault("_reseed", step))
    for v in (1.0, 1.1):
        assert g.observe(v) is Decision.OK
    nan = float("nan")
    assert g.observe(nan) is Decision.SKIP
    assert g.observe(nan) is Decision.SKIP
    assert g.observe(nan) is Decision.ROLLBACK
    assert g.rollback() == 3
    # the loader filled the template from the committed step
    for k, v in committed.items():
        np.testing.assert_array_equal(np.asarray(applied[k]), v)
    assert applied["_reseed"] == 3
    assert g.rollbacks == 1
    assert g._skip_budget == 1  # 2 * 0.5 backoff
    # tightened budget: one skip, then the rollback budget is spent
    assert g.observe(nan) is Decision.SKIP
    with pytest.raises(GuardianAbort) as ei:
        g.observe(nan)
    b = ei.value.bundle
    assert b["monitor"] == "nan_loss"
    assert b["rollbacks"] == 1 and b["skips"] == 3
    assert b["loss_window"] == [1.0, 1.1]
    assert any(kind == "rollback" for _, kind, _ in b["events"])
    assert "escalation exhausted" in str(ei.value)


def test_abort_directly_without_rollback_source():
    """No manager = nothing to roll back to: past the skip budget the
    guardian must abort rather than pretend to recover."""
    g = TrainingGuardian(GuardianPolicy(window=8, min_history=2,
                                        skip_budget=1))
    assert g.observe(float("inf")) is Decision.SKIP
    with pytest.raises(GuardianAbort):
        g.observe(float("inf"))


# -- compiled path (CompiledTrainStep.guarded_step) --------------------------

class _TinyReg(nn.Layer):
    def __init__(self):
        super().__init__()
        self.l1 = nn.Linear(8, 16)
        self.l2 = nn.Linear(16, 1)

    def forward(self, x, y):
        d = self.l2(paddle.tanh(self.l1(x))) - y
        return (d * d).mean()


def _reg_batch(i):
    rng = np.random.RandomState(1000 + i)
    return (rng.randn(4, 8).astype(np.float32),
            rng.randn(4, 1).astype(np.float32))


def _compiled(seed=0, lr=1e-2):
    paddle.seed(seed)
    return CompiledTrainStep(_TinyReg(), lr=lr)


def _run_guarded(n, manager=None, policy=None):
    g = GuardedTrainStep(_compiled(), manager=manager, policy=policy)
    losses = []
    while g.global_step < n:
        loss, _ = g.step(*_reg_batch(g.global_step + 1))
        losses.append(loss)
    return g, losses


def test_guarded_clean_run_matches_plain_step():
    """With no anomalies the gate must be a no-op versus the plain
    compiled step (same trees, +0.0 injections, ok=True).

    Agreement is to a few float32 ulps, not bit-exact: the guarded and
    plain steps are two different XLA programs, and the installed
    jaxlib 0.9's XLA:CPU fuses the AdamW chain differently in each
    (observed 3.7e-7 relative after 4 steps; bit-equal again under
    XLA_FLAGS=--xla_disable_hlo_passes=fusion,cpu-instruction-fusion,
    so it is instruction fusion, not state or the compile cache)."""
    plain = _compiled()
    for i in range(4):
        plain.step(*_reg_batch(i + 1))
    guarded = _compiled()
    for i in range(4):
        loss, gnorm, ok = guarded.guarded_step(float("inf"),
                                               *_reg_batch(i + 1))
        assert ok and np.isfinite(loss) and np.isfinite(gnorm)
    assert plain._t == guarded._t == 4
    for k in plain.params:
        np.testing.assert_allclose(np.asarray(plain.params[k]),
                                   np.asarray(guarded.params[k]),
                                   rtol=2e-6, atol=1e-8)


@pytest.mark.parametrize("spec", [
    "guard.nan_loss:before:1=inject",
    "guard.nan_grad:before:1=inject",
    "guard.loss_spike:before:1=inject:1e6",
])
def test_skip_preserves_state_found_inf_semantics(spec):
    """A poisoned step must leave params, BOTH moment trees, and the
    Adam step counter bit-identical — the in-graph jnp.where gate plus
    the host-side _t bookkeeping (GradScaler found_inf semantics)."""
    ts = _compiled()
    ts.guarded_step(float("inf"), *_reg_batch(1))
    snap = {name: {k: np.asarray(v) for k, v in tree.items()}
            for name, tree in (("p", ts.params), ("m", ts._m),
                               ("v", ts._v), ("ma", ts._master))}
    t0 = ts._t
    faults.reset(spec)
    # ceiling 1e3: far above the clean loss, far below the 1e6 spike;
    # the nan faults trip the finiteness checks instead
    loss, gnorm, ok = ts.guarded_step(1e3, *_reg_batch(2))
    assert not ok
    if "nan_loss" in spec:
        assert not np.isfinite(loss)
    elif "nan_grad" in spec:
        assert np.isfinite(loss) and not np.isfinite(gnorm)
    else:
        assert loss > 1e3  # the injected spike, visible to the host
    assert ts._t == t0
    for name, tree in (("p", ts.params), ("m", ts._m), ("v", ts._v),
                       ("ma", ts._master)):
        for k, v in tree.items():
            np.testing.assert_array_equal(np.asarray(v), snap[name][k])
    # spec consumed: the same batch passes clean afterwards
    _, _, ok2 = ts.guarded_step(1e3, *_reg_batch(2))
    assert ok2


def _recovery_parity(spec_burst, tmp_path, n=10):
    """Acceptance core: run n steps with an injected anomaly burst at
    step 5 (skip, skip, rollback to the committed step 4), replaying
    each step's batch by global_step — the recovered run must finish
    with EXACTLY the uninjected run's parameters."""
    policy = GuardianPolicy(window=8, min_history=4, skip_budget=2,
                            rollback_budget=2, checkpoint_every=4)
    clean, clean_losses = _run_guarded(n, policy=policy)

    old = os.environ.get("PT_FAULTS")
    os.environ["PT_FAULTS"] = spec_burst
    try:
        faults.reset()  # harness-driven: arm from the env var
        mgr = CheckpointManager(str(tmp_path), world_size=1, rank=0)
        g, _ = _run_guarded(n, manager=mgr, policy=policy)
    finally:
        if old is None:
            os.environ.pop("PT_FAULTS", None)
        else:
            os.environ["PT_FAULTS"] = old
        faults.disarm_all()

    assert g.guardian.skips == 2
    assert g.guardian.rollbacks == 1
    assert g.global_step == n
    for k in clean.inner.params:
        np.testing.assert_array_equal(
            np.asarray(clean.inner.params[k]),
            np.asarray(g.inner.params[k]))
    # and the guarded run's loss curve stayed healthy
    assert np.isfinite(clean_losses).all()


def test_nan_loss_recovery_parity(tmp_path):
    burst = ",".join(["guard.nan_loss:before:5=inject"] * 3)
    _recovery_parity(burst, tmp_path)


def test_loss_spike_recovery_parity(tmp_path):
    burst = ",".join(["guard.loss_spike:before:5=inject:1e4"] * 3)
    _recovery_parity(burst, tmp_path)


def test_persistent_anomaly_aborts_with_bundle(tmp_path):
    """An anomaly that survives every rollback must end in
    GuardianAbort carrying the diagnostic bundle."""
    mgr = CheckpointManager(str(tmp_path), world_size=1, rank=0)
    g = GuardedTrainStep(
        _compiled(), manager=mgr,
        policy=GuardianPolicy(window=8, min_history=4, skip_budget=1,
                              rollback_budget=1))
    for i in range(3):
        g.step(*_reg_batch(i + 1))
    faults.reset("guard.nan_loss:before:*=inject")
    with pytest.raises(GuardianAbort) as ei:
        for _ in range(8):
            g.step(*_reg_batch(g.global_step + 1))
    b = ei.value.bundle
    assert b["monitor"] == "nan_loss"
    assert b["rollbacks"] == 1
    assert b["rank"] == 0
    assert len(b["loss_window"]) == 3


# -- GradScaler interplay ----------------------------------------------------

def _eager_sgd_setup():
    paddle.seed(3)
    net = nn.Linear(4, 2)
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=net.parameters())
    x = paddle.to_tensor(np.random.RandomState(0).randn(8, 4)
                         .astype(np.float32))
    return net, opt, x


def test_grad_scaler_mark_found_inf_skips_update_and_decays_scale():
    """mark_found_inf (the guardian's eager skip hook) must reproduce
    reference found-inf semantics exactly: the optimizer step is
    skipped (params, accumulators, global step untouched) while the
    scale schedule decays by decr_ratio."""
    from paddle_tpu.amp import GradScaler

    net, opt, x = _eager_sgd_setup()
    scaler = GradScaler(enable=True, init_loss_scaling=1024.0)
    scaler.scale((net(x) ** 2).mean()).backward()
    w0 = net.weight.numpy().copy()
    opt_state0 = {k: np.asarray(v).copy()
                  for k, v in opt.state_dict()["accumulators"].items()}
    gstep0 = opt.state_dict()["global_step"]

    scaler.mark_found_inf()
    scaler.step(opt)
    scaler.update()
    np.testing.assert_array_equal(net.weight.numpy(), w0)
    st = opt.state_dict()
    assert st["global_step"] == gstep0
    for k, v in st["accumulators"].items():
        np.testing.assert_array_equal(np.asarray(v), opt_state0[k])
    assert scaler._scale == 512.0  # decayed by decr_ratio
    opt.clear_grad()

    # and a clean step afterwards still moves the weights
    scaler.scale((net(x) ** 2).mean()).backward()
    scaler.step(opt)
    scaler.update()
    assert np.abs(net.weight.numpy() - w0).max() > 0
    assert opt.state_dict()["global_step"] == gstep0 + 1


def test_compiled_skip_keeps_adam_counter_sequence():
    """After a skipped step the NEXT accepted step must use the same
    Adam t as if the anomaly never happened (bias correction must not
    jump) — verified by comparing against an uninjected twin."""
    a = _compiled()
    b = _compiled()
    for i in range(2):
        a.guarded_step(float("inf"), *_reg_batch(i + 1))
        b.guarded_step(float("inf"), *_reg_batch(i + 1))
    faults.reset("guard.nan_loss:before:1=inject")
    _, _, ok = b.guarded_step(float("inf"), *_reg_batch(99))
    assert not ok
    a.guarded_step(float("inf"), *_reg_batch(3))
    b.guarded_step(float("inf"), *_reg_batch(3))
    assert a._t == b._t == 3
    for k in a.params:
        np.testing.assert_array_equal(np.asarray(a.params[k]),
                                      np.asarray(b.params[k]))


# -- hapi (eager fit) path ---------------------------------------------------

class _FakeData(paddle.io.Dataset):
    def __init__(self, n=48, seed=0):
        rng = np.random.RandomState(seed)
        self.x = rng.randn(n, 8).astype(np.float32)
        self.y = rng.randint(0, 4, size=(n, 1)).astype(np.int64)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def _hapi_model(amp_configs=None):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 4))
    model = paddle.hapi.Model(net)
    model.prepare(paddle.optimizer.Adam(learning_rate=0.01,
                                        parameters=net.parameters()),
                  nn.CrossEntropyLoss(), amp_configs=amp_configs)
    return model


def test_hapi_fit_guardian_skip_then_finish(tmp_path):
    from paddle_tpu.training.guardian import guardian_for_model

    model = _hapi_model()
    g = guardian_for_model(
        model, CheckpointManager(str(tmp_path), world_size=1, rank=0),
        policy=GuardianPolicy(window=8, min_history=4, skip_budget=2,
                              rollback_budget=1, checkpoint_every=3))
    faults.reset("guard.nan_loss:before:4=inject")
    res = model.fit(_FakeData(48), batch_size=16, epochs=2, verbose=0,
                    guardian=g)
    assert g.skips == 1 and g.rollbacks == 0
    assert np.isfinite(res["loss"])
    assert g.manager.latest_step() is not None


def test_hapi_fit_guardian_rollback_restores_committed(tmp_path):
    from paddle_tpu.training.guardian import guardian_for_model

    model = _hapi_model()
    g = guardian_for_model(
        model, CheckpointManager(str(tmp_path), world_size=1, rank=0),
        policy=GuardianPolicy(window=8, min_history=4, skip_budget=1,
                              rollback_budget=2, checkpoint_every=2))
    model.fit(_FakeData(48), batch_size=16, epochs=1, verbose=0,
              guardian=g)
    committed = g.manager.latest_step()
    assert committed is not None
    # a spike burst: skip (budget 1), then rollback, then clean finish
    faults.reset(",".join(["guard.loss_spike:before:2=inject:1e5"] * 3))
    res = model.fit(_FakeData(48, seed=1), batch_size=16, epochs=1,
                    verbose=0, guardian=g)
    assert g.rollbacks >= 1
    assert g.manager.latest_step() >= committed
    assert np.isfinite(res["loss"])


def test_hapi_scaler_guardian_skip_decays_scale(tmp_path):
    """Eager skip under AMP: the guardian routes through
    mark_found_inf, so the scale schedule reacts like a real inf."""
    from paddle_tpu.training.guardian import guardian_for_model

    model = _hapi_model(amp_configs={"level": "O1", "dtype": "bfloat16",
                                     "use_loss_scaling": True})
    scale0 = model._scaler._scale
    g = guardian_for_model(
        model, CheckpointManager(str(tmp_path), world_size=1, rank=0),
        policy=GuardianPolicy(window=8, min_history=4, skip_budget=3))
    faults.reset("guard.nan_loss:before:2=inject")
    model.fit(_FakeData(32), batch_size=16, epochs=1, verbose=0,
              guardian=g)
    assert g.skips == 1
    assert model._scaler._scale == scale0 * model._scaler._decr_ratio
