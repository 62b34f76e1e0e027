"""High-level Model API (Keras-style fit/evaluate/predict).

Reference: ``python/paddle/hapi/model.py:1082`` (Model), ``:2010`` (fit),
``:2264`` (evaluate), ``:2394`` (predict).  The reference dispatches to a
DynamicGraphAdapter/StaticGraphAdapter pair; here there is one dygraph
train/eval path over the jax-backed eager engine, with AMP via
``paddle.amp`` and metrics via ``paddle.metric``.
"""
from __future__ import annotations

import math
import os

import numpy as np

from .. import obs
from ..core.tensor import Tensor
from ..metric import Metric
from ..nn.layers import Layer
from .callbacks import Callback, CallbackList, ModelCheckpoint, ProgBarLogger


def _timed_batches(loader, timer):
    """Iterate ``loader``, timing each ``next()`` under a
    ``train.data_wait`` span — input starvation becomes visible as wide
    data-wait slices in the trace.  ``timer`` (an
    ``obs.perf.StepTimer``) additionally accumulates the wait into the
    step's ``data_wait`` phase."""
    it = iter(loader)
    while True:
        try:
            with timer.phase("data_wait"), \
                    obs.span("train.data_wait", cat="train"):
                batch = next(it)
        except StopIteration:
            return
        yield batch


def _to_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _to_tensor(x):
    if isinstance(x, Tensor):
        return x
    return Tensor(np.asarray(x))


class Model:
    """Network wrapper with training/inference loops.

    model = paddle.Model(network)
    model.prepare(optimizer, loss, metrics)
    model.fit(train_dataset, eval_dataset, epochs=2, batch_size=32)
    """

    def __init__(self, network, inputs=None, labels=None):
        if not isinstance(network, Layer):
            raise TypeError("Model expects a paddle.nn.Layer, got "
                            f"{type(network)}")
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._scaler = None
        self._amp_level = "O0"
        self._amp_dtype = "bfloat16"
        self.stop_training = False

    # -- setup -------------------------------------------------------------

    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None):
        self._optimizer = optimizer
        if loss is not None and not (isinstance(loss, Layer)
                                     or callable(loss)):
            raise TypeError("loss must be a Layer or a callable")
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metric {m} is not a paddle.metric.Metric")
        if amp_configs is not None:
            if isinstance(amp_configs, str):
                amp_configs = {"level": amp_configs}
            self._amp_level = amp_configs.get("level", "O1")
            self._amp_dtype = amp_configs.get("dtype", "bfloat16")
            if self._amp_level not in ("O0", "O1", "O2"):
                raise ValueError(f"bad amp level {self._amp_level}")
            if self._amp_level != "O0":
                from .. import amp

                use_scaler = amp_configs.get(
                    "use_loss_scaling", self._amp_dtype == "float16")
                self._scaler = amp.GradScaler(enable=use_scaler)
                if self._amp_level == "O2":
                    amp.decorate(self.network, level="O2",
                                 dtype=self._amp_dtype)

    # -- single-batch paths (reference model.py train_batch/eval_batch) ----

    def _forward(self, inputs):
        return self.network(*inputs)

    def _compute_loss(self, outputs, labels):
        outs, labs = _to_list(outputs), _to_list(labels)
        if isinstance(self._loss, Layer) or callable(self._loss):
            return self._loss(*(outs + labs))
        raise RuntimeError("loss not set; call prepare(loss=...)")

    def train_batch(self, inputs, labels=None, update=True):
        if self._optimizer is None:
            raise RuntimeError("optimizer not set; call prepare() first")
        self.network.train()
        inputs = [_to_tensor(t) for t in _to_list(inputs)]
        labels = [_to_tensor(t) for t in _to_list(labels)]

        if self._amp_level != "O0":
            from .. import amp

            with amp.auto_cast(level=self._amp_level,
                               dtype=self._amp_dtype):
                outputs = self._forward(inputs)
                loss = self._compute_loss(outputs, labels)
        else:
            outputs = self._forward(inputs)
            loss = self._compute_loss(outputs, labels)

        scaled = self._scaler.scale(loss) if self._scaler else loss
        scaled.backward()
        if update:
            self._apply_update()

        metrics = self._update_metrics(outputs, labels)
        return (float(np.asarray(loss.numpy())), metrics)

    def _apply_update(self, found_inf=False):
        """Apply (or, with ``found_inf``, skip with GradScaler found_inf
        semantics) the pending optimizer update and clear grads."""
        if self._scaler:
            if found_inf:
                self._scaler.mark_found_inf()
            self._scaler.step(self._optimizer)
            self._scaler.update()
        elif not found_inf:
            self._optimizer.step()
        self._optimizer.clear_grad()

    def _global_grad_norm(self):
        """Global L2 norm over all parameter grads (guardian monitor;
        eager path — the loop is host-synchronous anyway)."""
        tot = 0.0
        for p in self._optimizer._parameter_list():
            if p.grad is not None:
                g = np.asarray(p.grad._data, np.float64)
                tot += float((g * g).sum())
        return float(np.sqrt(tot))

    def _guarded_train_batch(self, guardian, inputs, labels):
        """One fit-loop step under the training guardian: forward +
        backward, poll the guard.* value-fault points, classify, then
        apply / skip (found_inf semantics) / roll back per the
        escalation policy."""
        from ..testing import faults
        from ..training.guardian import Decision

        loss, metrics = self.train_batch(inputs, labels, update=False)
        if faults.poll("guard.nan_loss") is not None:
            loss = float("nan")
        else:
            spike = faults.poll("guard.loss_spike")
            if spike is not None:
                loss = loss + (1e6 if spike is True else float(spike))
        gnorm = None
        if guardian.policy.check_grad_norm:
            gnorm = self._global_grad_norm()
            if faults.poll("guard.nan_grad") is not None:
                gnorm = float("nan")
        decision = guardian.observe(loss, gnorm)
        if decision is Decision.OK:
            self._apply_update()
            guardian.maybe_commit(guardian.steps_seen)
        elif decision is Decision.SKIP:
            self._apply_update(found_inf=True)
        else:  # ROLLBACK — restore last committed state, drop grads
            guardian.rollback()
            self._optimizer.clear_grad()
        return loss, metrics

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        from ..autograd import engine as _engine

        inputs = [_to_tensor(t) for t in _to_list(inputs)]
        labels = [_to_tensor(t) for t in _to_list(labels)]
        with _engine.no_grad():
            outputs = self._forward(inputs)
            loss = (self._compute_loss(outputs, labels)
                    if self._loss is not None else None)
        metrics = self._update_metrics(outputs, labels)
        lv = float(np.asarray(loss.numpy())) if loss is not None else None
        return (lv, metrics)

    def predict_batch(self, inputs):
        self.network.eval()
        from ..autograd import engine as _engine

        inputs = [_to_tensor(t) for t in _to_list(inputs)]
        with _engine.no_grad():
            outputs = self._forward(inputs)
        return [np.asarray(o.numpy()) for o in _to_list(outputs)]

    def _update_metrics(self, outputs, labels):
        res = {}
        outs, labs = _to_list(outputs), _to_list(labels)
        for m in self._metrics:
            state = m.compute(*(outs + labs))
            m.update(*_to_list(state))
            res[m.name()] = m.accumulate()
        return res

    # -- loops --------------------------------------------------------------

    def _make_loader(self, data, batch_size, shuffle, num_workers,
                     drop_last):
        from ..io import DataLoader, Dataset

        if isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers, drop_last=drop_last)
        raise TypeError(f"expected Dataset or DataLoader, got {type(data)}")

    def _split_batch(self, batch):
        """A loader batch is (input..., label...); with a loss configured the
        last element feeds the loss, otherwise everything is input."""
        batch = list(batch) if isinstance(batch, (list, tuple)) else [batch]
        if self._loss is None or len(batch) == 1:
            return batch, []
        n_lab = len(self._labels) if self._labels else 1
        return batch[:-n_lab], batch[-n_lab:]

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            guardian=None):
        """``guardian``: a ``paddle.training.TrainingGuardian`` (e.g.
        from ``training.guardian.guardian_for_model``) — each train
        step is then monitored (NaN/Inf loss, grad norm, loss spike)
        and anomalies escalate skip -> rollback-to-last-committed ->
        ``GuardianAbort`` per its policy."""
        loader = self._make_loader(train_data, batch_size, shuffle,
                                   num_workers, drop_last)
        cbks = _to_list(callbacks)
        if not any(isinstance(c, ProgBarLogger) for c in cbks):
            cbks.append(ProgBarLogger(log_freq, verbose=verbose))
        if save_dir and not any(isinstance(c, ModelCheckpoint)
                                for c in cbks):
            cbks.append(ModelCheckpoint(save_freq, save_dir))
        cbk = CallbackList(cbks)
        cbk.set_model(self)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbk.set_params({"epochs": epochs, "steps": steps,
                        "verbose": verbose,
                        "metrics": ["loss"] + [m.name()
                                               for m in self._metrics]})
        self.stop_training = False
        cbk.on_train_begin()
        if guardian is not None and guardian.manager is not None \
                and guardian.manager.latest_step() is None:
            # Rollback must always have a committed source.
            guardian.commit(0)
        logs = {}
        timer = obs.perf.StepTimer("train.step")
        # health plane: guardian-anomaly SLO + "train" heartbeat,
        # evaluated once per fit step when telemetry is on
        health_eng = None
        if obs.handle() is not None:
            from ..obs import health as _health

            health_eng = _health.SLOEngine(
                _health.default_train_slos(), source="train")
            obs.handle().statusz["train"] = \
                lambda: {"phase_seconds": timer.phase_seconds()}
        for epoch in range(epochs):
            if self.stop_training:
                break
            cbk.on_epoch_begin(epoch)
            for m in self._metrics:
                m.reset()
            for step, batch in enumerate(_timed_batches(loader, timer)):
                cbk.on_train_batch_begin(step)
                ins, labs = self._split_batch(batch)
                with obs.span("train.fit_step", cat="train",
                              epoch=epoch, step=step) as sp:
                    with timer.phase("compute"):
                        if guardian is not None:
                            loss, metrics = self._guarded_train_batch(
                                guardian, ins, labs)
                        else:
                            loss, metrics = self.train_batch(ins, labs)
                    sp.set(loss=float(loss))
                logs = {"loss": loss, **metrics}
                # Callback flush (progress bars, metric sinks) is the
                # loop's own telemetry cost — the "obs" phase.
                with timer.phase("obs"):
                    cbk.on_train_batch_end(step, logs)
                timer.end_step()
                if health_eng is not None:
                    health_eng.evaluate(step=step)
                    obs.beat("train")
            if eval_data is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(eval_data, batch_size=batch_size,
                                          log_freq=log_freq, verbose=0,
                                          num_workers=num_workers,
                                          callbacks=cbk)
                logs.update({f"eval_{k}" if not k.startswith("eval_")
                             else k: v for k, v in eval_logs.items()})
            # Epoch-boundary callbacks carry the ModelCheckpoint save.
            with timer.phase("checkpoint"):
                cbk.on_epoch_end(epoch, logs)
            timer.end_step()
        cbk.on_train_end(logs)
        return logs

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None):
        loader = self._make_loader(eval_data, batch_size, False,
                                   num_workers, False)
        own_cbk = not isinstance(callbacks, CallbackList)
        if own_cbk:
            cbks = _to_list(callbacks)
            if verbose and not any(isinstance(c, ProgBarLogger)
                                   for c in cbks):
                cbks.append(ProgBarLogger(log_freq, verbose=verbose))
            cbk = CallbackList(cbks)
            cbk.set_model(self)
            cbk.set_params({"verbose": verbose,
                            "metrics": ["loss"] + [m.name()
                                                   for m in self._metrics]})
        else:
            cbk = callbacks
        for m in self._metrics:
            m.reset()
        cbk.on_eval_begin()
        logs, losses = {}, []
        for step, batch in enumerate(loader):
            cbk.on_eval_batch_begin(step)
            ins, labs = self._split_batch(batch)
            loss, metrics = self.eval_batch(ins, labs)
            if loss is not None:
                losses.append(loss)
            logs = dict(metrics)
            if losses:
                logs["loss"] = float(np.mean(losses))
            cbk.on_eval_batch_end(step, logs)
        cbk.on_eval_end(logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        loader = self._make_loader(test_data, batch_size, False,
                                   num_workers, False)
        outputs = []
        for batch in loader:
            ins, _ = self._split_batch(batch)
            outs = self.predict_batch(ins)
            outputs.append(outs if len(outs) > 1 else outs[0])
        if stack_outputs and outputs:
            if isinstance(outputs[0], list):
                outputs = [np.concatenate([o[i] for o in outputs])
                           for i in range(len(outputs[0]))]
            else:
                outputs = np.concatenate(outputs)
        return outputs

    # -- persistence ---------------------------------------------------------

    def save(self, path, training=True):
        """path is a prefix: writes <path>.pdparams (+ .pdopt when
        training=True), matching the reference's save layout."""
        from .. import framework_io

        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        framework_io.save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            framework_io.save(self._optimizer.state_dict(),
                              path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from .. import framework_io

        state = framework_io.load(path + ".pdparams")
        if skip_mismatch:
            own = self.network.state_dict()
            state = {k: v for k, v in state.items()
                     if k in own and list(np.asarray(v).shape)
                     == list(own[k].shape)}
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if (not reset_optimizer and self._optimizer is not None
                and os.path.exists(opt_path)):
            self._optimizer.set_state_dict(framework_io.load(opt_path))

    # -- introspection -------------------------------------------------------

    def parameters(self, include_sublayers=True):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        """Parameter-count summary (reference hapi/model_summary.py)."""
        rows, total = [], 0
        for name, p in self.network.named_parameters():
            n = int(np.prod(p.shape)) if p.shape else 1
            total += n
            rows.append((name, list(p.shape), n))
        width = max((len(r[0]) for r in rows), default=10) + 2
        lines = [f"{'Layer (param)':<{width}}{'Shape':<20}{'Params':>12}"]
        lines += [f"{n:<{width}}{str(s):<20}{c:>12,}" for n, s, c in rows]
        lines.append(f"Total params: {total:,}")
        out = "\n".join(lines)
        print(out)
        return {"total_params": total, "trainable_params": total}
