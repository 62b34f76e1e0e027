"""The comparison that decides ``correct`` has to fail what it should:
a run is driven as the harness drives it (without the look for a chip),
with the timed path broken underneath, and ``correct`` comes out false —
once for each fault a one-chip cell can have.  And the controls: the
reference put in the program's place at the precision below the one the
configuration states.  Training's control that fails (a bf16 master in
place of the float32 one) fails here at the tiny size too; the int8
controls separate only at real widths — PERF.md has the chip's readings
at the cells' own size — and are driven here to show that they run and
are read."""
import os

import jax
import pytest

from chipbench import control, spec

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench",
                    "BENCHMARK.json")


def _failing(line):
    return [k for k, row in line["checks"].items()
            if not row["value"] <= row["limit"]]


def test_a_step_that_leaves_its_state_unchanged(monkeypatch, rehearse):
    import paddle_tpu.models as models

    class Frozen(models.CompiledTrainStep):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            loss_of = self.loss_of
            self._step = jax.jit(
                lambda p, master, m, v, t, lr, *batch:
                (p, master, m, v, loss_of(p, *batch)))

    monkeypatch.setattr(models, "CompiledTrainStep", Frozen)
    line = rehearse("tiny-train.tiny-steady")
    assert line["correct"] is False
    assert set(_failing(line)) == {"grad_norm_gap", "change_norm_gap",
                                   "grad_diff_gap", "change_diff_gap"}
    # nothing moved: both norms read 0 against the reference's, a gap of 1
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert line["checks"]["change_diff_gap"]["value"] == pytest.approx(1.0)


def test_an_update_in_the_wrong_direction(monkeypatch, rehearse):
    """The norms see nothing of a flipped update; the difference does."""
    import paddle_tpu.models as models

    class Flipped(models.CompiledTrainStep):
        def __init__(self, model, lr, **kw):
            super().__init__(model, lr=-lr, **kw)

    monkeypatch.setattr(models, "CompiledTrainStep", Flipped)
    line = rehearse("tiny-train.tiny-steady")
    assert line["correct"] is False
    assert _failing(line) == ["change_diff_gap"]
    assert line["checks"]["change_diff_gap"]["value"] == pytest.approx(2, abs=0.1)


def test_half_of_the_batch_left_out(monkeypatch, rehearse):
    import paddle_tpu.models as models

    class Half(models.CompiledTrainStep):
        def step(self, ids, labels):
            n = len(ids) // 2
            return super().step(ids[:n], labels[:n])

    monkeypatch.setattr(models, "CompiledTrainStep", Half)
    line = rehearse("tiny-train.tiny-steady")
    assert line["correct"] is False
    assert {"grad_norm_gap", "grad_diff_gap"} <= set(_failing(line))


def test_a_token_altered_where_it_is_produced(monkeypatch, rehearse):
    from paddle_tpu.inference.server.executor import PagedExecutor

    sound, calls = PagedExecutor.decode, [0]

    def decode(self, sids):
        out = sound(self, sids)
        calls[0] += 1
        if calls[0] % 2 == 0:       # every answer is some steps long
            for sid in out:
                out[sid] = self.last_token[sid] = \
                    (out[sid] + 1) % self.config.vocab_size
        return out

    monkeypatch.setattr(PagedExecutor, "decode", decode)
    line = rehearse("tiny-serve.tiny-closed", seconds=0.5)
    assert line["correct"] is False
    assert _failing(line) == ["served_token_gap"]


def test_a_failed_request_is_not_correct(monkeypatch, rehearse):
    """The first request submitted inside the window fails in its first
    chunk (requests start prefilling in the order they were submitted)."""
    from chipbench import run
    from paddle_tpu.inference.server import ServingEngine
    from paddle_tpu.inference.server.executor import PagedExecutor

    chunk, submit = PagedExecutor.prefill_chunk, ServingEngine.submit
    started = run.Context.window_started
    n = {"submitted": 0, "begun": 0, "at_open": None}

    def counted_submit(self, *a, **kw):
        n["submitted"] += 1
        return submit(self, *a, **kw)

    def window_started(self, t):
        n["at_open"] = n["submitted"]
        started(self, t)

    def prefill_chunk(self, sid, chunk_ids, start, final):
        if start == 0:
            n["begun"] += 1
            if n["begun"] == (n["at_open"] or 0) + 1 and n["at_open"]:
                raise ValueError("planted")
        return chunk(self, sid, chunk_ids, start, final)

    monkeypatch.setattr(ServingEngine, "submit", counted_submit)
    monkeypatch.setattr(run.Context, "window_started", window_started)
    monkeypatch.setattr(PagedExecutor, "prefill_chunk", prefill_chunk)
    line = rehearse("tiny-serve.tiny-closed", seconds=0.5)
    assert line["failed"] >= 1 and line["correct"] is False


@pytest.mark.parametrize("workload,others", [
    ("tiny-train.tiny-steady", ["int8", "bf16_master", "half_batch"]),
    ("tiny-serve.tiny-closed", ["control_token_gap"])])
def test_control_is_read_beside_the_program(workload, others, capsys):
    rows = control.main(["--workload", workload, "--seeds", "2147483659",
                         "--seconds", "0.3"], bench_path=TINY, rehearse=True)
    assert [r["seed"] for r in rows] == [2147483659]
    for row in rows:
        got = row["readings"]
        if "program" in got:        # training: one reading per variant
            assert set(got) == {"program", *others}
            limits = spec.limits(spec.load_benchmark(TINY), workload)
            # the control comes out as not correct, and so does the fault
            assert got["bf16_master"]["change_norm_gap"] > \
                3 * limits["change_norm_gap"]
            assert got["half_batch"]["grad_norm_gap"] > limits["grad_norm_gap"]
            assert all(got["program"][k] <= limits[k] for k in limits)
            # each control goes through the run's own judgement (the int8
            # control separates only at real widths: PERF.md section 6)
            assert row["verdicts"] == {"int8": True, "bf16_master": False,
                                       "half_batch": False}
        else:
            assert all(k in got for k in ["served_token_gap", *others])
            assert set(row["verdicts"]) == {"int8"}
