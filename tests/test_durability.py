"""Durable serving: WAL journal, whole-process crash recovery, and
hung-replica KV-page salvage (r22).

The contract under test: an accepted request either finishes
**bit-identically** to an uninterrupted run or is reported rejected —
across any failure up to and including a SIGKILL of the whole serving
process.  A real subprocess (``tests/_durability_worker.py``) serves a
seeded load and is hard-killed at seeded journal depths; recovery goes
through ``ServingCluster.recover`` against the same seeded weights.
"""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.server import (RequestState, ServingCluster,
                                         ServingEngine, WriteAheadLog,
                                         check_pool_invariants, replay)
from paddle_tpu.inference.server.cluster import DEAD_STATES
from paddle_tpu.inference.server.wal import (compact, resolve_wal,
                                             segment_paths, stream_crc)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import faults
from paddle_tpu.testing.load import LoadSpec, generate_load

KW = dict(max_seqs=4, page_size=4, max_len=64, prefill_chunk=8)
SPEC = LoadSpec(n_requests=8, mean_interarrival=1.0, prompt_len=(4, 14),
                max_new=(4, 8), vocab=256, seed=3)
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_durability_worker.py")


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset("")
    yield
    faults.reset("")


@pytest.fixture(scope="module")
def work():
    return sorted(generate_load(SPEC), key=lambda w: w["arrival_tick"])


@pytest.fixture(scope="module")
def baseline(model, work):
    """{rid: tokens} from a fault-free, WAL-free single engine — the
    uninterrupted run every recovered stream must match bit-exactly."""
    eng = ServingEngine(model, **KW)
    return {w["rid"]: eng.submit(
        w["prompt_ids"], max_new_tokens=w["max_new_tokens"],
        rid=w["rid"]).result() for w in work}


def _audit(cl):
    for rep in cl.replicas:
        if rep.state not in DEAD_STATES:
            check_pool_invariants(rep.engine.executor.cache,
                                  rep.engine.prefix)


def _drive(cl, work, max_steps=400, audit=True):
    """Submit at arrival ticks and step until drained."""
    handles = {}
    i = 0
    while i < len(work) or cl.in_flight:
        while i < len(work) and work[i]["arrival_tick"] <= cl.tick:
            w = work[i]
            i += 1
            handles[w["rid"]] = cl.submit(
                w["prompt_ids"], max_new_tokens=w["max_new_tokens"],
                rid=w["rid"])
        cl.step()
        if audit:
            _audit(cl)
        assert cl.tick < max_steps, "cluster did not drain"
    return handles


def _drain(cl, max_steps=400, audit=True):
    while cl.in_flight:
        cl.step()
        if audit:
            _audit(cl)
        assert cl.tick < max_steps, "recovered cluster did not drain"


def _assert_bit_identical(cl, handles, baseline):
    for rid, h in handles.items():
        assert h.tokens == baseline[rid], \
            f"{rid}: {h.tokens} != baseline {baseline[rid]}"


# -- gate + plumbing ----------------------------------------------------

def test_pt_wal_env_gate(monkeypatch, tmp_path):
    from paddle_tpu.inference.server import wal as wal_mod

    monkeypatch.setenv("PT_WAL", "bogus")
    with pytest.raises(ValueError, match="PT_WAL"):
        wal_mod.wal_enabled()
    monkeypatch.setenv("PT_WAL", "on")
    monkeypatch.delenv("PT_WAL_DIR", raising=False)
    with pytest.raises(ValueError, match="PT_WAL_DIR"):
        wal_mod.default_wal()
    monkeypatch.setenv("PT_WAL_DIR", str(tmp_path / "j"))
    assert isinstance(wal_mod.default_wal(), WriteAheadLog)
    monkeypatch.setenv("PT_WAL", "off")
    assert wal_mod.default_wal() is None
    with pytest.raises(ValueError, match="wal="):
        resolve_wal(123)


@pytest.mark.slow
def test_wal_off_is_bitexact_default(model, work, baseline):
    # PT_WAL unset: no journal anywhere, streams untouched
    cl = ServingCluster(model, n_replicas=2, cluster=True, **KW)
    assert cl.wal is None
    assert all(r.engine.wal is None for r in cl.replicas)
    assert all(r.engine.scheduler.wal is None for r in cl.replicas)
    handles = _drive(cl, work)
    _assert_bit_identical(cl, handles, baseline)


def test_wal_fsync_batching(tmp_path):
    wal = WriteAheadLog(tmp_path / "j", fsync_every=4)
    for i in range(10):
        wal.append({"t": "token", "rid": "r", "tok": i})
    assert wal.appended == 10
    assert wal.fsyncs == 2 and wal.last_fsync_at == 8
    assert wal.statusz()["lag_records"] == 2
    wal.fsync()
    assert wal.fsyncs == 3 and wal.statusz()["lag_records"] == 0
    # the journal accounts its own serving-path cost
    assert 0 < wal.statusz()["write_s"] < 1.0


def test_wal_segment_rotation(tmp_path):
    # tiny segments force several rolls; replay stitches them in order
    wal = WriteAheadLog(tmp_path / "j", fsync_every=4, segment_bytes=128)
    for i in range(10):
        wal.append({"t": "token", "rid": "r", "tok": i})
    wal.close()
    st = wal.statusz()
    assert st["segments"] > 1
    recs, report = replay(tmp_path / "j")
    assert [r["tok"] for r in recs] == list(range(10))
    assert report["segments"] == st["segments"]
    assert report["corrupt"] == 0 and report["torn_bytes"] == 0
    # a new writer never appends to an old (possibly torn) segment
    wal2 = WriteAheadLog(tmp_path / "j", fsync_every=4)
    wal2.append({"t": "token", "rid": "r", "tok": 10})
    wal2.close()
    assert wal2.statusz()["segments"] == st["segments"] + 1
    recs2, _ = replay(tmp_path / "j")
    assert [r["tok"] for r in recs2] == list(range(11))


def test_wal_roll_survives_fsync_failure(tmp_path):
    # a persistently failing fsync must not abort rotation: the old fd
    # still closes, the new segment opens, and every record lands —
    # otherwise a sick disk leaks the fd and pins the segment forever
    wal = WriteAheadLog(tmp_path / "j", fsync_every=100,
                        segment_bytes=64)
    faults.reset("wal.fsync:before:*=raise")
    for i in range(6):
        wal.append({"t": "token", "rid": "r", "tok": i})
    faults.reset("")
    wal.close()
    assert wal.errors >= 1              # the fsyncs degraded...
    assert wal.statusz()["segments"] > 1    # ...rotation did not
    recs, report = replay(tmp_path / "j")
    assert [r["tok"] for r in recs] == list(range(6))
    assert report["corrupt"] == 0


# -- journal compaction -------------------------------------------------

def _journal_stream(wal, rid, toks, finish=True):
    wal.append({"t": "submit", "rid": rid, "prompt": [1, 2, 3]})
    for i, t in enumerate(toks):
        wal.append({"t": "token", "rid": rid, "i": i, "tok": t})
    if finish:
        wal.append({"t": "finish", "rid": rid, "n": len(toks),
                    "crc": stream_crc(toks)})


def test_wal_compact_drops_terminal_keeps_live(tmp_path):
    """Compaction folds the journal with recover's own semantics:
    proven-finished and rejected-not-superseded rids drop, in-flight
    and resubmitted-after-reject rids keep their full record sets
    verbatim, and the writer continues on a strictly newer segment."""
    wal = WriteAheadLog(tmp_path / "j", fsync_every=1,
                        segment_bytes=200)
    _journal_stream(wal, "a", [5, 6, 7])
    _journal_stream(wal, "b", [9])
    _journal_stream(wal, "d", [4, 4], finish=False)     # in flight
    wal.append({"t": "submit", "rid": "e", "prompt": [7]})
    wal.append({"t": "reject", "rid": "e", "reason": "shed"})
    wal.append({"t": "submit", "rid": "f", "prompt": [8]})
    wal.append({"t": "reject", "rid": "f", "reason": "shed"})
    wal.append({"t": "submit", "rid": "f", "prompt": [8]})  # supersedes
    wal.append({"t": "token", "rid": "f", "i": 0, "tok": 3})
    n_before = len(segment_paths(tmp_path / "j"))
    assert n_before > 1                 # rotation actually happened
    rep = wal.compact()
    assert rep["live_rids"] == 2 and rep["segments_dropped"] == n_before
    assert rep["records_dropped"] > 0
    assert len(segment_paths(tmp_path / "j")) == 1
    recs, report = replay(tmp_path / "j")
    assert sorted({r["rid"] for r in recs}) == ["d", "f"]
    assert [r["tok"] for r in recs
            if r.get("t") == "token" and r["rid"] == "d"] == [4, 4]
    assert report["corrupt"] == 0 and report["torn_bytes"] == 0
    # appends land on a fresh segment strictly after the compacted one
    _journal_stream(wal, "g", [1])
    assert int(os.path.basename(
        segment_paths(tmp_path / "j")[-1])[4:12]) \
        == rep["segment_index"] + 1
    assert wal.compactions == 1
    assert wal.statusz()["compactions"] == 1
    wal.close()


def test_wal_compact_every_trigger(tmp_path, monkeypatch):
    # PT_WAL_COMPACT_EVERY arms the append-count trigger; a journal
    # whose rids are all terminal compacts down to nothing
    monkeypatch.setenv("PT_WAL_COMPACT_EVERY", "4")
    wal = WriteAheadLog(tmp_path / "j", fsync_every=1)
    _journal_stream(wal, "a", [1, 2])   # submit + 2 tokens + finish
    assert wal.compactions == 1
    recs, _ = replay(tmp_path / "j")
    assert recs == []
    wal.close()
    with pytest.raises(ValueError, match="compact_every"):
        WriteAheadLog(tmp_path / "k", compact_every=-1)


def test_wal_compact_crash_window_degrades(tmp_path):
    """A raise in the window between the durable rewrite and the old-
    segment unlinks degrades (errors counted, no report) and leaves
    old + new segments coexisting — safe because replay's recover fold
    is duplicate-idempotent."""
    wal = WriteAheadLog(tmp_path / "j", fsync_every=1)
    wal.append({"t": "submit", "rid": "x", "prompt": [1]})
    wal.append({"t": "token", "rid": "x", "i": 0, "tok": 2})
    faults.reset("wal.compact:after:1=raise")
    rep = wal.compact()
    faults.reset("")
    assert rep is None and wal.errors >= 1
    assert len(segment_paths(tmp_path / "j")) == 2   # old + complete new
    recs, _ = replay(tmp_path / "j")
    toks = [r for r in recs if r.get("t") == "token"]
    assert len(toks) == 2               # the duplicate is present...
    got = []
    for r in toks:
        if int(r["i"]) == len(got):
            got.append(r["tok"])
    assert got == [2]                   # ...and folds to one token
    # the writer survives the degraded compaction on a newer segment
    wal.append({"t": "token", "rid": "x", "i": 1, "tok": 9})
    assert len(segment_paths(tmp_path / "j")) == 3
    wal.close()


@pytest.mark.slow
def test_wal_journal_roundtrip(model, work, baseline, tmp_path):
    cl = ServingCluster(model, n_replicas=2, cluster=True,
                        wal=str(tmp_path / "j"), **KW)
    handles = _drive(cl, work)
    _assert_bit_identical(cl, handles, baseline)
    recs, report = replay(tmp_path / "j")
    assert report["corrupt"] == 0 and report["torn_bytes"] == 0
    subs = [r for r in recs if r["t"] == "submit"]
    fins = {r["rid"]: r for r in recs if r["t"] == "finish"}
    admits = {r["rid"] for r in recs if r["t"] == "admit"}
    assert {s["rid"] for s in subs} == set(baseline) == admits
    for rid, toks in baseline.items():
        journaled = [r["tok"] for r in recs
                     if r["t"] == "token" and r["rid"] == rid]
        assert journaled == toks, rid
        assert fins[rid]["n"] == len(toks)
        assert fins[rid]["crc"] == stream_crc(toks)
    # prompt in the submit record is what recovery recomputes from
    by_rid = {w["rid"]: w for w in work}
    for s in subs:
        assert s["prompt"] == list(map(int, by_rid[s["rid"]]["prompt_ids"]))


# -- idempotent duplicate submit ---------------------------------------

def test_engine_duplicate_submit_returns_original(model, tmp_path):
    eng = ServingEngine(model, wal=str(tmp_path / "j"), **KW)
    h1 = eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=4,
                    rid="dup")
    h2 = eng.submit(np.asarray([9, 9, 9], np.int32), max_new_tokens=4,
                    rid="dup")
    assert h2._req is h1._req and eng.dedup_hits == 1
    toks = h1.result()
    # terminal requests dedup too (exactly-once across retries)
    h3 = eng.submit(np.asarray([1, 2, 3], np.int32), rid="dup")
    assert h3._req is h1._req and h3.tokens == toks
    recs, _ = replay(tmp_path / "j")
    assert sum(1 for r in recs if r["t"] == "dedup") == 2
    assert sum(1 for r in recs if r["t"] == "submit") == 1


def test_anonymous_rids_skip_explicit_collisions(model):
    # a client-supplied rid squatting on the auto-rid namespace must
    # never capture an anonymous submit as a silent dedup
    eng = ServingEngine(model, **KW)
    h0 = eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=2,
                    rid="req-0")
    h1 = eng.submit(np.asarray([4, 5, 6], np.int32), max_new_tokens=2)
    h2 = eng.submit(np.asarray([7, 8, 9], np.int32), max_new_tokens=2)
    assert len({h0._req.rid, h1._req.rid, h2._req.rid}) == 3
    assert eng.dedup_hits == 0
    cl = ServingCluster(model, n_replicas=2, cluster=True, **KW)
    c0 = cl.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=2,
                   rid="req-0")
    c1 = cl.submit(np.asarray([4, 5, 6], np.int32), max_new_tokens=2)
    c2 = cl.submit(np.asarray([7, 8, 9], np.int32), max_new_tokens=2)
    assert len({c0._req.rid, c1._req.rid, c2._req.rid}) == 3
    assert cl.dedup_hits == 0


# -- crash recovery (in-process) ---------------------------------------

def test_recover_serves_finished_from_log(model, work, baseline,
                                          tmp_path):
    cl = ServingCluster(model, n_replicas=2, cluster=True,
                        wal=str(tmp_path / "j"), **KW)
    _drive(cl, work)
    del cl   # whole-process crash: the journal is all that survives
    cl2 = ServingCluster.recover(model, str(tmp_path / "j"),
                                 n_replicas=2, cluster=True, **KW)
    assert cl2.recovery["served_from_log"] == len(baseline)
    assert cl2.recovery["resubmitted"] == 0
    for rid, toks in baseline.items():
        h = cl2.recovered_handles[rid]
        assert h.state in (RequestState.FINISHED,
                           RequestState.TRUNCATED)
        assert h.tokens == toks and h._req.recovered
    # nothing recomputed: the fleet never decoded a token
    assert cl2.stats()["decode_tokens"] == 0
    # at-least-once resubmission of every rid dedupes to the log copy
    handles = {w["rid"]: cl2.submit(
        w["prompt_ids"], max_new_tokens=w["max_new_tokens"],
        rid=w["rid"]) for w in work}
    assert cl2.dedup_hits == len(work)
    _assert_bit_identical(cl2, handles, baseline)


@pytest.mark.slow
def test_recover_resubmits_in_flight(model, work, baseline, tmp_path):
    cl = ServingCluster(model, n_replicas=2, cluster=True,
                        wal=str(tmp_path / "j"), **KW)
    i = 0
    while cl.tick < 8:          # abandon mid-load, streams unfinished
        while i < len(work) and work[i]["arrival_tick"] <= cl.tick:
            w = work[i]
            i += 1
            cl.submit(w["prompt_ids"],
                      max_new_tokens=w["max_new_tokens"], rid=w["rid"])
        cl.step()
    submitted = {w["rid"] for w in work[:i]}
    del cl
    cl2 = ServingCluster.recover(model, str(tmp_path / "j"),
                                 n_replicas=2, cluster=True, **KW)
    rec = cl2.recovery
    assert rec["resubmitted"] > 0
    assert rec["served_from_log"] + rec["resubmitted"] == len(submitted)
    assert set(cl2.recovered_handles) == submitted
    # the client replays its whole workload (at-least-once): journaled
    # rids dedup, never-submitted ones serve fresh — exactly once each
    handles = {w["rid"]: cl2.submit(
        w["prompt_ids"], max_new_tokens=w["max_new_tokens"],
        rid=w["rid"]) for w in work}
    assert cl2.dedup_hits == len(submitted)
    _drain(cl2)
    _assert_bit_identical(cl2, handles, baseline)
    # recovery is itself journaled: a second recovery still converges
    cl3 = ServingCluster.recover(model, str(tmp_path / "j"),
                                 n_replicas=2, cluster=True, **KW)
    for rid, toks in baseline.items():
        assert cl3.recovered_handles[rid].tokens == toks


def test_recover_advances_anonymous_rids(model, tmp_path):
    # journaled req-N rids must not capture post-recovery anonymous
    # submits: _next_rid restarts at 0, so recover() advances it past
    # every replayed auto rid
    cl = ServingCluster(model, n_replicas=2, cluster=True,
                        wal=str(tmp_path / "j"), **KW)
    old = [cl.submit(np.asarray([3, i + 1], np.int32), max_new_tokens=3)
           for i in range(3)]
    rids = {h._req.rid for h in old}
    assert rids == {"req-0", "req-1", "req-2"}
    for h in old:
        h.result()
    del cl
    cl2 = ServingCluster.recover(model, str(tmp_path / "j"),
                                 n_replicas=2, cluster=True, **KW)
    assert cl2._next_rid == 3
    h = cl2.submit(np.asarray([9, 9], np.int32), max_new_tokens=2)
    assert h._req.rid not in rids and not h._req.recovered
    assert cl2.dedup_hits == 0
    assert h.result()   # a live fresh stream, not someone's log copy


def test_recover_resubmit_after_shed_supersedes_reject(model, tmp_path):
    # "r1" was shed with retry_after, then resubmitted and finished
    # before the crash: recovery restores the finished stream, not the
    # stale rejection.  A shed-only rid ("r2") restores nothing and is
    # neither corrupt nor deduped — post-crash retries serve it fresh,
    # exactly like the live shed path.
    eng = ServingEngine(model, wal=str(tmp_path / "j"), **KW)
    for rid in ("r1", "r2"):
        eng.wal.append({"t": "reject", "rid": rid,
                        "reason": "overload", "retry_after": 2})
    toks = eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=4,
                      rid="r1").result()
    eng.wal.close()
    cl = ServingCluster.recover(model, str(tmp_path / "j"),
                                n_replicas=2, cluster=True, **KW)
    assert set(cl.recovered_handles) == {"r1"}
    h = cl.recovered_handles["r1"]
    assert h.state is not RequestState.REJECTED and h.tokens == toks
    assert cl.recovery["corrupt"] == 0
    assert cl.recovery["served_from_log"] == 1
    h2 = cl.submit(np.asarray([5, 6], np.int32), max_new_tokens=2,
                   rid="r2")
    assert cl.dedup_hits == 0 and h2.result()


# -- crash recovery (real subprocess, SIGKILL) --------------------------

def _run_worker_until(wal_dir, kill_after, fault_spec="", timeout=240):
    """Spawn the serving worker; SIGKILL it once its journal holds
    ``kill_after`` records (or let an armed crash fault kill it).
    Returns (returncode, drained)."""
    proc = subprocess.Popen(
        [sys.executable, WORKER, str(wal_dir), fault_spec],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PT_FAULTS": ""})
    drained = False
    deadline = time.monotonic() + timeout
    try:
        for line in proc.stdout:
            assert time.monotonic() < deadline, "worker timed out"
            if line.startswith("DRAINED"):
                drained = True
            if kill_after is not None and line.startswith("tick "):
                appended = int(line.split()[-1])
                if appended >= kill_after:
                    proc.kill()          # SIGKILL, no goodbye
                    break
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return proc.returncode, drained


def _recover_and_verify(model, wal_dir, work, baseline):
    cl = ServingCluster.recover(model, str(wal_dir), n_replicas=2,
                                cluster=True, **KW)
    _audit(cl)
    # zero request loss: every journaled rid has a handle, and the
    # client's at-least-once replay of the workload completes all 8
    assert cl.recovery["records"] > 0
    handles = {w["rid"]: cl.submit(
        w["prompt_ids"], max_new_tokens=w["max_new_tokens"],
        rid=w["rid"]) for w in work}
    assert cl.dedup_hits == len(cl.recovered_handles)
    _drain(cl)
    _assert_bit_identical(cl, handles, baseline)
    return cl


# three seeded kill points: early (prefills in flight), mid (decode
# steady-state), late (most streams finished).  One rides the fast
# lane; the others are slow-marked for the tier-1 budget.
@pytest.mark.parametrize("kill_after", [
    pytest.param(20, marks=pytest.mark.slow),
    pytest.param(6, marks=pytest.mark.slow),
    pytest.param(34, marks=pytest.mark.slow),
])
def test_sigkill_subprocess_recovers(model, work, baseline, tmp_path,
                                     kill_after):
    rc, drained = _run_worker_until(tmp_path / "j", kill_after)
    assert rc == -signal.SIGKILL and not drained
    cl = _recover_and_verify(model, tmp_path / "j", work, baseline)
    assert cl.recovery["resubmitted"] + cl.recovery["served_from_log"] \
        == len(cl.recovered_handles)


@pytest.mark.slow
@pytest.mark.parametrize("fault_spec", [
    "wal.append:after:12=crash",     # hard kill right after an append
    "wal.fsync:before:2=crash",      # ...and before a batched barrier
    "wal.append:after:12=truncate",  # torn write + hard kill
])
def test_crash_fault_subprocess_recovers(model, work, baseline,
                                         tmp_path, fault_spec):
    rc, drained = _run_worker_until(tmp_path / "j", None,
                                    fault_spec=fault_spec)
    assert rc == faults.EXIT_CODE and not drained
    _recover_and_verify(model, tmp_path / "j", work, baseline)


@pytest.mark.slow
def test_wal_compact_preserves_recovery(model, work, baseline,
                                        tmp_path):
    """Compacting a SIGKILLed process's journal (the ops idiom before
    archiving or re-serving it) must not change what recover
    reconstructs: zero loss, streams bit-identical."""
    rc, drained = _run_worker_until(tmp_path / "j", 20)
    assert rc == -signal.SIGKILL and not drained
    rep = compact(tmp_path / "j")
    assert rep["records_kept"] > 0
    _recover_and_verify(model, tmp_path / "j", work, baseline)


# -- torn tails and bit-rot --------------------------------------------

def _write_records(path, recs, **kw):
    wal = WriteAheadLog(path, **kw)
    for r in recs:
        wal.append(r)
    wal.close()
    return wal


def test_torn_tail_truncated_on_replay(tmp_path):
    _write_records(tmp_path / "j",
                   [{"t": "token", "rid": "r", "tok": i}
                    for i in range(6)])
    seg = segment_paths(tmp_path / "j")[-1]
    with open(seg, "ab") as f:
        f.write(b"deadbeef {\"t\": \"tok")   # half-written final record
    recs, report = replay(tmp_path / "j")
    assert [r["tok"] for r in recs] == list(range(6))
    assert report["torn_bytes"] > 0
    # the tear was physically truncated: replay is now clean, and a
    # new writer appends AFTER the repair point, never behind garbage
    recs2, report2 = replay(tmp_path / "j")
    assert [r["tok"] for r in recs2] == list(range(6))
    assert report2["torn_bytes"] == 0


def test_corrupt_interior_record_skipped(tmp_path):
    _write_records(tmp_path / "j",
                   [{"t": "token", "rid": "r", "tok": i}
                    for i in range(6)])
    seg = segment_paths(tmp_path / "j")[-1]
    with open(seg, "r+b") as f:
        raw = f.read()
        pos = raw.index(b'"tok":2')       # flip a byte mid-record
        f.seek(pos)
        f.write(b"X")
    recs, report = replay(tmp_path / "j")
    assert report["corrupt"] == 1 and report["torn_bytes"] == 0
    assert [r["tok"] for r in recs] == [0, 1, 3, 4, 5]


@pytest.mark.slow
def test_corrupt_token_record_downgrades_to_recompute(
        model, work, baseline, tmp_path):
    cl = ServingCluster(model, n_replicas=2, cluster=True,
                        wal=str(tmp_path / "j"), **KW)
    _drive(cl, work)
    del cl
    # bit-rot one token record of a FINISHED stream: its finish crc no
    # longer matches the replayable prefix, so recovery must refuse to
    # serve it from the log and recompute it instead
    victim = max(baseline, key=lambda r: len(baseline[r]))
    for seg in segment_paths(tmp_path / "j"):
        with open(seg, "r+b") as f:
            raw = f.read()
            needle = f'"t":"token","rid":"{victim}"'.encode()
            pos = raw.find(needle)
            if pos >= 0:
                f.seek(pos)
                f.write(b"X")
                break
    else:
        pytest.fail(f"no token record found for {victim}")
    cl2 = ServingCluster.recover(model, str(tmp_path / "j"),
                                 n_replicas=2, cluster=True, **KW)
    assert cl2.recovery["corrupt"] >= 1
    assert cl2.recovery["resubmitted"] >= 1
    assert not cl2.recovered_handles[victim]._req.terminal
    _drain(cl2)
    for rid, toks in baseline.items():
        assert cl2.recovered_handles[rid].tokens == toks, rid


# -- journaling faults must never take serving down ---------------------

@pytest.mark.slow
@pytest.mark.parametrize("point,phase", [
    ("wal.append", "before"),
    ("wal.append", "after"),
    ("wal.fsync", "before"),
    ("wal.fsync", "after"),
])
def test_wal_fault_degrades_not_serving(model, work, baseline,
                                        tmp_path, point, phase):
    faults.reset(f"{point}:{phase}:3=raise")
    cl = ServingCluster(model, n_replicas=2, cluster=True,
                        wal=str(tmp_path / "j"), **KW)
    cl.wal.fsync_every = 2      # make fsync faults reachable
    handles = _drive(cl, work)
    _assert_bit_identical(cl, handles, baseline)
    assert cl.wal.errors >= 1   # the journal degraded, serving didn't


def test_wal_replay_raise_is_clean(tmp_path):
    _write_records(tmp_path / "j", [{"t": "token", "rid": "r", "tok": 1}])
    faults.reset("wal.replay:before:1=raise")
    with pytest.raises(faults.InjectedFault):
        replay(tmp_path / "j")
    faults.reset("")
    recs, _ = replay(tmp_path / "j")    # the journal is unharmed
    assert [r["tok"] for r in recs] == [1]


# -- hung-replica KV-page salvage --------------------------------------

def _hang_and_drive(model, work, spec, **cluster_kw):
    faults.reset(spec)
    cl = ServingCluster(model, n_replicas=2, cluster=True,
                        beat_timeout=2, **cluster_kw, **KW)
    handles = _drive(cl, work)
    faults.reset("")
    return cl, handles


@pytest.mark.slow
def test_salvage_on_hang_skips_reprefill(model, work, baseline):
    hang = "replica.fail:before:7=hang"
    cl, handles = _hang_and_drive(model, work, hang)
    _assert_bit_identical(cl, handles, baseline)
    assert cl.salvages >= 1 and cl.salvaged_pages > 0
    assert cl.failovers >= cl.salvages
    # the measured point of the tentpole: pages moved instead of
    # re-prefilled — strictly fewer prefill tokens than the recompute
    # failover pays on the identical schedule
    ref, ref_handles = _hang_and_drive(model, work, hang, salvage=False)
    _assert_bit_identical(ref, ref_handles, baseline)
    assert ref.salvages == 0
    assert cl.stats()["prefill_tokens"] < ref.stats()["prefill_tokens"]


@pytest.mark.slow
@pytest.mark.parametrize("spec,expect_salvage", [
    # in-flight corruption: the crc32 verify must catch it + recompute
    ("replica.fail:before:7=hang,kv.salvage:before:1=inject", False),
    # injected raise before the copy: clean fallback to recompute
    ("replica.fail:before:7=hang,kv.salvage:before:1=raise", False),
    # raise after landing: the salvage commits (pages verified)
    ("replica.fail:before:7=hang,kv.salvage:after:1=raise", True),
])
def test_salvage_faults_fall_back_bit_identically(
        model, work, baseline, spec, expect_salvage):
    cl, handles = _hang_and_drive(model, work, spec)
    _assert_bit_identical(cl, handles, baseline)
    if expect_salvage:
        assert cl.salvages >= 1 and cl.salvages_failed == 0
    else:
        assert cl.salvages == 0 and cl.salvages_failed >= 1


@pytest.mark.slow
def test_crash_victim_never_salvaged(model, work, baseline):
    # a CRASHED engine's pool is garbage: the recompute path serves
    cl, handles = _hang_and_drive(model, work,
                                  "replica.fail:before:7=crash")
    _assert_bit_identical(cl, handles, baseline)
    assert cl.salvages == 0 and cl.failovers >= 1
