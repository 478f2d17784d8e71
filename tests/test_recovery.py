"""Crash-consistent recovery: the bitwise replay bar.

The contract (src/repro/checkpoint/summary.py): a summarizer killed at ANY
chunk boundary and recovered from its checkpoint directory — latest valid
epoch + deterministic journal-tail replay — must be leaf-bitwise equal to
the uninterrupted run, both at the kill point and after continuing to the
end of the stream.  Faults are injected with :mod:`repro.ft.inject`; every
scenario recovers through the same public ``recover()`` path a production
driver uses (``launch/stream.py --resume``), never through engine
internals.

Execution-variant coverage (replica_exec x trial_backend x policy) comes
from the CI router-stress matrix running this file under the REPRO_* env
vars; the tests only use defaults.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.checkpoint import checkpointer
from repro.checkpoint.summary import ConfigMismatchError
from repro.core.engine import (BatchedSummarizer, EngineConfig,
                               ShardedSummarizer)
from repro.ft import inject
from repro.graph.streams import edges_to_fully_dynamic_stream, sbm_edges

SRC = str(Path(__file__).resolve().parent.parent / "src")

CFG = EngineConfig(n_cap=160, m_cap=1024, d_cap=48, sn_cap=32, c=8,
                   batch=8, escape=0.3)


def _stream(n=56):
    edges = sbm_edges(44, 4, 0.5, 0.05, seed=11)
    return edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=11)[:n]


def _labels(stream, k=10):
    """First k distinct caller labels, in stream order (all seen, so the
    query layer cannot LookupError)."""
    seen = []
    for (u, v, _ins) in stream:
        for lab in (u, v):
            if lab not in seen:
                seen.append(lab)
    return seen[:k]


def assert_leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _batched(ckpt_dir=None):
    return BatchedSummarizer(CFG, checkpoint_dir=ckpt_dir)


def _sharded(ckpt_dir=None, **kw):
    kw.setdefault("n_shards", 2)
    kw.setdefault("router_chunk", 32)
    return ShardedSummarizer(CFG, checkpoint_dir=ckpt_dir, **kw)


def _snapshots(summ, stream):
    """Uninterrupted run, recording the closure after every chunk."""
    size = summ.dispatch_chunk
    snaps = []
    for off in range(0, len(stream), size):
        summ.process(stream[off:off + size])
        summ.flush()
        snaps.append((summ._ckpt_tree(), summ._ckpt_host()))
    return snaps


# --------------------------------------------------------------------------- #
# the bar: kill at EVERY chunk boundary, recover, bitwise-match
# --------------------------------------------------------------------------- #


def test_batched_kill_at_every_chunk_boundary_bitwise(tmp_path):
    stream = _stream(56)
    ref = _batched()
    snaps = _snapshots(ref, stream)         # 7 chunks of batch=8
    n_chunks = len(snaps)
    assert n_chunks == 7
    for k in range(n_chunks + 1):           # incl. kill after final dispatch
        d = str(tmp_path / f"k{k}")
        crashed = _batched(d)
        with pytest.raises(inject.SimulatedCrash):
            inject.drive(crashed, stream, ckpt_every=2, kill_at_chunk=k)
        rec = _batched(d)
        info = rec.recover()
        # recovery lands exactly at the kill point: k chunks were journaled
        # and dispatched before the crash, none after
        assert rec.stream_cursor == k * CFG.batch, info
        if k > 0:
            assert_leaves_equal(rec._ckpt_tree(), snaps[k - 1][0])
            assert rec._ckpt_host() == snaps[k - 1][1]
        inject.drive(rec, stream, start=rec.stream_cursor)
        assert_leaves_equal(rec.state, ref.state)
        assert rec._ids == ref._ids and rec._rev == ref._rev
        s1, s2 = ref.stats(), rec.stats()
        s1.pop("stream_retries"), s2.pop("stream_retries")
        assert s1 == s2


def test_sharded_kill_at_every_chunk_boundary_bitwise(tmp_path):
    stream = _stream(160)
    ref = _sharded()
    snaps = _snapshots(ref, stream)         # 5 chunks of router_chunk=32
    n_chunks = len(snaps)
    assert n_chunks == 5
    q_ref = ref.query()
    ref_deg = {u: q_ref.degree(u) for u in _labels(stream)}
    for k in range(n_chunks + 1):
        d = str(tmp_path / f"k{k}")
        crashed = _sharded(d)
        with pytest.raises(inject.SimulatedCrash):
            inject.drive(crashed, stream, ckpt_every=2, kill_at_chunk=k)
        rec = _sharded(d)
        rec.recover()
        assert rec.stream_cursor == k * 32
        if k > 0:
            rec.flush()
            assert_leaves_equal(rec._ckpt_tree(), snaps[k - 1][0])
            ref_host, rec_host = snaps[k - 1][1], rec._ckpt_host()
            assert ref_host["h2label"] == rec_host["h2label"]
            np.testing.assert_array_equal(ref_host["drain_rounds"],
                                          rec_host["drain_rounds"])
        inject.drive(rec, stream, start=rec.stream_cursor)
        rec.flush()                         # drain the pipelined last chunk
        assert_leaves_equal(rec.state, ref.state)
        assert_leaves_equal(rec.intern, ref.intern)
        assert rec.host_label_map() == ref.host_label_map()
        s1, s2 = ref.stats(), rec.stats()
        s1.pop("stream_retries"), s2.pop("stream_retries")
        assert s1 == s2
        # serve/query answers identical post-recovery
        q = rec.query()
        assert {u: q.degree(u) for u in ref_deg} == ref_deg


def test_query_answers_survive_mid_stream_recovery(tmp_path):
    """Answers from the recovered engine at the kill point equal answers
    from an uninterrupted run over the same prefix."""
    stream = _stream(160)
    k, cut = 3, 3 * 32
    prefix = _sharded()
    prefix.process(stream[:cut])
    qp = prefix.query()
    want = {u: (qp.degree(u), sorted(qp.neighbors(u)))
            for u in _labels(stream[:cut])}

    d = str(tmp_path / "ck")
    crashed = _sharded(d)
    with pytest.raises(inject.SimulatedCrash):
        inject.drive(crashed, stream, ckpt_every=2, kill_at_chunk=k)
    rec = _sharded(d)
    rec.recover()
    q = rec.query()
    got = {u: (q.degree(u), sorted(q.neighbors(u))) for u in want}
    assert got == want


@pytest.mark.parametrize("routing", ["device", "host"])
def test_engine_round_counter_recovers_bitwise(tmp_path, routing):
    """The engine-round count rides in the recovery closure (device
    telemetry, or the host count on the host path): a run killed
    mid-stream and recovered counts exactly the rounds of the
    uninterrupted run, at the kill point and at the end."""
    stream = _stream(160)
    ref = _sharded(routing=routing)
    snaps = _snapshots(ref, stream)
    want = ref.stats()["engine_rounds"]
    assert want >= len(snaps)
    prefix = _sharded(routing=routing)
    inject.drive(prefix, stream[:3 * 32])
    d = str(tmp_path)
    crashed = _sharded(d, routing=routing)
    with pytest.raises(inject.SimulatedCrash):
        inject.drive(crashed, stream, ckpt_every=2, kill_at_chunk=3)
    rec = _sharded(d, routing=routing)
    rec.recover()
    rec.flush()
    assert rec.stats()["engine_rounds"] == prefix.stats()["engine_rounds"]
    np.testing.assert_array_equal(rec._ckpt_host()["drain_rounds"],
                                  snaps[2][1]["drain_rounds"])
    inject.drive(rec, stream, start=rec.stream_cursor)
    rec.flush()
    assert rec.stats()["engine_rounds"] == want
    assert_leaves_equal(rec._ckpt_tree(), ref._ckpt_tree())
    host = rec._ckpt_host()
    np.testing.assert_array_equal(host["drain_rounds"],
                                  snaps[-1][1]["drain_rounds"])
    assert host["host_engine_rounds"] == snaps[-1][1]["host_engine_rounds"]


def test_closure_without_engine_round_column_restores_it_at_zero():
    """A recovery closure written before the engine-round counter (drain
    telemetry ``int32[n_dev]``, no host count) restores with the count at
    0 and every other leaf and counter unchanged."""
    stream = _stream(96)
    ref = _sharded()
    inject.drive(ref, stream)
    ref.flush()
    old_host = dict(ref._ckpt_host())
    old_host["drain_rounds"] = old_host["drain_rounds"][:, 0]
    del old_host["host_engine_rounds"]
    extra = {"epoch": ref.flush_epoch, "journal_seq": ref._journal_seq,
             "cursor": ref.stream_cursor}
    fresh = _sharded()
    fresh._ckpt_apply(ref._ckpt_tree(), old_host, extra)
    assert_leaves_equal(fresh._ckpt_tree(), ref._ckpt_tree())
    got, want = fresh.stats(), ref.stats()
    assert want["engine_rounds"] > 0 and got["engine_rounds"] == 0
    got.pop("engine_rounds"), want.pop("engine_rounds")
    assert got == want


_WITHOUT_PASSES = {
    "batched": _batched,
    "sharded-map": lambda d=None: _sharded(d, replica_exec="map"),
    "sharded-vmap": lambda d=None: _sharded(d, replica_exec="vmap"),
}


@pytest.mark.parametrize("make", list(_WITHOUT_PASSES))
def test_checkpoint_without_trial_passes_restores_them_at_zero(tmp_path,
                                                               make):
    """A checkpoint written before the engine state's ``n_passes`` leaf
    recovers with that count at 0 and every other leaf and counter
    bitwise unchanged — in both summarizers and both replica layouts."""
    make = _WITHOUT_PASSES[make]
    stream = _stream(96)
    d = str(tmp_path)
    ref = make(d)
    inject.drive(ref, stream)
    ref.flush()
    full = ref._ckpt_tree()
    old = {k: dict(v) for k, v in full.items()}
    del old["est"]["n_passes"]
    ref._ckpt_tree = lambda: old
    ref.save()
    del ref._ckpt_tree
    assert "est/n_passes" not in np.load(os.path.join(
        d, f"step_{checkpointer.latest_step(d):08d}", "arrays.npz")).files
    rec = make(d)
    info = rec.recover()
    assert info["replayed_chunks"] == 0
    got = rec._ckpt_tree()
    assert not np.asarray(got["est"]["n_passes"]).any()
    got["est"]["n_passes"] = full["est"]["n_passes"]
    assert_leaves_equal(got, full)
    want, have = ref.stats(), rec.stats()
    if want.get("trial_passes") is not None:
        assert want["trial_passes"] > 0 and have["trial_passes"] == 0
        want.pop("trial_passes"), have.pop("trial_passes")
    assert have == want


# --------------------------------------------------------------------------- #
# checkpoint faults
# --------------------------------------------------------------------------- #


def _crash_at(make, d, stream, k=5, ckpt_every=2):
    s = make(d)
    with pytest.raises(inject.SimulatedCrash):
        inject.drive(s, stream, ckpt_every=ckpt_every, kill_at_chunk=k)


def test_corrupt_newest_checkpoint_falls_back_one_epoch(tmp_path):
    stream = _stream(56)
    ref = _batched()
    inject.drive(ref, stream)
    d = str(tmp_path)
    _crash_at(_batched, d, stream)
    newest = inject.latest_checkpoint_step(d)
    inject.corrupt_checkpoint_arrays(d, newest)
    rec = _batched(d)
    info = rec.recover()
    assert info["step"] < newest            # checksum caught it, fell back
    assert info["rejected"] and "integrity" in info["rejected"][0]
    # journal retention reaches back to the SURVIVING epoch, so the replay
    # crosses the gap the corrupt checkpoint left
    assert info["replayed_chunks"] > 0
    inject.drive(rec, stream, start=rec.stream_cursor)
    assert_leaves_equal(rec.state, ref.state)


def test_all_checkpoints_corrupt_raises(tmp_path):
    stream = _stream(56)
    d = str(tmp_path)
    _crash_at(_batched, d, stream)
    for s in checkpointer.checkpoint_steps(d):
        inject.corrupt_checkpoint_arrays(d, s)
    with pytest.raises(FileNotFoundError, match="no restorable checkpoint"):
        _batched(d).recover()


def test_torn_staging_directory_is_ignored(tmp_path):
    stream = _stream(56)
    ref = _batched()
    inject.drive(ref, stream)
    d = str(tmp_path)
    _crash_at(_batched, d, stream)
    inject.tear_checkpoint_staging(d, inject.latest_checkpoint_step(d))
    rec = _batched(d)
    info = rec.recover()
    assert not info["rejected"]             # .tmp is invisible, not an error
    inject.drive(rec, stream, start=rec.stream_cursor)
    assert_leaves_equal(rec.state, ref.state)


def test_dropped_payload_file_detected(tmp_path):
    stream = _stream(56)
    d = str(tmp_path)
    _crash_at(_batched, d, stream)
    newest = inject.latest_checkpoint_step(d)
    inject.drop_checkpoint_file(d, newest, "host.pkl")
    rec = _batched(d)
    info = rec.recover()
    assert info["step"] < newest and info["rejected"]


# --------------------------------------------------------------------------- #
# journal faults
# --------------------------------------------------------------------------- #


def test_torn_journal_tail_recovers_valid_prefix(tmp_path):
    stream = _stream(56)
    ref = _batched()
    inject.drive(ref, stream)
    d = str(tmp_path)
    _crash_at(_batched, d, stream)          # 5 chunks journaled, ckpt at 4
    n = inject.journal_record_count(d)
    inject.truncate_journal_tail(d, nbytes=7)
    assert inject.journal_record_count(d) == n - 1
    rec = _batched(d)
    rec.recover()                           # lost exactly the torn chunk
    assert rec.stream_cursor == (5 - 1) * CFG.batch
    inject.drive(rec, stream, start=rec.stream_cursor)
    assert_leaves_equal(rec.state, ref.state)


def test_duplicated_journal_record_deduped(tmp_path):
    stream = _stream(56)
    ref = _batched()
    inject.drive(ref, stream)
    d = str(tmp_path)
    _crash_at(_batched, d, stream)
    inject.duplicate_journal_tail(d)
    rec = _batched(d)
    rec.recover()                           # replayed once, not twice
    assert rec.stream_cursor == 5 * CFG.batch
    inject.drive(rec, stream, start=rec.stream_cursor)
    assert_leaves_equal(rec.state, ref.state)


def test_fresh_run_resets_stale_journal(tmp_path):
    stream = _stream(56)
    d = str(tmp_path)
    _crash_at(_batched, d, stream)
    assert inject.journal_record_count(d) > 0
    fresh = _batched(d)                     # NOT recovered: a new run
    fresh.process(stream[:CFG.batch])
    assert inject.journal_record_count(d) == 1


# --------------------------------------------------------------------------- #
# manifest pins: refuse state from a different configuration
# --------------------------------------------------------------------------- #


def test_restore_refuses_different_policy_triple(tmp_path):
    d = str(tmp_path)
    s = _batched(d)
    s.process(_stream(16))
    s.save()
    other = BatchedSummarizer(
        EngineConfig(**{**CFG.manifest(), "commit": "threshold"}),
        checkpoint_dir=d)
    with pytest.raises(ConfigMismatchError, match="config"):
        other.restore()


def test_restore_refuses_different_n_shards_or_chunk(tmp_path):
    stream = _stream(64)
    d = str(tmp_path)
    s = _sharded(d)
    s.process(stream)
    s.save()
    with pytest.raises(ConfigMismatchError, match="n_shards"):
        _sharded(d, n_shards=4).restore()
    with pytest.raises(ConfigMismatchError, match="router_chunk"):
        _sharded(d, router_chunk=64).restore()


def test_restore_refuses_batched_into_sharded(tmp_path):
    d = str(tmp_path)
    s = _batched(d)
    s.process(_stream(16))
    s.save()
    with pytest.raises(ConfigMismatchError, match="tier"):
        _sharded(d).restore()


# --------------------------------------------------------------------------- #
# query-view fencing + retry loop
# --------------------------------------------------------------------------- #


def test_stale_query_view_fenced_after_restore(tmp_path):
    stream = _stream(160)
    d = str(tmp_path)
    s = _sharded(d)
    s.process(stream)
    s.save()
    lab = _labels(stream, 1)[0]
    stale = s.query()
    assert stale.degree(lab) >= 0           # live before the restore
    s.restore()
    with pytest.raises(RuntimeError, match="predates a checkpoint restore"):
        stale.degree(lab)
    assert s.query().degree(lab) >= 0       # a fresh view works


def test_run_stream_with_recovery_counts_retries(tmp_path):
    from repro.ft.resilience import run_stream_with_recovery
    stream = _stream(56)
    ref = _batched()
    inject.drive(ref, stream)

    class Flaky(BatchedSummarizer):
        crashes = [3, 5]                    # shared across rebuilds

        def process(self, changes):
            if self.crashes and self._journal_seq == self.crashes[0]:
                self.crashes.pop(0)
                raise RuntimeError("injected engine fault")
            super().process(changes)

    s = run_stream_with_recovery(
        lambda: Flaky(CFG, checkpoint_dir=str(tmp_path)),
        stream, str(tmp_path), ckpt_every=2, sleep=lambda _t: None)
    assert s.stats()["stream_retries"] == 2
    assert_leaves_equal(s.state, ref.state)
    # the final save() leaves a resumable epoch at end-of-stream
    rec = _batched(str(tmp_path))
    info = rec.recover()
    assert rec.stream_cursor == len(stream) and info["replayed_chunks"] == 0
    assert_leaves_equal(rec.state, ref.state)


def test_retry_loop_gives_up_past_max_failures(tmp_path):
    from repro.ft.resilience import run_stream_with_recovery

    class Doomed(BatchedSummarizer):
        def process(self, changes):
            raise RuntimeError("always fails")

    with pytest.raises(RuntimeError, match="always fails"):
        run_stream_with_recovery(
            lambda: Doomed(CFG, checkpoint_dir=str(tmp_path)),
            _stream(56), str(tmp_path), ckpt_every=2, max_failures=2,
            sleep=lambda _t: None)


# --------------------------------------------------------------------------- #
# elastic restore: checkpoint on 8 devices, recover on 1
# --------------------------------------------------------------------------- #


def test_checkpoint_on_8_devices_recovers_on_one(tmp_path):
    """A sharded run checkpoints mid-stream under 8 fake devices; this
    1-device process recovers it (same n_shards — the pinned quantity),
    continues, and must land bitwise on the 8-device run's final state.
    Topology is recorded in the manifest but NOT pinned: replica layout is
    bit-transparent per the standing differential bar."""
    d = str(tmp_path)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    code = textwrap.dedent(f"""
        import jax
        from repro.core.engine import EngineConfig, ShardedSummarizer
        from repro.ft import inject
        from repro.graph.streams import (edges_to_fully_dynamic_stream,
                                         sbm_edges)
        assert len(jax.devices()) == 8
        cfg = EngineConfig(**{CFG.manifest()!r})
        edges = sbm_edges(44, 4, 0.5, 0.05, seed=11)
        stream = edges_to_fully_dynamic_stream(
            edges, delete_prob=0.2, seed=11)[:160]
        s = ShardedSummarizer(cfg, n_shards=8, router_chunk=32,
                              checkpoint_dir={d!r})
        try:
            inject.drive(s, stream, ckpt_every=2, kill_at_chunk=3)
        except inject.SimulatedCrash:
            pass
        full = ShardedSummarizer(cfg, n_shards=8, router_chunk=32,
                                 checkpoint_dir={d!r} + "/full")
        inject.drive(full, stream)
        full.save()
        print("phi", full.phi)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]

    stream = _stream(160)
    rec = _sharded(d, n_shards=8)           # 1 device, 8 shards
    info = rec.recover()
    assert info["replayed_chunks"] > 0      # journal tail crossed topologies
    inject.drive(rec, stream, start=rec.stream_cursor)
    rec.flush()

    # compare against the 8-device run's own final checkpoint, leaf by leaf
    like = rec._ckpt_tree()
    step8 = checkpointer.latest_valid_step(d + "/full")
    tree8 = checkpointer.restore(d + "/full", step8, like)
    assert_leaves_equal(like, tree8)
    meta8 = checkpointer.load_meta(d + "/full", step8)
    assert meta8["extra"]["manifest"]["n_devices"] == 8
    assert meta8["extra"]["cursor"] == rec.stream_cursor
    # the recovered engine serves queries
    q = rec.query()
    assert sum(q.degree(u) for u in _labels(stream)) > 0
