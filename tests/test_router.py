"""Host-routed vs device-routed ShardedSummarizer differential tests.

The device router (repro/dist/router.py) must be a drop-in replacement for
host bucketing: fed the same FD stream with the same ``process`` call
boundaries, both modes intern nodes in the same per-shard order and advance
every engine replica's PRNG identically, so the engine states — and hence
phi — are bit-comparable after every batch.  This extends the standing
differential verification bar (ROADMAP) to the routing layer.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # container has no hypothesis; deterministic shim
    from repro.testing.proptest import given, settings, strategies as st

from repro.core.engine import EngineConfig, ShardedSummarizer
from repro.graph.streams import edges_to_fully_dynamic_stream, sbm_edges

from conftest import ground_truth_edges


def _cfg(**kw):
    base = dict(n_cap=160, m_cap=1024, d_cap=48, sn_cap=32, c=8, batch=8,
                escape=0.3)
    base.update(kw)
    return EngineConfig(**base)


def _stream(seed=11):
    edges = sbm_edges(44, 4, 0.5, 0.05, seed=seed)
    return edges_to_fully_dynamic_stream(edges, delete_prob=0.2,
                                         seed=seed + 1)


@pytest.mark.parametrize("n_shards", [1, 2])
def test_device_vs_host_routing_differential(n_shards):
    """Identical phi + lossless decode after every batch, 1 device."""
    stream = _stream()
    cfg = _cfg()
    kw = dict(n_shards=n_shards, router_chunk=64)
    dev = ShardedSummarizer(cfg, routing="device", **kw)
    host = ShardedSummarizer(cfg, routing="host", **kw)
    live = set()

    for off in range(0, len(stream), 64):
        chunk = stream[off:off + 64]
        dev.process(chunk)
        host.process(chunk)
        for (u, v, ins) in chunk:
            e = (min(u, v), max(u, v))
            live.add(e) if ins else live.discard(e)
        tag = f"off={off}"
        # no lane overflow at this scale: pure device routing throughout
        assert dev.router_overflows == 0, tag
        # identical per-shard phi — the engines are in lockstep
        assert dev.shard_phis() == host.shard_phis(), tag
        # both satisfy the phi invariant and decode losslessly
        dm, hm = dev.materialize().validate(), host.materialize().validate()
        assert dm.phi == dev.phi == dev.phi_recomputed(), tag
        assert hm.phi == host.phi == host.phi_recomputed(), tag
        assert dm.decode_edges() == live, tag
        assert hm.decode_edges() == live, tag

    assert live == ground_truth_edges(stream)
    assert 0 < dev.phi <= len(live)
    assert dev.stats()["routing"] == "device"
    assert host.stats()["routing"] == "host"


def test_device_routing_states_bit_identical_to_host():
    """Beyond phi: every engine-state leaf matches between the modes."""
    stream = _stream(seed=21)
    cfg = _cfg()
    dev = ShardedSummarizer(cfg, routing="device", n_shards=2,
                            router_chunk=128).run(stream)
    host = ShardedSummarizer(cfg, routing="host", n_shards=2,
                             router_chunk=128).run(stream)
    assert dev.router_overflows == 0
    for d, h in zip(dev.host_states(), host.host_states()):
        for name, dl, hl in zip(d._fields, d, h):
            np.testing.assert_array_equal(
                np.asarray(dl), np.asarray(hl), err_msg=name)
    for d, h in zip(dev.host_interns(), host.host_interns()):
        assert int(d.n_nodes) == int(h.n_nodes)
        np.testing.assert_array_equal(np.asarray(d.l2h), np.asarray(h.l2h))


def test_lane_overflow_drains_on_device_by_default():
    """A tiny lane_cap no longer spills to the host: the default drain
    budget guarantees delivery, so the router re-ranks the suffix and runs
    extra all_to_all rounds instead — lossless, sync-free, no fallback."""
    stream = _stream(seed=31)
    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64, lane_cap=1)
    assert ss.sync_free and ss.router_geometry.drain_guaranteed
    ss.run(stream)
    st = ss.stats()
    assert ss.router_overflows == 0 and st["router_syncs"] == 0
    assert st["router_drain_rounds"] > 0       # the drain loop actually ran
    truth = ground_truth_edges(stream)
    assert ss.live_edges() == truth
    out = ss.materialize()
    assert out.decode_edges() == truth
    assert out.phi == ss.phi == ss.phi_recomputed()


def test_bounded_drain_budget_falls_back_to_host_path_losslessly():
    """An explicitly lowered max_drain_rounds keeps the PR-2 contract: the
    undelivered suffix replays through the host path in stream order, the
    spill is counted, and the run stays lossless."""
    stream = _stream(seed=31)
    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64, lane_cap=1, max_drain_rounds=1)
    assert not ss.sync_free          # bounded budget -> per-chunk watermark
    ss.run(stream)
    assert ss.router_overflows > 0
    assert ss.stats()["router_overflows"] == ss.router_overflows
    assert ss.stats()["router_syncs"] > 0
    truth = ground_truth_edges(stream)
    assert ss.live_edges() == truth
    out = ss.materialize()
    assert out.decode_edges() == truth
    assert out.phi == ss.phi == ss.phi_recomputed()


@pytest.mark.parametrize("routing", ["device", "host"])
def test_node_capacity_drop_raises_at_sync(routing):
    """Exceeding per-shard n_cap cannot silently lose changes: the device
    intern counter trips a RuntimeError at the next host sync point."""
    stream = _stream(seed=41)
    ss = ShardedSummarizer(_cfg(n_cap=16), routing=routing, n_shards=2,
                           router_chunk=64)
    ss.run(stream)    # streaming itself must NOT raise (raise-at-sync)
    with pytest.raises(RuntimeError, match="node capacity exceeded"):
        ss.stats()


def test_shard_of_is_read_only():
    """Placement is a pure function of the 62-bit label hash — host
    bucketing, the device router, and ``shard_of`` must all agree — and
    querying it mutates nothing: unseen labels raise instead of being
    assigned."""
    from repro.dist.labelhash import hash_label

    stream = _stream(seed=61)
    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64).run(stream)
    u, v, _ = stream[0]
    assert ss.shard_of(u, v) == min(hash_label(u), hash_label(v)) % 2
    n_before = len(ss._h2label)
    with pytest.raises(LookupError, match="has not been streamed"):
        ss.shard_of("never-streamed-a", "never-streamed-b")
    assert len(ss._h2label) == n_before


def test_arbitrary_hashable_labels_roundtrip():
    """Caller labels never touch the device: strings stream and decode."""
    stream = [(f"n{u}", f"n{v}", ins) for (u, v, ins) in _stream(seed=51)]
    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64).run(stream)
    truth = ground_truth_edges(stream)
    assert ss.live_edges() == truth
    assert ss.materialize().decode_edges() == truth


# --------------------------------------------------------------------------- #
# device-resident overflow drain + elided watermark sync (PR 3)
# --------------------------------------------------------------------------- #


def _skew_hub(leaves):
    """A hub label whose 62-bit hash undercuts every leaf's, so the
    canonical pair key ``min(h(u), h(v))`` is always the hub's and every
    change routes to ONE shard — the worst case for the capacity-bounded
    lanes.  (Placement is hash-based since PR 4; being streamed first no
    longer matters.)"""
    from repro.dist.labelhash import hash_label
    lo = min(hash_label(x) for x in leaves)
    return next(h for h in (f"hub{j}" for j in range(100_000))
                if hash_label(h) < lo)


def _skew_stream(n_leaves, delete_every=3):
    """Adversarial key skew: a star around a minimal-hash hub."""
    leaves = [f"x{i:03d}" for i in range(n_leaves)]
    hub = _skew_hub(leaves)
    ins = [(hub, x, True) for x in leaves]
    dels = [(hub, x, False) for x in leaves[::delete_every]]
    return ins + dels


def test_key_skew_multi_round_drain_bit_identical_to_host():
    """All changes hash to one shard at a tiny lane_cap: the drain loop
    delivers each chunk over many all_to_all rounds, losslessly and
    order-preservingly — the final engine/intern states are bit-identical
    to host routing, which is the strongest order statement available."""
    stream = _skew_stream(60)
    cfg = _cfg()
    dev = ShardedSummarizer(cfg, routing="device", n_shards=2,
                            router_chunk=64, lane_cap=2)
    host = ShardedSummarizer(cfg, routing="host", n_shards=2,
                             router_chunk=64)
    for off in range(0, len(stream), 64):
        dev.process(stream[off:off + 64])
        host.process(stream[off:off + 64])
    st = dev.stats()
    assert dev.router_overflows == 0       # no host replay was needed
    assert st["router_syncs"] == 0         # and no per-chunk watermark fetch
    assert st["router_drain_rounds"] >= 2  # genuinely multi-round
    assert dev.shard_phis() == host.shard_phis()
    for d, h in zip(dev.host_states(), host.host_states()):
        for name, dl, hl in zip(d._fields, d, h):
            np.testing.assert_array_equal(
                np.asarray(dl), np.asarray(hl), err_msg=name)
    for d, h in zip(dev.host_interns(), host.host_interns()):
        assert int(d.n_nodes) == int(h.n_nodes)
        np.testing.assert_array_equal(np.asarray(d.l2h), np.asarray(h.l2h))
    truth = ground_truth_edges(stream)
    assert dev.live_edges() == truth
    assert dev.materialize().decode_edges() == truth


@settings(max_examples=8, deadline=None)
@given(st.integers(20, 70), st.integers(1, 4), st.integers(2, 5))
def test_key_skew_drain_property(n_leaves, lane_cap, delete_every):
    """Property: for any star size / lane capacity / deletion cadence, the
    drain loop delivers fully on device (no fallback, no syncs) and the
    result is lossless and phi-identical to host routing."""
    stream = _skew_stream(n_leaves, delete_every)
    cfg = _cfg()
    dev = ShardedSummarizer(cfg, routing="device", n_shards=2,
                            router_chunk=32, lane_cap=lane_cap)
    host = ShardedSummarizer(cfg, routing="host", n_shards=2,
                             router_chunk=32)
    for off in range(0, len(stream), 32):
        dev.process(stream[off:off + 32])
        host.process(stream[off:off + 32])
    assert dev.router_overflows == 0 and dev.router_syncs == 0
    assert dev.shard_phis() == host.shard_phis()
    truth = ground_truth_edges(stream)
    assert dev.live_edges() == truth
    assert dev.materialize().decode_edges() == truth


def test_no_overflow_geometry_elides_watermark_sync():
    """With lane_cap == chunk // n_dev overflow is statically impossible:
    the compiled program carries no watermark collective, the geometry
    proves it (static_no_overflow), and process() performs zero per-chunk
    host syncs (router_syncs counts every watermark fetch)."""
    stream = _stream(seed=71)
    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64, lane_cap=64)
    g = ss.router_geometry
    assert g.static_no_overflow and g.max_drain_rounds == 1
    assert ss.sync_free
    for off in range(0, len(stream), 64):
        ss.process(stream[off:off + 64])
    st = ss.stats()
    assert st["router_syncs"] == 0 and st["router_sync_free"]
    assert st["router_drain_rounds"] == 0 and ss.router_overflows == 0
    assert ss.live_edges() == ground_truth_edges(stream)


def test_chunk_sync_forces_watermark_fetch_with_identical_results():
    """chunk_sync=True reinstates the per-chunk fetch (the measurement
    baseline for the sync-elision benchmark) without changing any result:
    same engine states, same phi, one sync per chunk."""
    stream = _stream(seed=81)
    free = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                             router_chunk=64)
    sync = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                             router_chunk=64, chunk_sync=True)
    assert free.sync_free and not sync.sync_free
    n_chunks = 0
    for off in range(0, len(stream), 64):
        free.process(stream[off:off + 64])
        sync.process(stream[off:off + 64])
        n_chunks += 1
    assert free.router_syncs == 0
    assert sync.router_syncs == n_chunks
    assert free.shard_phis() == sync.shard_phis()
    for a, b in zip(free.host_states(), sync.host_states()):
        for name, al, bl in zip(a._fields, a, b):
            np.testing.assert_array_equal(
                np.asarray(al), np.asarray(bl), err_msg=name)


def test_skew_drain_bit_identical_at_two_shards_per_device():
    """The skew-drain differential scaled to the mesh this process sees:
    n_shards = 2 * n_devices, so under the CI router-stress job
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) the drain
    loop's all_to_all, pmin watermark, and multi-round append all run on a
    REAL 8-device mesh inside this file — on the default 1-device tier-1
    run it degrades to the cheap 2-shard case."""
    import jax
    n_shards = 2 * len(jax.devices())
    stream = _skew_stream(60)
    cfg = _cfg()
    dev = ShardedSummarizer(cfg, routing="device", n_shards=n_shards,
                            router_chunk=64, lane_cap=2)
    host = ShardedSummarizer(cfg, routing="host", n_shards=n_shards,
                             router_chunk=64)
    assert dev.router_geometry.n_dev == len(jax.devices())
    assert dev.sync_free
    for off in range(0, len(stream), 64):
        dev.process(stream[off:off + 64])
        host.process(stream[off:off + 64])
    st = dev.stats()
    assert dev.router_overflows == 0 and st["router_syncs"] == 0
    assert st["router_drain_rounds"] >= 2
    assert dev.shard_phis() == host.shard_phis()
    for d, h in zip(dev.host_states(), host.host_states()):
        for name, dl, hl in zip(d._fields, d, h):
            np.testing.assert_array_equal(
                np.asarray(dl), np.asarray(hl), err_msg=name)
    truth = ground_truth_edges(stream)
    assert dev.live_edges() == truth
    assert dev.materialize().decode_edges() == truth


def test_default_lane_cap_is_sync_free_by_construction():
    """The out-of-the-box configuration must never pay the per-chunk sync:
    the default lane_cap + drain budget always yields a delivery
    guarantee."""
    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=128)
    assert ss.router_geometry.drain_guaranteed and ss.sync_free


# --------------------------------------------------------------------------- #
# hash-interned labels + pipelined two-stage dispatch (PR 4)
# --------------------------------------------------------------------------- #


def test_pipelined_vs_serial_dispatch_bit_identical_under_key_skew():
    """The two-stage pipeline (chunk k+1 routed while chunk k steps) is a
    pure dispatch-order change: under forced key skew with multi-round
    drains, pipelined and serial device dispatch produce bitwise-identical
    engine/intern states and identical router telemetry — and the pipelined
    run's dispatch performed zero host fetches and zero host dict ops."""
    stream = _skew_stream(60)
    cfg = _cfg()
    pipe = ShardedSummarizer(cfg, routing="device", n_shards=2,
                             router_chunk=64, lane_cap=2)
    ser = ShardedSummarizer(cfg, routing="device", n_shards=2,
                            router_chunk=64, lane_cap=2, pipeline=False)
    assert pipe.pipeline and not ser.pipeline
    for off in range(0, len(stream), 64):
        pipe.process(stream[off:off + 64])
        ser.process(stream[off:off + 64])
    sp, ss_ = pipe.stats(), ser.stats()
    assert sp["router_drain_rounds"] >= 2      # genuinely multi-round
    assert sp["router_syncs"] == 0 and sp["router_host_dict_ops"] == 0
    tele = [k for k in sp if k.startswith("router_")
            and k != "router_pipelined"]
    assert {k: sp[k] for k in tele} == {k: ss_[k] for k in tele}
    assert sp["router_pipelined"] and not ss_["router_pipelined"]
    for a, b in zip(pipe.host_states(), ser.host_states()):
        for name, al, bl in zip(a._fields, a, b):
            np.testing.assert_array_equal(
                np.asarray(al), np.asarray(bl), err_msg=name)
    for a, b in zip(pipe.host_interns(), ser.host_interns()):
        assert int(a.n_nodes) == int(b.n_nodes)
        np.testing.assert_array_equal(np.asarray(a.l2h), np.asarray(b.l2h))
    truth = ground_truth_edges(stream)
    assert pipe.live_edges() == truth
    assert pipe.materialize().decode_edges() == truth


def test_steady_state_dispatch_is_fetch_free_and_dict_free():
    """The acceptance contract of the pipelined path: a default-geometry
    device-routed run performs zero per-chunk device-to-host fetches
    (``router_syncs``) and zero per-chunk host dict operations
    (``router_host_dict_ops``) — interleaved sync points (``phi``) must
    not void either counter."""
    stream = _stream(seed=91)
    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64)
    assert ss.sync_free and ss.pipeline
    for off in range(0, len(stream), 64):
        ss.process(stream[off:off + 64])
        _ = ss.phi                      # sync point between chunks
    st = ss.stats()
    assert st["router_syncs"] == 0
    assert st["router_host_dict_ops"] == 0
    assert st["router_sync_free"] and st["router_pipelined"]
    assert ss.live_edges() == ground_truth_edges(stream)


def test_label_hash_collision_raises_loudly():
    """Two distinct labels landing on one 62-bit hash must never silently
    merge: the lazy reverse-map fold detects the collision and raises."""
    from repro.dist import labelhash

    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64)
    h = labelhash.hash_label("a")
    ss.process([("a", "b", True)])
    # forge a buffered chunk claiming label "evil-twin" has a's hash
    hi = np.array([(h >> 31)], np.int32)
    lo = np.array([h & labelhash.MASK31], np.int32)
    ss._label_buf.append((["evil-twin"], hi, lo))
    with pytest.raises(RuntimeError, match="hash collision"):
        ss.stats()


def test_pipelined_skew_drain_8_fake_devices_subprocess():
    """Satellite 8-device variant: the pipelined two-stage dispatch with
    multi-round drains on a REAL 8-device mesh (subprocess, fake host
    devices) stays bitwise-identical to serial dispatch and to host
    bucketing, with zero syncs and zero host dict ops."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.core.engine import EngineConfig, ShardedSummarizer
        from repro.dist.labelhash import hash_label

        assert len(jax.devices()) == 8
        cfg = EngineConfig(n_cap=128, m_cap=1024, d_cap=32, sn_cap=24,
                           c=8, batch=8, escape=0.3)
        leaves = ["x%03d" % i for i in range(90)]
        lo = min(hash_label(x) for x in leaves)
        hub = next(h for h in ("hub%d" % j for j in range(100000))
                   if hash_label(h) < lo)
        stream = [(hub, x, True) for x in leaves]
        kw = dict(n_shards=16, router_chunk=128, lane_cap=2)
        pipe = ShardedSummarizer(cfg, routing="device", **kw)
        ser = ShardedSummarizer(cfg, routing="device", pipeline=False, **kw)
        host = ShardedSummarizer(cfg, routing="host", n_shards=16,
                                 router_chunk=128)
        assert pipe.router_geometry.n_dev == 8
        assert pipe.sync_free and pipe.pipeline and not ser.pipeline
        for off in range(0, len(stream), 128):
            pipe.process(stream[off:off + 128])
            ser.process(stream[off:off + 128])
            host.process(stream[off:off + 128])
        st = pipe.stats()
        assert st["router_syncs"] == 0 and st["router_host_dict_ops"] == 0
        assert st["router_drain_rounds"] >= 2, st
        for other in (ser, host):
            assert pipe.shard_phis() == other.shard_phis()
            for a, b in zip(pipe.host_states(), other.host_states()):
                for name, al, bl in zip(a._fields, a, b):
                    np.testing.assert_array_equal(
                        np.asarray(al), np.asarray(bl), err_msg=name)
        truth = {(min(hub, x), max(hub, x)) for x in leaves}
        assert pipe.live_edges() == truth
        assert pipe.materialize().decode_edges() == truth
        print("8-device pipelined skew drain OK:",
              st["router_drain_rounds"], "rounds")
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# --------------------------------------------------------------------------- #
# vmapped shard replicas (predicated trial engine, PR 5)
# --------------------------------------------------------------------------- #


def test_replica_exec_vmap_vs_map_vs_host_bitwise_on_key_skew():
    """replica_exec is a pure lowering change: under forced key skew with
    multi-round drains, the vmapped replica layout, the lax.map layout,
    and host routing (through the vmapped bucketed step) produce
    leaf-bitwise identical engine AND intern states — the strongest
    statement that batching replicas changes no PRNG draw, no intern
    order, and no trial outcome."""
    stream = _skew_stream(60)
    cfg = _cfg()
    kw = dict(n_shards=2, router_chunk=64)
    vm = ShardedSummarizer(cfg, routing="device", lane_cap=2,
                           replica_exec="vmap", **kw)
    mp = ShardedSummarizer(cfg, routing="device", lane_cap=2,
                           replica_exec="map", **kw)
    host = ShardedSummarizer(cfg, routing="host", replica_exec="vmap", **kw)
    assert vm.replica_exec == "vmap" and mp.replica_exec == "map"
    for off in range(0, len(stream), 64):
        vm.process(stream[off:off + 64])
        mp.process(stream[off:off + 64])
        host.process(stream[off:off + 64])
    assert vm.stats()["router_drain_rounds"] >= 2   # genuinely multi-round
    assert vm.stats()["trial_passes"] > 0
    assert mp.stats()["trial_passes"] is None
    for other in (mp, host):
        assert vm.shard_phis() == other.shard_phis()
        for a, b in zip(vm.host_states(), other.host_states()):
            if other is mp:     # the map layout runs no speculative pass
                assert int(b.n_passes) == 0
                b = b._replace(n_passes=a.n_passes)
            for name, al, bl in zip(a._fields, a, b):
                np.testing.assert_array_equal(
                    np.asarray(al), np.asarray(bl), err_msg=name)
        for a, b in zip(vm.host_interns(), other.host_interns()):
            assert int(a.n_nodes) == int(b.n_nodes)
            np.testing.assert_array_equal(np.asarray(a.l2h),
                                          np.asarray(b.l2h))
    truth = ground_truth_edges(stream)
    assert vm.live_edges() == truth
    assert vm.materialize().decode_edges() == truth


def test_replica_exec_default_is_backend_aware_and_validated():
    """The resolved default must be a legal mode (vmap on accelerators,
    map on the XLA CPU backend — see repro/dist/router.py), and an unknown
    mode must fail fast."""
    import jax

    from repro.dist.router import REPLICA_EXEC_MODES, default_replica_exec

    assert default_replica_exec() in REPLICA_EXEC_MODES
    if jax.default_backend() == "cpu" and "REPRO_REPLICA_EXEC" not in \
            __import__("os").environ:
        assert default_replica_exec() == "map"
    ss = ShardedSummarizer(_cfg(), n_shards=2, router_chunk=64)
    assert ss.replica_exec == default_replica_exec()
    with pytest.raises(ValueError, match="replica_exec"):
        ShardedSummarizer(_cfg(), n_shards=2, replica_exec="pmap")


def test_importing_the_main_path_starts_no_backend():
    """The replica layout, the probe backend and the platform are chosen
    when a summarizer is built, so importing the package must not start a
    JAX backend (an entry point may still have to pick or refuse one)."""
    import os
    import subprocess
    import sys

    code = ("import repro.core.engine.api, repro.dist.router, "
            "repro.serve.query, repro.checkpoint.summary, "
            "repro.launch.stream\n"
            "from jax._src import xla_bridge\n"
            "print(xla_bridge.backends_are_initialized())")
    out = subprocess.run([sys.executable, "-c", code], env=dict(os.environ),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "False"


def test_label_buffer_compacts_on_long_zero_sync_runs():
    """A dispatch-only run must not buffer every label occurrence until
    the next sync: the buffer compacts to unique hashes every 64 chunks
    (numpy only — the dict-op and sync counters stay 0), and decoding
    after the eventual sync is unaffected."""
    edges = sbm_edges(120, 4, 0.4, 0.02, seed=101)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=102)
    assert len(stream) > 64 * 8             # many chunks, one process call
    ss = ShardedSummarizer(_cfg(n_cap=512, m_cap=4096), routing="device",
                           n_shards=2, router_chunk=8)
    ss.process(stream)
    # > 64 chunks ran; without compaction there would be 2 entries/chunk
    assert len(ss._label_buf) < 2 * len(stream) // 8, len(ss._label_buf)
    st = ss.stats()
    assert st["router_syncs"] == 0 and st["router_host_dict_ops"] == 0
    assert ss.live_edges() == ground_truth_edges(stream)
    assert ss.materialize().decode_edges() == ground_truth_edges(stream)


# --------------------------------------------------------------------------- #
# engine-round counter, in-program spans and named scopes
# --------------------------------------------------------------------------- #


def _host_round_schedule(stream, n_shards, chunk, batch):
    """The engine rounds host bucketing runs: per chunk,
    ``ceil(max shard load / batch)``, computed from the label hashes."""
    from repro.dist.labelhash import hash_label

    total = 0
    for off in range(0, len(stream), chunk):
        keys = [min(hash_label(u), hash_label(v)) % n_shards
                for (u, v, _) in stream[off:off + chunk]]
        load = np.bincount(keys, minlength=n_shards)
        total += -(-int(load.max()) // batch)
    return total


@pytest.mark.parametrize("routing", ["device", "host"])
@pytest.mark.parametrize("skewed", [False, True])
def test_engine_rounds_match_the_host_schedule(routing, skewed):
    """``stats()['engine_rounds']`` counts exactly the rounds the host
    schedule predicts, in both routing modes; under key skew (every change
    on one shard, multi-round drains) a chunk needs several rounds."""
    stream = _skew_stream(60) if skewed else _stream(seed=41)
    cfg = _cfg()
    kw = dict(lane_cap=2) if skewed and routing == "device" else {}
    ss = ShardedSummarizer(cfg, routing=routing, n_shards=2,
                           router_chunk=64, **kw)
    n_chunks = 0
    for off in range(0, len(stream), 64):
        ss.process(stream[off:off + 64])
        n_chunks += 1
    st = ss.stats()
    want = _host_round_schedule(stream, 2, 64, cfg.batch)
    assert st["engine_rounds"] == want
    assert st["router_overflows"] == 0
    if skewed:
        assert want > n_chunks
    assert ss.live_edges() == ground_truth_edges(stream)


def test_engine_rounds_under_skew_on_4_virtual_devices_subprocess():
    """On a real 4-device mesh under forced key skew the pmax-agreed round
    count exceeds the chunk count and equals the host schedule, in both
    routing modes."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.core.engine import EngineConfig, ShardedSummarizer
        from repro.dist.labelhash import hash_label

        assert len(jax.devices()) == 4
        cfg = EngineConfig(n_cap=128, m_cap=1024, d_cap=32, sn_cap=24,
                           c=4, batch=8, escape=0.3)
        leaves = ["x%03d" % i for i in range(70)]
        lo = min(hash_label(x) for x in leaves)
        hub = next(h for h in ("hub%d" % j for j in range(100000))
                   if hash_label(h) < lo)
        stream = [(hub, x, True) for x in leaves]
        stream += [(hub, x, False) for x in leaves[::3]]
        want = 0
        for off in range(0, len(stream), 64):
            keys = [min(hash_label(u), hash_label(v)) % 4
                    for (u, v, _) in stream[off:off + 64]]
            want += -(-int(np.bincount(keys, minlength=4).max()) // 8)
        n_chunks = -(-len(stream) // 64)
        dev = ShardedSummarizer(cfg, routing="device", n_shards=4,
                                router_chunk=64, lane_cap=4)
        host = ShardedSummarizer(cfg, routing="host", n_shards=4,
                                 router_chunk=64)
        assert dev.router_geometry.n_dev == 4
        for off in range(0, len(stream), 64):
            dev.process(stream[off:off + 64])
            host.process(stream[off:off + 64])
        sd, sh = dev.stats(), host.stats()
        assert sd["router_drain_rounds"] >= 2, sd
        assert sd["engine_rounds"] == sh["engine_rounds"] == want, (sd, sh)
        assert want > n_chunks, (want, n_chunks)
        print("4-device engine rounds OK:", want, "rounds,", n_chunks,
              "chunks")
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_dispatch_records_spans_without_device_fetches(monkeypatch):
    """With the span recorder on, steady-state ``process()`` still fetches
    nothing from the device (reading any array's value raises inside the
    dispatch loop, as does a transfer under the guard on backends that
    enforce it) and records its spans: one ``summarizer.process`` root
    per call, its children tagged with the chunk's journal sequence
    number."""
    import jax
    from jax._src import array as jax_array

    stream = _stream(seed=91)
    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64)
    assert ss.sync_free and ss.pipeline and ss.obs.enabled
    ss.process(stream[:64])                 # compiles outside the guard
    ss.process(stream[64:128])
    calls = 2

    def fetch(*_):
        raise AssertionError("device fetch inside process()")

    with monkeypatch.context() as m:
        m.setattr(jax_array.ArrayImpl, "_value", property(fetch))
        m.setattr(jax, "device_get", fetch)
        with jax.transfer_guard_device_to_host("disallow"):
            for off in range(128, len(stream), 64):
                ss.process(stream[off:off + 64])
                calls += 1
    spans = ss.obs.spans("summarizer.")
    roots = [s for s in spans if s.parent_id == 0]
    assert [s.name for s in roots] == ["summarizer.process"] * calls
    assert [s.request_id for s in roots] == list(range(calls))
    by_id = {s.span_id: s for s in spans}
    kids = [s for s in spans if s.parent_id]
    assert {s.name for s in kids} == {
        "summarizer.journal", "summarizer.pack", "summarizer.route",
        "summarizer.engine"}
    for s in kids:
        parent = by_id[s.parent_id]
        assert parent.name == "summarizer.process"
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
        # the pipelined engine stage runs the previous chunk
        want = parent.request_id - (s.name == "summarizer.engine")
        assert s.request_id == want, s
    st = ss.stats()
    assert st["router_syncs"] == 0 and st["router_host_dict_ops"] == 0
    assert [s.name for s in ss.obs.spans("summarizer.sync")] == [
        "summarizer.sync"]
    assert ss.live_edges() == ground_truth_edges(stream)


def test_stage_programs_counts_each_jitted_stage():
    stream = _stream(seed=41)
    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64)
    ss.run(stream)
    progs = ss.stats()["stage_programs"]
    assert set(progs) == {"route", "engine", "query"}
    assert progs["route"] >= 1 and progs["engine"] >= 1
    before = progs["query"]
    view = ss.query()
    view.degree_batch([stream[0][0]])
    assert ss.stats()["stage_programs"]["query"] >= max(before, 1)
    host = ShardedSummarizer(_cfg(), routing="host", n_shards=2,
                             router_chunk=64).run(stream)
    hp = host.stats()["stage_programs"]
    assert hp["route"] == 0 and hp["engine"] >= 1


def test_named_scopes_keep_module_names():
    """The stages carry named scopes in their op metadata, and the jitted
    programs keep the module names the benchmark's trace reduction keys
    on (``jit_local`` for the route and engine stages)."""
    from repro.serve.query import make_sharded_query_kernels

    ss = ShardedSummarizer(_cfg(), routing="device", n_shards=2,
                           router_chunk=64)
    packed = ss._pack_chunk(_stream(seed=41)[:64], pad_to=64)
    route = ss._route.lower(*packed).as_text(debug_info=True)
    *buckets, counts, _, rounds = ss._route(*packed)
    engine = ss._engine.lower(ss.state, ss.intern, ss._drain_rounds,
                              *buckets, counts, rounds).as_text(
                                  debug_info=True)
    assert "module @jit_local" in route and "module @jit_local" in engine
    for scope in ("route/keys", "route/drain"):
        assert scope in route, scope
    # op locations hold each scope at the head of a name path
    for scope in ("engine/intern", "engine/round", '"apply/',
                  '"trial_group/', '"plan/', '"eval_phi/', '"commit/'):
        assert scope in engine, scope
    k = make_sharded_query_kernels(ss.cfg, ss.mesh, ss.trial_backend)
    q = np.zeros(8, np.int32)
    for fn, name, n_q in ((k.neighbors, "nbrs_local", 2),
                          (k.degree, "deg_local", 2),
                          (k.has_edge, "he_local", 4)):
        text = fn.lower(ss.state, ss.intern, *(q,) * n_q).as_text(
            debug_info=True)
        assert f"module @jit_{name}" in text
        assert "query/resolve" in text and "query/scan" in text
