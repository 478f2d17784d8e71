"""Distributed semantics via subprocesses with 8 fake host devices.

Tests spawn a fresh interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test
process must keep 1 device — DESIGN.md), and assert sharded execution
matches single-device semantics.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_py(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_lm_train_step_matches_single_device():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import REGISTRY
        from repro.dist import sharding as shd
        from repro.models import transformer as tfm
        from repro.optim import adamw
        from repro.train.step import make_train_step

        assert len(jax.devices()) == 8
        cfg = REGISTRY["internlm2-20b"].make_smoke_config()
        params = tfm.init_transformer(cfg, jax.random.key(0))
        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        opt = adamw.init(params, opt_cfg)
        step = make_train_step(lambda p, t, l: tfm.loss_fn(p, t, l, cfg), opt_cfg)
        toks = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab)

        # single device
        p1, o1, m1 = jax.jit(step)(params, opt, toks, toks)

        # sharded 2x4 mesh
        # GSPMD (Auto) axes: jax.make_mesh defaults to Explicit axes, under
        # which the embedding gather's output spec repeats 'data'
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        p_sh = shd.tree_shardings(params, shd.LM_RULES, mesh)
        o_sh = adamw.AdamWState(step=NamedSharding(mesh, P()),
                                m=shd.tree_shardings(params, shd.LM_RULES, mesh),
                                v=shd.tree_shardings(params, shd.LM_RULES, mesh))
        b_sh = NamedSharding(mesh, P("data", None))
        jt = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None))
        params_s = jax.device_put(params, p_sh)
        opt_s = jax.device_put(opt, o_sh)
        p2, o2, m2 = jt(params_s, opt_s, jax.device_put(toks, b_sh),
                        jax.device_put(toks, b_sh))
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)
        print("sharded == single-device: OK", float(m1["loss"]))
    """))


def test_distributed_mosso_phi_equals_sum_of_shards():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.engine import BatchedSummarizer, EngineConfig
        from repro.core.engine.state import new_state
        from repro.core.engine.trial import step_fn
        from repro.graph.streams import sbm_edges, edges_to_insertion_stream

        n_dev = len(jax.devices()); assert n_dev == 8
        cfg = EngineConfig(n_cap=256, m_cap=2048, d_cap=32, sn_cap=24,
                           c=8, batch=8, escape=0.3)
        mesh = jax.make_mesh((n_dev,), ("d",))

        # edge-partitioned sharded summarization: route each change to the
        # shard owning hash(min endpoint); phi_total = psum of local phis.
        edges = sbm_edges(64, 4, 0.5, 0.05, seed=3)
        stream = edges_to_insertion_stream(edges, seed=4)
        shards = [[] for _ in range(n_dev)]
        for (u, v, ins) in stream:
            shards[min(u, v) % n_dev].append((u, v, ins))

        def local(st, u, v, ins):
            st0 = jax.tree.map(lambda x: x[0], st)
            st1 = step_fn(st0, u[0], v[0], ins[0], cfg)
            return (jax.tree.map(lambda x: x[None], st1),
                    jax.lax.psum(st1.phi, "d")[None])

        st1 = new_state(cfg)
        stacked = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (n_dev,) + l.shape), st1)
        dist = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("d"), st1), P("d"), P("d"), P("d")),
            out_specs=(jax.tree.map(lambda _: P("d"), st1), P("d")),
            check_vma=False))

        b = cfg.batch
        n_steps = max(len(s) for s in shards)
        n_steps = (n_steps + b - 1) // b
        state = stacked
        phi = None
        for i in range(n_steps):
            u = np.full((n_dev, b), -1, np.int32)
            v = np.full((n_dev, b), -1, np.int32)
            ins = np.zeros((n_dev, b), bool)
            for d in range(n_dev):
                chunk = shards[d][i*b:(i+1)*b]
                for j, (a, c, s_) in enumerate(chunk):
                    u[d, j], v[d, j], ins[d, j] = a, c, s_
            state, phi = dist(state, jnp.asarray(u), jnp.asarray(v),
                              jnp.asarray(ins))
        local_phis = np.asarray(state.phi if state.phi.ndim else None)
        # psum result equals the sum of shard phis
        total = int(np.asarray(phi)[0])
        assert total == sum(int(x) for x in np.asarray(state.phi)), \
            (total, np.asarray(state.phi))
        # sharded-summarization quality: phi_total <= |E| (each shard
        # compresses its partition losslessly)
        assert 0 < total <= len(edges)
        print("distributed mosso OK: phi_total", total, "|E|", len(edges))
    """))


def test_sharded_summarizer_lossless_across_8_devices():
    print(run_py("""
        import jax, numpy as np
        from repro.core.engine import EngineConfig, ShardedSummarizer
        from repro.graph.streams import edges_to_fully_dynamic_stream, sbm_edges

        assert len(jax.devices()) == 8
        cfg = EngineConfig(n_cap=128, m_cap=1024, d_cap=32, sn_cap=24,
                           c=8, batch=8, escape=0.3)
        edges = sbm_edges(72, 6, 0.5, 0.04, seed=7)
        stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=8)
        ss = ShardedSummarizer(cfg)       # one partition per device
        assert ss.n_shards == 8
        ss.run(stream)

        truth = set()
        for (u, v, ins) in stream:
            e = (min(u, v), max(u, v))
            truth.add(e) if ins else truth.discard(e)

        out = ss.materialize()
        assert len(out.shards) == 8
        assert out.decode_edges() == truth            # lossless union decode
        assert ss.live_edges() == truth
        assert out.phi == ss.phi == sum(ss.shard_phis()) == ss.phi_recomputed()
        assert ss.num_edges == len(truth)
        assert 0 < ss.phi <= len(truth)               # per-shard compression
        loads = [int(x) for x in np.asarray(ss.state.num_edges)]
        assert sum(1 for l in loads if l > 0) >= 6, loads
        print("sharded summarizer OK: phi", ss.phi, "|E|", len(truth),
              "shard loads", loads)
    """))


def test_device_router_matches_host_routing_across_8_devices():
    """Host-vs-device routing differential on a real 8-device all_to_all,
    with n_shards=16 so each device carries two shard replicas (the router's
    lane layout is [n_dev, n_loc, lane_cap])."""
    print(run_py("""
        import jax, numpy as np
        from repro.core.engine import EngineConfig, ShardedSummarizer
        from repro.graph.streams import edges_to_fully_dynamic_stream, sbm_edges

        assert len(jax.devices()) == 8
        cfg = EngineConfig(n_cap=128, m_cap=1024, d_cap=32, sn_cap=24,
                           c=8, batch=8, escape=0.3)
        edges = sbm_edges(72, 6, 0.5, 0.04, seed=7)
        stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=8)
        kw = dict(n_shards=16, router_chunk=128)
        dev = ShardedSummarizer(cfg, routing="device", **kw)
        host = ShardedSummarizer(cfg, routing="host", **kw)
        live = set()
        for off in range(0, len(stream), 128):
            chunk = stream[off:off + 128]
            dev.process(chunk); host.process(chunk)
            for (u, v, ins) in chunk:
                e = (min(u, v), max(u, v))
                live.add(e) if ins else live.discard(e)
            assert dev.router_overflows == 0
            assert dev.shard_phis() == host.shard_phis(), off
            assert dev.materialize().decode_edges() == live, off
            assert host.materialize().decode_edges() == live, off
        assert dev.live_edges() == live
        assert 0 < dev.phi <= len(live)
        print("8-device router differential OK: phi", dev.phi,
              "|E|", len(live))
    """))


def test_device_router_drains_skew_across_8_devices():
    """Key-skewed stream (every change routed to one shard: the hub's
    62-bit hash undercuts every leaf's, so it is always the canonical-pair
    key) at a tiny lane_cap: the on-device drain loop runs many real
    all_to_all rounds and still matches host routing bit for bit — no host
    fallback, no per-chunk watermark sync."""
    print(run_py("""
        import jax, numpy as np
        from repro.core.engine import EngineConfig, ShardedSummarizer
        from repro.dist.labelhash import hash_label

        assert len(jax.devices()) == 8
        cfg = EngineConfig(n_cap=128, m_cap=1024, d_cap=32, sn_cap=24,
                           c=8, batch=8, escape=0.3)
        leaves = ["x%03d" % i for i in range(1, 100)]
        lo = min(hash_label(x) for x in leaves)
        hub = next(h for h in ("hub%d" % j for j in range(100000))
                   if hash_label(h) < lo)
        stream = [(hub, x, True) for x in leaves]
        kw = dict(n_shards=16, router_chunk=128)
        dev = ShardedSummarizer(cfg, routing="device", lane_cap=2, **kw)
        host = ShardedSummarizer(cfg, routing="host", **kw)
        assert dev.router_geometry.n_dev == 8
        assert dev.sync_free and dev.router_geometry.drain_guaranteed
        for off in range(0, len(stream), 128):
            dev.process(stream[off:off + 128])
            host.process(stream[off:off + 128])
        st = dev.stats()
        assert dev.router_overflows == 0 and st["router_syncs"] == 0
        assert st["router_drain_rounds"] >= 2, st
        assert dev.shard_phis() == host.shard_phis()
        for d, h in zip(dev.host_states(), host.host_states()):
            for name, dl, hl in zip(d._fields, d, h):
                np.testing.assert_array_equal(np.asarray(dl), np.asarray(hl),
                                              err_msg=name)
        truth = {(min(hub, x), max(hub, x)) for x in leaves}
        assert dev.live_edges() == truth
        assert dev.materialize().decode_edges() == truth
        print("8-device skew drain OK:", st["router_drain_rounds"], "rounds")
    """))


def test_vmapped_replicas_bitwise_across_8_devices():
    """PR-5 satellite: with 8 fake devices and 16 shards (two replicas
    stacked per device), the vmapped replica layout runs its batched
    engine program on a real mesh and stays leaf-bitwise identical to the
    lax.map layout and to host routing — including the intern tables."""
    print(run_py("""
        import jax, numpy as np
        from repro.core.engine import EngineConfig, ShardedSummarizer
        from repro.graph.streams import edges_to_fully_dynamic_stream, sbm_edges

        assert len(jax.devices()) == 8
        cfg = EngineConfig(n_cap=128, m_cap=1024, d_cap=32, sn_cap=24,
                           c=8, batch=8, escape=0.3)
        edges = sbm_edges(72, 6, 0.5, 0.04, seed=7)
        stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=8)
        kw = dict(n_shards=16, router_chunk=128)
        vm = ShardedSummarizer(cfg, routing="device", replica_exec="vmap", **kw)
        mp = ShardedSummarizer(cfg, routing="device", replica_exec="map", **kw)
        host = ShardedSummarizer(cfg, routing="host", replica_exec="vmap", **kw)
        assert vm.router_geometry.n_dev == 8
        assert vm.router_geometry.n_loc == 2      # vmap axis is 2 replicas
        for off in range(0, len(stream), 128):
            vm.process(stream[off:off + 128])
            mp.process(stream[off:off + 128])
            host.process(stream[off:off + 128])
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
        truth = set()
        for (u, v, ins) in stream:
            e = (min(u, v), max(u, v))
            truth.add(e) if ins else truth.discard(e)
        assert vm.live_edges() == truth
        assert vm.materialize().decode_edges() == truth
        st = vm.stats()
        assert st["router_syncs"] == 0 and st["router_host_dict_ops"] == 0
        print("8-device vmapped replicas OK: phi", vm.phi)
    """))


def test_data_parallel_wrapper_and_cache():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist import sharding as shd

        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.arange(64.0).reshape(8, 8)

        g = shd.data_parallel(lambda a: a * 2.0 + 1.0, mesh)
        np.testing.assert_allclose(np.asarray(g(x)), np.asarray(x) * 2 + 1)
        np.testing.assert_allclose(np.asarray(g(x)), np.asarray(x) * 2 + 1)

        # distinct pytree STRUCTURES with identical leaves must not collide
        # in the compile cache (keyed on treedef + avals)
        h = shd.data_parallel(
            lambda t: t[0] + t[1] if isinstance(t, tuple) else t["a"] - t["b"],
            mesh)
        got_t = np.asarray(h((x, x)))
        got_d = np.asarray(h({"a": x, "b": x}))
        np.testing.assert_allclose(got_t, 2 * np.asarray(x))
        np.testing.assert_allclose(got_d, np.zeros_like(np.asarray(x)))

        # a leaf with FEWER dims than its rule takes the rule's TRAILING
        # entries: rank-1 'embed' gets the 'embed' (fsdp->data) entry, never
        # the leading 'vocab' one
        from jax.sharding import PartitionSpec as P
        spec = shd.spec_for_leaf("embed", (64,), mesh, shd.LM_RULES)
        assert spec == P("data"), spec
        assert shd.spec_for_leaf("embed", (), mesh, shd.LM_RULES) == P()
        print("data_parallel OK")
    """))


def test_compressed_psum_error_bounded():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import compressed_psum, int8_quantize, int8_dequantize

        x = jnp.array(np.random.default_rng(0).normal(size=(8, 64)), jnp.float32)
        q, s = int8_quantize(x[0])
        err = float(jnp.max(jnp.abs(int8_dequantize(q, s) - x[0])))
        assert err <= float(s) * 0.51 + 1e-6

        mesh = jax.make_mesh((8,), ("d",))
        f = jax.shard_map(lambda a: compressed_psum(a, "d"), mesh=mesh,
                          in_specs=P("d"), out_specs=P(), check_vma=False)
        got = f(x)
        want = jnp.sum(x, axis=0)
        rel = float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-9))
        assert rel < 0.02, rel
        print("compressed psum OK, rel err", rel)
    """))
