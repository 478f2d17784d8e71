#!/usr/bin/env python3
"""Bring-up smoke: the sharded summarizer on a TPU, checked against the host.

    python chip_smoke.py              # one chip, full mosso_stream capacities
    python chip_smoke.py --chips 4    # four chips against a one-chip reference

The one-chip phase builds a ``ShardedSummarizer`` at
``configs/mosso_stream.full_config()`` capacities with several shards stacked
on the chip, lets the code choose every path as it does on a TPU (device
routing, pipelined dispatch, the default replica layout, the XLA probe
backend), feeds it a seeded fully dynamic Barabasi-Albert stream and checks
it against plain host replays of that stream: phi against its refold, the
lossless decode, sampled point queries, the buffer-donation contract of
query views, and the bitwise crash-recovery bar (a checkpoint taken
mid-stream, a kill one chunk later, recovery into a fresh summarizer).

The four-chip phase streams the same changes through ``n_shards=8`` on four
chips and on one chip, and requires leaf-bitwise equal engine and intern
states plus a lossless decode; its per-shard capacities are cut so that the
one-chip reference holds all eight shards.

Every check that fails raises, so the script exits nonzero; the last line
of a passing run is one JSON object naming the device.  Without a TPU the
script stops before any work, also nonzero: there is no CPU fallback.  The
phase functions take a config, so the tests run them on the CPU at
``smoke_config()`` size.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
_COMPILE_S = [0.0]         # trace + lowering + backend compile seconds


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(name: str, ok: bool, detail: str = "") -> None:
    if not ok:
        raise SmokeFailure(f"check {name} FAILED {detail}".rstrip())
    log(f"check {name}: ok")


def _count_compile_time() -> None:
    import jax
    if getattr(_count_compile_time, "on", False):
        return

    def listen(event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            _COMPILE_S[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    _count_compile_time.on = True


# --------------------------------------------------------------------------- #
# host replays (the plain reference)
# --------------------------------------------------------------------------- #


def make_chunks(n_chunks: int, chunk: int, seed: int):
    """``n_chunks`` dispatch slices of one seeded fully dynamic BA stream."""
    from repro.launch.stream import make_stream
    # BA with m = 4 yields ~4.4 fully dynamic changes per node
    stream = make_stream("ba", n_chunks * chunk // 4 + 64, 4, 0.7, True,
                         seed)
    need = n_chunks * chunk
    if len(stream) < need:
        raise SmokeFailure(f"stream has {len(stream)} < {need} changes")
    return [stream[k * chunk:(k + 1) * chunk] for k in range(n_chunks)]


def replay(chunks) -> set:
    """Live edge set after applying ``chunks`` in order (canonical pairs)."""
    live = set()
    for ch in chunks:
        for u, v, ins in ch:
            e = (u, v) if u <= v else (v, u)
            if ins:
                live.add(e)
            else:
                live.discard(e)
    return live


def adjacency(live: set) -> dict:
    adj: dict = {}
    for u, v in live:
        adj.setdefault(u, set()).add(v)
        adj.setdefault(v, set()).add(u)
    return adj


def check_queries(tag: str, view, chunks, n_queries: int, seed: int) -> None:
    """Sampled ``neighbors``/``degree``/``has_edge`` answers of a query
    view against the replay of ``chunks`` (the view's epoch)."""
    live = replay(chunks)
    adj = adjacency(live)
    seen = sorted({x for ch in chunks for (u, v, _) in ch for x in (u, v)})
    rng = random.Random(seed)
    labels = rng.sample(seen, min(n_queries, len(seen)))
    got = []
    for i in range(0, len(labels), 64):     # bounded [shards, 64, n_cap] masks
        got += view.neighbors_batch(labels[i:i + 64])
    want = [adj.get(x, set()) for x in labels]
    bad = [x for x, g, w in zip(labels, got, want) if g != w]
    check(f"{tag}.neighbors[{len(labels)}]", not bad, f"labels {bad[:5]}")
    deg = view.degree_batch(labels)
    bad = [x for x, d, w in zip(labels, deg, want) if d != len(w)]
    check(f"{tag}.degree[{len(labels)}]", not bad, f"labels {bad[:5]}")
    edges = sorted(live)
    pairs = rng.sample(edges, min(n_queries // 2, len(edges)))
    pairs += [tuple(rng.sample(seen, 2)) for _ in range(n_queries // 2)]
    has = view.has_edge_batch(pairs)
    bad = [p for p, h in zip(pairs, has)
           if h != ((min(p), max(p)) in live)]
    check(f"{tag}.has_edge[{len(pairs)}]", not bad, f"pairs {bad[:5]}")


def check_decode(tag: str, summ, chunks) -> None:
    out = summ.materialize().validate()
    check(f"{tag}.lossless_decode", out.decode_edges() == replay(chunks))
    phi = summ.phi
    check(f"{tag}.phi_recomputed", phi == summ.phi_recomputed() == out.phi,
          f"phi={phi}")


def check_leaves_equal(tag: str, a, b) -> None:
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    same = len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))
    check(f"{tag}.leaf_bitwise[{len(la)} leaves]", same)


def _tree_bytes(tree) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _memory() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return ("device memory: " + " ".join(
        f"{k}={stats[k]}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_limit") if k in stats)
            if stats else "device memory: not reported")


def _crash_image(src: str, dst: str) -> None:
    """The checkpoint directory as a kill at this boundary leaves it: the
    checkpoint payloads (hard links) plus a copy of the journal so far."""
    def place(s, d):
        (shutil.copy2 if os.path.basename(s) == "journal.bin"
         else os.link)(s, d)
    shutil.copytree(src, dst, copy_function=place)


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #


def single_chip_phase(cfg, *, n_shards: int, n_chunks: int = 4,
                      chunk: int = 1024, n_queries: int = 256,
                      seed: int = SEED, workdir: str) -> dict:
    """Stream, query, checkpoint, kill and recover on the default mesh.

    Run U streams every chunk and saves after chunk 2.  Two query views
    taken at that save are held across the next ``process()`` calls: the
    plain one must raise where the engine step donates its buffers (and
    keep answering where it does not), the ``copy=True`` one must answer
    either way.  The directory as a kill after chunk 3 leaves it is kept
    aside; run R recovers from it into a fresh summarizer (restore +
    journal replay), finishes the stream, and must land leaf-bitwise on U.
    """
    import jax

    from repro.core.engine import ShardedSummarizer
    from repro.dist.router import default_replica_exec

    _count_compile_time()
    if n_chunks < 4:
        raise ValueError("the recovery schedule needs n_chunks >= 4")
    chunks = make_chunks(n_chunks, chunk, seed)
    n_changes = sum(map(len, chunks))
    ckpt, crashed = (os.path.join(workdir, d) for d in ("ckpt", "crashed"))
    save_at, kill_at = 2, 3

    c0 = _COMPILE_S[0]
    t0 = time.perf_counter()
    u = ShardedSummarizer(cfg, n_shards=n_shards, router_chunk=chunk,
                          checkpoint_dir=ckpt)
    jax.block_until_ready((u.state, u.intern))
    platform = u.mesh.devices.flat[0].platform
    log(f"layout: n_shards={u.n_shards} devices={u.mesh.devices.size} "
        f"({platform}) routing={u.routing} pipeline={u.pipeline} "
        f"replica_exec={u.replica_exec} trial_backend={u.trial_backend} "
        f"router_chunk={u.router_chunk} lane_cap={u.lane_cap}")
    est_b = _tree_bytes(u.state) // u.n_shards
    ist_b = _tree_bytes(u.intern) // u.n_shards
    log(f"state bytes per shard: engine={est_b} intern={ist_b} "
        f"(all shards {(est_b + ist_b) * u.n_shards})")
    log(f"build {time.perf_counter() - t0:.3f}s; {_memory()}")
    check("paths_as_on_chip",
          u.routing == "device" and u.pipeline and u.sync_free
          and u.trial_backend == "xla"
          and u.replica_exec == default_replica_exec())

    # ---- run U: the uninterrupted stream ------------------------------- #
    def stream(ks) -> float:
        """Wall seconds to apply chunks ``ks`` on the device, ending at a
        drained pipeline (dispatch is asynchronous until then)."""
        t = time.perf_counter()
        for k in ks:
            u.process(chunks[k])
            if k + 1 == kill_at:
                _crash_image(ckpt, crashed)
        u.flush()
        jax.block_until_ready((u.state, u.intern))
        return time.perf_counter() - t

    first_s = stream(range(save_at))
    compile_s = _COMPILE_S[0] - c0
    log(f"chunks 1-{save_at}: {first_s:.3f}s (compile {compile_s:.3f}s "
        f"inside); {_memory()}")
    t = time.perf_counter()
    u.save()
    save_s = time.perf_counter() - t
    log(f"save after chunk {save_at}: {save_s:.3f}s")
    held, kept = u.query(), u.query(copy=True)
    check("U.view_epoch", held.epoch == kept.epoch == save_at)
    steady_s = stream(range(save_at, n_chunks))
    steady_n = sum(len(chunks[k]) for k in range(save_at, n_chunks))
    stats = u.stats()
    log(f"chunks {save_at + 1}-{n_chunks}: {steady_n} changes in "
        f"{steady_s:.3f}s; trials={stats['trials']} "
        f"accepted={stats['accepted']} skipped={stats['skipped']} "
        f"drain_rounds={stats['router_drain_rounds']}; {_memory()}")
    at_view = chunks[:save_at]
    if platform != "cpu":             # the engine step donates its state
        try:
            held.degree_batch([chunks[0][0][0]])
            trapped = False
        except RuntimeError as e:     # jax: "Array has been deleted ..."
            trapped = "deleted" in str(e)
        check("U.held_view_raises_after_donation", trapped)
    else:
        check_queries("U.held_view(no donation)", held, at_view,
                      n_queries // 4, seed + 1)
    check_queries("U.copy_view", kept, at_view, n_queries // 4, seed + 1)
    del held, kept
    check("U.sync_free_dispatch", stats["router_syncs"] == 0
          and stats["router_host_dict_ops"] == 0)
    check_decode("U", u, chunks)
    check_queries("U.query", u.query(), chunks, n_queries, seed)
    want = jax.device_get((u.state, u.intern))
    del u

    # ---- run R: recover the kill-after-chunk-3 image, finish ----------- #
    t = time.perf_counter()
    r = ShardedSummarizer(cfg, n_shards=n_shards, router_chunk=chunk,
                          checkpoint_dir=crashed)
    info = r.recover()
    recover_s = time.perf_counter() - t
    log(f"recover: restored epoch {info['epoch']}, replayed "
        f"{info['replayed_chunks']} chunk(s), cursor {info['cursor']} "
        f"in {recover_s:.3f}s; {_memory()}")
    check("R.recover_point", info["epoch"] == save_at
          and info["replayed_chunks"] == kill_at - save_at
          and info["cursor"] == kill_at * chunk)
    r.process([c for ch in chunks[kill_at:] for c in ch])
    r.flush()
    check("R.cursor_at_end", r.stream_cursor == n_changes)
    check_leaves_equal("R_vs_U", jax.device_get((r.state, r.intern)), want)
    check_decode("R", r, chunks)
    del r

    return dict(n_shards=n_shards, n_changes=n_changes,
                state_bytes_per_shard=est_b + ist_b,
                compile_s=compile_s, first_chunks_s=first_s,
                steady_chunks_s=steady_s, save_s=save_s,
                recover_s=recover_s,
                smoke_changes_per_s=steady_n / steady_s,
                peak_bytes_in_use=_peak_bytes())


def multi_chip_phase(cfg, *, n_shards: int = 8, n_chunks: int = 2,
                     chunk: int = 1024, seed: int = SEED) -> dict:
    """The same stream on every local device and on one device: the
    stacked states must agree leaf for leaf, and both decode losslessly."""
    import jax

    from repro.core.engine import ShardedSummarizer
    from repro.launch.mesh import make_engine_mesh

    _count_compile_time()
    chunks = make_chunks(n_chunks, chunk, seed)
    out = {}
    states = {}
    for tag, mesh in (("mesh", None), ("one_device", make_engine_mesh(1))):
        t0, c0 = time.perf_counter(), _COMPILE_S[0]
        s = ShardedSummarizer(cfg, n_shards=n_shards, router_chunk=chunk,
                              mesh=mesh)
        for ch in chunks:
            s.process(ch)
        s.flush()
        jax.block_until_ready((s.state, s.intern))
        wall = time.perf_counter() - t0
        comp = _COMPILE_S[0] - c0
        st = s.stats()
        log(f"{tag}: n_shards={s.n_shards} devices={s.mesh.devices.size} "
            f"replica_exec={s.replica_exec} lane_cap={s.lane_cap} "
            f"drain_rounds={st['router_drain_rounds']} "
            f"{sum(map(len, chunks))} changes in {wall:.3f}s "
            f"(compile {comp:.3f}s inside)")
        check_decode(tag, s, chunks)
        states[tag] = jax.device_get((s.state, s.intern))
        out[f"{tag}_devices"] = int(s.mesh.devices.size)
        out[f"{tag}_s"], out[f"{tag}_compile_s"] = wall, comp
        del s
    check("mesh.spans_devices", out["mesh_devices"] > 1,
          f"{out['mesh_devices']} device(s)")
    check_leaves_equal("mesh_vs_one_device", states["mesh"],
                       states["one_device"])
    out["peak_bytes_in_use"] = _peak_bytes()
    return out


# --------------------------------------------------------------------------- #
# entry point
# --------------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip vs one-chip phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs.mosso_stream import full_config
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")
    cfg = full_config()
    t0 = time.perf_counter()
    if args.chips == 1:
        log(f"config: full_config() n_cap={cfg.n_cap} m_cap={cfg.m_cap} "
            f"d_cap={cfg.d_cap} sn_cap={cfg.sn_cap} c={cfg.c} "
            f"batch={cfg.batch}")
        workdir = tempfile.mkdtemp(prefix="chip_smoke_")
        try:
            res = single_chip_phase(cfg, n_shards=4, workdir=workdir)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    else:
        cut = dataclasses.replace(cfg, n_cap=cfg.n_cap // 32,
                                  m_cap=cfg.m_cap // 32)
        log(f"config: full_config() with capacities cut 32x for the "
            f"one-chip reference: n_cap={cut.n_cap} m_cap={cut.m_cap} "
            f"(widths kept: d_cap={cut.d_cap} sn_cap={cut.sn_cap} "
            f"c={cut.c} batch={cut.batch})")
        res = multi_chip_phase(cut, n_shards=8)
    for key, val in res.items():
        log(f"result {key}: {val}")
    if "smoke_changes_per_s" in res:
        log(f"smoke throughput (not a benchmark): "
            f"{res['smoke_changes_per_s']:.3f} changes/s, "
            f"{1e3 / res['smoke_changes_per_s']:.3f} ms/change")
    log(f"total {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
