"""End-to-end driver: sharded, device-routed summarization of a large
stream with crash-consistent checkpointing (the paper's workload,
production shape).

Feeds a fully dynamic stream through ``ShardedSummarizer`` on the default
``routing="device"`` path — the two-stage pipelined router that hashes
labels on the host (no per-change dict work), routes and interns on
device, and overlaps chunk k+1's routing with chunk k's engine rounds —
then reports the any-time compression ratio, certifies the sync-free
dispatch telemetry, and exercises the crash-consistency layer end to end:
the run is killed mid-stream at a chunk boundary, a FRESH summarizer
recovers from the checkpoint directory (last epoch checkpoint + journal
tail replay, ``recover()``), its query answers are asserted identical to
the pre-kill view, and after continuing it must land leaf-bitwise on the
uninterrupted run's state.

This example is CI-smoked (`.github/workflows/ci.yml`), so it cannot
drift from the real API.

Run:  PYTHONPATH=src python examples/summarize_stream.py [n_nodes] \
          [--proposal {minhash,magsdm}] [--objective {exact,weighted}]
"""
import argparse
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import numpy as np

from repro.core.engine import EngineConfig, ShardedSummarizer
from repro.core.engine.state import OBJECTIVES, PROPOSALS
from repro.dist.router import default_replica_exec
from repro.ft.inject import SimulatedCrash, drive
from repro.graph.streams import (barabasi_albert_edges,
                                 edges_to_fully_dynamic_stream)

# policy defaults come FROM EngineConfig so this example cannot drift from
# the engine (same contract as repro/launch/stream.py)
_dflt = EngineConfig()
ap = argparse.ArgumentParser()
ap.add_argument("n_nodes", type=int, nargs="?", default=2000)
ap.add_argument("--proposal", choices=list(PROPOSALS), default=_dflt.proposal)
ap.add_argument("--objective", choices=list(OBJECTIVES),
                default=_dflt.objective)
ap.add_argument("--weight-levels", type=int, default=_dflt.weight_levels)
args = ap.parse_args()

n_nodes = args.n_nodes
edges = barabasi_albert_edges(n_nodes, 4, seed=0)
stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.1, seed=1)
print(f"stream: {len(stream)} changes over {n_nodes} nodes")

# per-shard caps budget the vertex-cut replication factor, not |V|/n_shards
# (src/repro/dist/README.md)
cfg = EngineConfig(n_cap=1 << max(8, (2 * n_nodes).bit_length()),
                   m_cap=1 << max(10, (2 * len(stream)).bit_length()),
                   d_cap=64, sn_cap=48, c=24, batch=64, escape=0.2,
                   proposal=args.proposal, objective=args.objective,
                   weight_levels=args.weight_levels)
print(f"policy: proposal={cfg.proposal} objective={cfg.objective} "
      f"commit={cfg.commit}")

ckpt_dir = "/tmp/mosso_stream_ckpt"
shutil.rmtree(ckpt_dir, ignore_errors=True)


def make_engine(checkpoint_dir=None):
    return ShardedSummarizer(cfg, n_shards=2, router_chunk=512,
                             checkpoint_dir=checkpoint_dir)


ss = make_engine(ckpt_dir)
assert ss.routing == "device" and ss.sync_free and ss.pipeline
# the constructor resolves replica_exec=None to the backend-aware default
assert ss.replica_exec == default_replica_exec()
print(f"router: chunk={ss.router_chunk} lane_cap={ss.lane_cap} "
      f"sync_free={ss.sync_free} pipeline={ss.pipeline} "
      f"replica_exec={ss.replica_exec}")

# --- crash mid-stream: every chunk is write-ahead journaled before its
# dispatch, an epoch checkpoint lands every 2 chunks, and the kill fires
# at a chunk boundary that is NOT a checkpoint (the journal tail earns it)
n_chunks = -(-len(stream) // ss.router_chunk)
kill_at = max(n_chunks // 2, 1) | 1          # odd => between checkpoints
t0 = time.time()
try:
    drive(ss, stream, ckpt_every=2, kill_at_chunk=kill_at)
    raise SystemExit("kill point never reached — stream too short?")
except SimulatedCrash as e:
    half = ss.stream_cursor
    t_half = time.time() - t0
    print(f"[t={half}] ratio={ss.compression_ratio():.3f} phi={ss.phi} "
          f"({1e6*t_half/max(half,1):.0f} us/change incl. compile)")
    print(f"crash injected: {e}")

# steady-state dispatch stayed sync-free and dict-free up to the kill
st = ss.stats()
assert st["router_syncs"] == 0 and st["router_host_dict_ops"] == 0, st
print(f"dispatch telemetry: syncs={st['router_syncs']} "
      f"host_dict_ops={st['router_host_dict_ops']} "
      f"drain_rounds={st['router_drain_rounds']}")
ss.flush()                                   # pin the view at the kill point
q_pre = ss.query()
probe = sorted({u for (u, v, _ins) in stream[:half]})[:64]
answers_pre = {u: (q_pre.degree(u), sorted(q_pre.neighbors(u)))
               for u in probe}

# --- recovery: the crashed object is ABANDONED (as a real restart would);
# a fresh engine restores the last epoch and replays the journal tail
ss2 = make_engine(ckpt_dir)
info = ss2.recover()
print(f"recovered: epoch={info['epoch']} "
      f"replayed_chunks={info['replayed_chunks']} cursor={info['cursor']}")
assert ss2.stream_cursor == half, (ss2.stream_cursor, half)

# post-recovery query answers are identical to the pre-kill view (both
# views pinned at the same flush epoch — the kill-point chunk boundary)
ss2.flush()
q_post = ss2.query()
answers_post = {u: (q_post.degree(u), sorted(q_post.neighbors(u)))
                for u in probe}
assert answers_post == answers_pre, "recovered query answers diverged!"
print(f"query answers identical across recovery ({len(probe)} labels) ✓")

# --- continue both runs to the end: the recovered run must land bitwise
# on the uninterrupted run's state (the standing recovery bar)
ref = make_engine()                          # uninterrupted reference
t0 = time.time()
ref.process(stream)
ss2.process(stream[ss2.stream_cursor:])
ref.flush(), ss2.flush()
t_rest = time.time() - t0
for a, b in zip(jax.tree.leaves(ref.state), jax.tree.leaves(ss2.state)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(jax.tree.leaves(ref.intern), jax.tree.leaves(ss2.intern)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
assert ref.phi == ss2.phi
print(f"crash-recover verified: bitwise state match, phi={ref.phi} ✓")

print(f"[t={len(stream)}] ratio={ss2.compression_ratio():.3f} "
      f"phi={ss2.phi} |E|={ss2.num_edges}")
print(f"stats: {ss2.stats()}")
print(f"steady-state throughput: "
      f"{(2 * len(stream) - half)/t_rest:.0f} changes/s on CPU "
      f"(both runs; TPU is the deployment target)")
