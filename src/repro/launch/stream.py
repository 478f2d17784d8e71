"""MoSSo streaming driver: summarize a dynamic graph stream end to end.

Runs the faithful reference (Tier A), the batched engine (Tier B), or the
edge-partitioned sharded engine over a synthetic or file-based stream,
reporting phi, the compression ratio (Eq. 3), and per-change timing — the
paper's any-time workload as a CLI.  The sharded engine streams batches
through the device-side router by default (``--routing device``); pass
``--routing host`` to drive the same shards through host bucketing, the
differential reference path.

With ``--checkpoint-dir`` the batched/sharded engines run crash-consistent:
every dispatch chunk is write-ahead journaled, an epoch checkpoint lands
every ``--checkpoint-every`` chunks, and a failed chunk abandons the live
summarizer, restores the latest valid epoch, replays the journal tail and
resumes (``repro.ft.resilience.run_stream_with_recovery``; retries are
reported as ``stream_retries`` in the final stats).  ``--resume`` recovers
from the directory before processing, so a killed run continues from its
last journaled chunk instead of starting over.

Usage:
  PYTHONPATH=src python -m repro.launch.stream --algo mosso --nodes 2000 \
      --edges 8000 --engine reference
  PYTHONPATH=src python -m repro.launch.stream --engine batched --batch 64
  PYTHONPATH=src python -m repro.launch.stream --engine sharded --shards 2 \
      --routing device --router-chunk 1024
  PYTHONPATH=src python -m repro.launch.stream --engine sharded \
      --checkpoint-dir /tmp/mosso-ckpt --checkpoint-every 8 --resume
"""
from __future__ import annotations

import argparse
import time

from repro.core.engine import (BatchedSummarizer, EngineConfig,
                               ShardedSummarizer)
from repro.core.engine.state import OBJECTIVES, PROPOSALS
from repro.core.reference import ALGORITHMS, WeightedDynamicSummary
from repro.dist.router import REPLICA_EXEC_MODES
from repro.graph.streams import (barabasi_albert_edges, copying_model_edges,
                                 edges_to_fully_dynamic_stream,
                                 edges_to_insertion_stream)
from repro.launch.cache import enable_compile_cache


def make_stream(kind: str, nodes: int, edges_per_node: int, beta: float,
                fully_dynamic: bool, seed: int):
    if kind == "copying":
        edges = copying_model_edges(nodes, edges_per_node, beta, seed)
    else:
        edges = barabasi_albert_edges(nodes, edges_per_node, seed)
    if fully_dynamic:
        return edges_to_fully_dynamic_stream(edges, seed=seed)
    return edges_to_insertion_stream(edges, seed=seed)


def main() -> None:
    # search/batch defaults come FROM EngineConfig, so the CLI, tests, and
    # benchmarks run the same configuration by construction (drifting
    # literals here once shipped c=32/escape=0.2/batch=64 against the
    # engine's 20/0.3/32)
    dflt = EngineConfig()
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=["reference", "batched", "sharded"],
                    default="reference")
    ap.add_argument("--shards", type=int, default=None,
                    help="sharded: logical partitions (default: one/device)")
    ap.add_argument("--routing", choices=["device", "host"], default="device",
                    help="sharded: device-side router or host bucketing")
    ap.add_argument("--router-chunk", type=int, default=1024,
                    help="sharded: changes per routed dispatch")
    ap.add_argument("--lane-cap", type=int, default=None,
                    help="sharded: per (source, shard) router lane capacity")
    ap.add_argument("--max-drain-rounds", type=int, default=None,
                    help="sharded: on-device overflow drain round budget "
                         "(default: enough to guarantee full delivery, "
                         "which elides the per-chunk watermark sync)")
    ap.add_argument("--chunk-sync", action="store_true",
                    help="sharded: force the per-chunk watermark fetch even "
                         "when delivery is statically guaranteed (measures "
                         "the sync-elision gap)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="sharded: dispatch route and engine stages "
                         "serially per chunk instead of overlapping chunk "
                         "k+1's routing with chunk k's engine rounds "
                         "(measures the pipeline gap; results are "
                         "bit-identical)")
    ap.add_argument("--replica-exec", choices=list(REPLICA_EXEC_MODES),
                    default=None,
                    help="sharded: lay the per-device shard replicas out "
                         "as one vmapped program or a serializing lax.map "
                         "(results are bit-identical; default: vmap on "
                         "accelerators, map on the CPU)")
    ap.add_argument("--algo", choices=list(ALGORITHMS), default="mosso")
    ap.add_argument("--graph", choices=["ba", "copying"], default="ba")
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--deg", type=int, default=4)
    ap.add_argument("--beta", type=float, default=0.7)
    ap.add_argument("--fully-dynamic", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--c", type=int, default=dflt.c)
    ap.add_argument("--escape", type=float, default=dflt.escape)
    ap.add_argument("--batch", type=int, default=dflt.batch)
    # policy triple: defaults from EngineConfig (which resolves the
    # REPRO_PROPOSAL/REPRO_OBJECTIVE env vars), same no-drift contract
    ap.add_argument("--proposal", choices=list(PROPOSALS),
                    default=dflt.proposal,
                    help="candidate scheme (batched/sharded engines; the "
                         "reference analog is --algo mosso vs --algo mags)")
    ap.add_argument("--objective", choices=list(OBJECTIVES),
                    default=dflt.objective,
                    help="move-scoring objective (all engines)")
    ap.add_argument("--weight-levels", type=int, default=dflt.weight_levels,
                    help="weighted objective: node weights 1 + hash % N "
                         "(0/1 = uniform)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="batched/sharded: crash-consistent mode — "
                         "write-ahead journal every dispatch chunk and "
                         "checkpoint epochs into this directory")
    ap.add_argument("--checkpoint-every", type=int, default=16,
                    help="chunks between epoch checkpoints "
                         "(with --checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="recover from --checkpoint-dir (last valid epoch "
                         "+ journal replay) before processing")
    ap.add_argument("--max-failures", type=int, default=3,
                    help="failed chunks tolerated before giving up "
                         "(with --checkpoint-dir)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.checkpoint_dir and args.engine == "reference":
        ap.error("--checkpoint-dir requires --engine batched or sharded "
                 "(the reference tier has no checkpoint closure)")

    stream = make_stream(args.graph, args.nodes, args.deg, args.beta,
                         args.fully_dynamic, args.seed)
    print(f"stream: {len(stream)} changes")
    t0 = time.time()
    if args.engine == "reference":
        algo = ALGORITHMS[args.algo](seed=args.seed)
        if args.objective == "weighted":
            # the driver hooks are summary-agnostic: swap in the weighted
            # host state machine before any change is processed
            algo.s = WeightedDynamicSummary(weight_levels=args.weight_levels)
        if hasattr(algo, "c"):
            algo.c = args.c
        if hasattr(algo, "escape"):
            algo.escape = args.escape
        algo.run(stream)
        phi, m = algo.s.phi, algo.s.num_edges
        extra = f"trials={algo.stats.trials} accepted={algo.stats.accepted}"
    elif args.engine == "batched":
        n_cap = 1 << max(8, (args.nodes * 2).bit_length())
        m_cap = 1 << max(10, (len(stream) * 2).bit_length())
        cfg = EngineConfig(
            n_cap=n_cap, m_cap=m_cap, c=args.c, escape=args.escape,
            batch=args.batch, proposal=args.proposal,
            objective=args.objective, weight_levels=args.weight_levels)
        if args.checkpoint_dir:
            from repro.ft.resilience import run_stream_with_recovery
            bs = run_stream_with_recovery(
                lambda: BatchedSummarizer(
                    cfg, checkpoint_dir=args.checkpoint_dir),
                stream, args.checkpoint_dir,
                ckpt_every=args.checkpoint_every, resume=args.resume,
                max_failures=args.max_failures)
        else:
            bs = BatchedSummarizer(cfg).run(stream)
        phi, m = bs.phi, bs.num_edges
        extra = str(bs.stats())
    else:
        # per-shard caps: vertex-cut replication means n_cap budgets more
        # than |V| / n_shards (src/repro/dist/README.md)
        n_cap = 1 << max(8, (args.nodes * 2).bit_length())
        m_cap = 1 << max(10, (len(stream) * 2).bit_length())
        cfg = EngineConfig(n_cap=n_cap, m_cap=m_cap, c=args.c,
                           escape=args.escape, batch=args.batch,
                           proposal=args.proposal, objective=args.objective,
                           weight_levels=args.weight_levels)

        def make_sharded():
            return ShardedSummarizer(
                cfg, n_shards=args.shards, routing=args.routing,
                router_chunk=args.router_chunk, lane_cap=args.lane_cap,
                max_drain_rounds=args.max_drain_rounds,
                chunk_sync=args.chunk_sync, pipeline=not args.no_pipeline,
                replica_exec=args.replica_exec,
                checkpoint_dir=args.checkpoint_dir)

        if args.checkpoint_dir:
            from repro.ft.resilience import run_stream_with_recovery
            ss = run_stream_with_recovery(
                make_sharded, stream, args.checkpoint_dir,
                ckpt_every=args.checkpoint_every, resume=args.resume,
                max_failures=args.max_failures)
        else:
            ss = make_sharded()
            if args.routing == "device":
                print(f"router: lane_cap={ss.lane_cap} "
                      f"max_drain_rounds={ss.max_drain_rounds} "
                      f"sync_free={ss.sync_free} pipeline={ss.pipeline} "
                      f"replica_exec={ss.replica_exec}")
            ss.run(stream)
        phi, m = ss.phi, ss.num_edges
        extra = str(ss.stats())
    el = time.time() - t0
    print(f"phi={phi} |E|={m} compression_ratio={phi/max(m,1):.4f}")
    print(f"total {el:.1f}s ({1e6*el/len(stream):.0f} us/change)  {extra}")


if __name__ == "__main__":
    main()
