"""Online summary service: read traffic concurrent with the write stream.

Promotes the write driver (``launch/stream.py``) and the serving pattern
of ``launch/serve.py`` into one loop over the graph workload the paper
motivates: a :class:`ShardedSummarizer` consumes the change stream chunk
by chunk while ``neighbors``/``degree``/``has_edge`` reads are answered
from flush-epoch query snapshots (:mod:`repro.serve.query`).  On the
pipelined sync-free router the snapshot intentionally trails the write
head by the one routed-but-undispatched chunk, so reads overlap the
in-flight engine stage instead of forcing a per-chunk barrier — the
reported ``epoch lag`` histogram makes that overlap visible.

``--verify`` additionally checks every sampled read against the host
ground truth of the snapshot's OWN epoch prefix (not the write head's),
i.e. the snapshot-consistency contract tests/test_query.py pins.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_summary --nodes 400 \
      --reads-per-chunk 64 --verify
"""
from __future__ import annotations

import argparse
import time
from typing import Sequence

import numpy as np

from repro.core.engine import EngineConfig, ShardedSummarizer
from repro.dist.router import REPLICA_EXEC_MODES
from repro.launch.cache import enable_compile_cache
from repro.launch.stream import make_stream


def serve_summary(summarizer: ShardedSummarizer, stream: Sequence,
                  reads_per_chunk: int = 64, verify: bool = False,
                  seed: int = 0) -> dict:
    """Interleave write chunks with read batches; return service stats.

    Reads are sampled from the labels streamed so far and answered from a
    fresh ``query()`` snapshot after every chunk — while the pipelined
    router still has that chunk's engine stage (and the next chunk's
    routing) in flight.  With ``verify`` each read batch is compared to
    the edge set of the snapshot's epoch prefix.  ``read_ms_p50`` and
    ``read_ms_p95`` are taken from the program's own read spans (one
    ``query.*`` root span per read batch; ``None`` with the summarizer's
    span recorder off).
    """
    rng = np.random.default_rng(seed)
    chunk_n = summarizer.router_chunk
    n_chunks = -(-len(stream) // chunk_n)
    seen: list = []
    seen_set: set = set()
    live_after: list = []       # live edge set after chunk k (verify only)
    live: set = set()

    n_reads = 0
    t_start = time.perf_counter()
    lags: list = []
    for k in range(n_chunks):
        chunk = stream[k * chunk_n:(k + 1) * chunk_n]
        summarizer.process(chunk)
        for (u, v, ins) in chunk:
            for lab in (u, v):
                if lab not in seen_set:
                    seen_set.add(lab)
                    seen.append((lab, k + 1))   # first visible at epoch k+1
            if verify:
                e = (min(u, v), max(u, v))
                live.add(e) if ins else live.discard(e)
        if verify:
            live_after.append(frozenset(live))

        view = summarizer.query()
        lags.append(k + 1 - view.epoch)
        # only labels the snapshot's epoch has seen are queryable on it
        pool = [lab for (lab, ep) in seen if ep <= view.epoch]
        if not pool:
            continue
        labs = [pool[i] for i in
                rng.integers(0, len(pool), reads_per_chunk)]
        pairs = list(zip(labs, labs[::-1]))
        nbrs = view.neighbors_batch(labs)
        degs = view.degree_batch(labs)
        present = [view.has_edge(u, v) if u != v else False
                   for (u, v) in pairs[:8]]
        n_reads += len(labs) * 2 + len(present)

        if verify:
            truth = live_after[view.epoch - 1] if view.epoch else frozenset()
            adj: dict = {}
            for (u, v) in truth:
                adj.setdefault(u, set()).add(v)
                adj.setdefault(v, set()).add(u)
            for lab, s, d in zip(labs, nbrs, degs):
                want = adj.get(lab, set())
                assert s == want, f"epoch {view.epoch} neighbors({lab!r})"
                assert d == len(want)
            for (u, v), p in zip(pairs, present):
                want = (min(u, v), max(u, v)) in truth
                assert p == want, f"epoch {view.epoch} has_edge({u!r},{v!r})"

    # one root span per read batch, in ms
    reads = [s.seconds * 1e3 for s in summarizer.obs.spans(
        "query.", t_start, time.perf_counter()) if s.parent_id == 0]
    summarizer.flush()
    final = summarizer.query()
    assert final.epoch == n_chunks
    if verify:
        labs = [lab for (lab, _) in seen]
        truth = live_after[-1] if live_after else frozenset()
        adj = {}
        for (u, v) in truth:
            adj.setdefault(u, set()).add(v)
            adj.setdefault(v, set()).add(u)
        for lab, s in zip(labs, final.neighbors_batch(labs)):
            assert s == adj.get(lab, set()), f"final neighbors({lab!r})"

    return dict(chunks=n_chunks, changes=len(stream), reads=n_reads,
                read_ms_p50=float(np.percentile(reads, 50)) if reads else None,
                read_ms_p95=float(np.percentile(reads, 95)) if reads else None,
                epoch_lags=lags, max_lag=max(lags, default=0),
                reads_overlapped_writes=any(l > 0 for l in lags),
                final_epoch=final.epoch, phi=summarizer.phi,
                num_edges=summarizer.num_edges, verified=bool(verify))


def main() -> None:
    dflt = EngineConfig()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", choices=["ba", "copying"], default="ba")
    ap.add_argument("--nodes", type=int, default=400)
    ap.add_argument("--deg", type=int, default=4)
    ap.add_argument("--beta", type=float, default=0.7)
    ap.add_argument("--fully-dynamic", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=None)
    ap.add_argument("--router-chunk", type=int, default=256)
    ap.add_argument("--no-pipeline", action="store_true",
                    help="serial route/engine dispatch: every snapshot "
                         "then sits exactly at the write head (lag 0)")
    ap.add_argument("--replica-exec", choices=list(REPLICA_EXEC_MODES),
                    default=None,
                    help="default: vmap on accelerators, map on the CPU")
    ap.add_argument("--reads-per-chunk", type=int, default=64)
    ap.add_argument("--verify", action="store_true",
                    help="differentially check every sampled read against "
                         "the snapshot epoch's host ground truth")
    ap.add_argument("--c", type=int, default=dflt.c)
    ap.add_argument("--escape", type=float, default=dflt.escape)
    ap.add_argument("--batch", type=int, default=dflt.batch)
    args = ap.parse_args()
    enable_compile_cache()

    stream = make_stream(args.graph, args.nodes, args.deg, args.beta,
                         args.fully_dynamic, args.seed)
    n_cap = 1 << max(8, (args.nodes * 2).bit_length())
    m_cap = 1 << max(10, (len(stream) * 2).bit_length())
    ss = ShardedSummarizer(
        EngineConfig(n_cap=n_cap, m_cap=m_cap, c=args.c, escape=args.escape,
                     batch=args.batch),
        n_shards=args.shards, router_chunk=args.router_chunk,
        pipeline=not args.no_pipeline, replica_exec=args.replica_exec)
    print(f"stream: {len(stream)} changes; shards={ss.n_shards} "
          f"pipeline={ss.pipeline}")
    t0 = time.time()
    out = serve_summary(ss, stream, reads_per_chunk=args.reads_per_chunk,
                        verify=args.verify, seed=args.seed)
    el = time.time() - t0
    print(f"served {out['reads']} reads over {out['chunks']} write chunks "
          f"(read batch ms p50 {out['read_ms_p50']} p95 "
          f"{out['read_ms_p95']}, max epoch lag {out['max_lag']}, "
          f"overlapped={out['reads_overlapped_writes']})")
    print(f"phi={out['phi']} |E|={out['num_edges']} "
          f"verified={out['verified']}  total {el:.1f}s "
          f"({1e6 * el / len(stream):.0f} us/change incl. reads)")


if __name__ == "__main__":
    main()
