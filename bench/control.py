#!/usr/bin/env python3
"""Calibrate the check that decides ``correct``: sound runs, the control,
and the faults, on the chip at a cell's own size, in one process.

    python bench/control.py --workload ba_fd_1chip.ingest --seconds 12 \
        --seeds 11 12 13 --variants sound control state_unchanged

Each variant runs the cell through the harness (``benchlib.harness``) with
the timed path as it is (``sound``), or with one planted fault:

* ``control``: deletions never reach the summarizer -- an insert-only
  summary, the guarantee "lossless at every flushed epoch" broken;
* ``state_unchanged``: the engine stage returns its state unchanged;
* ``half_chunk``: the second half of every chunk is left out at the route
  stage's input;
* ``answer_altered``: every read batch's first answer is altered where the
  view produces it.

It prints one ``CONTROL`` JSON line per run with the numbers compared.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

from benchlib.harness import stage_callable  # noqa: E402


def _drop_deletions(run):
    process = run.summ.process
    run.summ.process = lambda changes: process([c for c in changes if c[2]])


def _state_unchanged(run):
    stage_callable(run.summ, "_engine")

    def engine(est, ist, telem, *buckets):
        return est, ist, telem
    run.summ._engine = engine


def _half_chunk(run):
    import numpy as np
    route = stage_callable(run.summ, "_route")

    def half(uh, ul, vh, vl, fl):
        uh = np.array(uh)
        uh[len(uh) // 2:] = -1                  # the padding marker
        return route(uh, ul, vh, vl, fl)
    run.summ._route = half


@contextlib.contextmanager
def _answers_altered():
    from repro.serve.query import ShardedSummaryQuery as Q
    saved = Q.neighbors_batch, Q.degree_batch, Q.has_edge_batch

    def alter(fn, bump):
        def wrapped(self, keys):
            out = list(fn(self, keys))
            out[0] = bump(out[0])
            return out
        return wrapped

    Q.neighbors_batch = alter(saved[0], lambda s: set(s) | {"altered"})
    Q.degree_batch = alter(saved[1], lambda d: d + 1)
    Q.has_edge_batch = alter(saved[2], lambda b: not b)
    try:
        yield
    finally:
        Q.neighbors_batch, Q.degree_batch, Q.has_edge_batch = saved


PATCHES = {"sound": None, "control": _drop_deletions,
           "state_unchanged": _state_unchanged, "half_chunk": _half_chunk}
CONTEXTS = {"answer_altered": _answers_altered}


def run_variant(root, workload, seed, seconds, variant, **kw):
    """One harness run with ``variant`` planted; returns (result, info)."""
    from benchlib.harness import run_cell
    ctx = CONTEXTS.get(variant, contextlib.nullcontext)
    with ctx():
        return run_cell(root, workload, seed, seconds, False,
                        patch=PATCHES.get(variant), **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=["sound", "control"],
                    choices=sorted(set(PATCHES) | set(CONTEXTS)))
    args = ap.parse_args(argv)
    from benchlib.harness import NoAccelerator
    for variant in args.variants:
        for seed in args.seeds:
            t = time.perf_counter()
            try:
                res, info = run_variant(ROOT, args.workload, seed,
                                        args.seconds, variant)
                checks = {k: c["value"] for k, c in res["checks"].items()}
                line = {"variant": variant, "seed": seed,
                        "correct": res["correct"], "checks": checks,
                        "metrics": {k: m["value"] for k, m in
                                    res["metrics"].items()},
                        "info": info}
            except NoAccelerator as e:
                print(f"control: {e}", file=sys.stderr)
                return 2
            except Exception as e:              # a crash reads as failed
                line = {"variant": variant, "seed": seed, "correct": False,
                        "crash": repr(e)[:400]}
            line["wall_s"] = time.perf_counter() - t
            print("CONTROL " + json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
