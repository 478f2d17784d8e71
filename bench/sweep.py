#!/usr/bin/env python3
"""Find the write knee of an open-loop cell: the highest write rate at
which the backlog stays flat with the mix's reads on.

    python bench/sweep.py --workload ba_fd_1chip.serve --seconds 45 \
        --seed 5 --rates 14 20 26 32

Each rate is one harness run of the cell with its ``write_rate`` replaced.
The backlog is flat when the time from a chunk's last arrival to its
epoch's completion does not grow from chunk to chunk: the run prints, per
rate, those times, their growth per chunk, and ``visible_p95_s``.  Set the
cell's rate to four fifths of the highest flat one.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchlib.harness import NoAccelerator, run_cell
    for rate in args.rates:
        try:
            res, info = run_cell(ROOT, args.workload, args.seed,
                                 args.seconds, False,
                                 mix_overrides={"write_rate": rate})
        except NoAccelerator as e:
            print(f"sweep: {e}", file=sys.stderr)
            return 2
        lag = info["chunk_full_to_visible_s"]
        slope = float(np.polyfit(np.arange(len(lag)), lag, 1)[0]) \
            if len(lag) > 2 else None
        print("SWEEP " + json.dumps({
            "rate": rate, "correct": res["correct"],
            "full_to_visible_s": lag, "growth_s_per_chunk": slope,
            "metrics": {k: m["value"] for k, m in res["metrics"].items()},
            "info": info}, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
