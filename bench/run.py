#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload ba_fd_1chip.ingest --seed 7 --seconds 51 \
        --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; see ``bench/benchlib/harness.py``.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: every number compared with its limit, also the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchlib.harness import NoAccelerator, run_cell
    try:
        result, info = run_cell(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    except NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    print("info: " + json.dumps(info, default=str), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
