# Paper-table benchmark driver. Prints ``name,us_per_call,derived`` CSV.
#
#   python benchmarks/run.py                  # every benchmark
#   python benchmarks/run.py --only router    # name-filtered subset
#   python benchmarks/run.py --smoke          # tiny CI config: router path
#                                             # (host + device) end to end
#   python benchmarks/run.py --smoke --json BENCH_router.json
#                                             # also write rows as JSON (CI
#                                             # records the perf trajectory;
#                                             # rows carry git sha + config)
#   python benchmarks/run.py --smoke --compare BENCH_router.json
#                                             # exit 1 on >20% us_per_call
#                                             # regression vs the committed
#                                             # baseline (matching rows)
import argparse
import json
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

REGRESSION_TOLERANCE = 1.20   # --compare fails rows slower than 1.2x baseline


def _git_sha() -> str:
    root = Path(__file__).resolve().parent.parent
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            capture_output=True, text=True, timeout=10).stdout.strip()
        if not sha:
            return "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root,
            capture_output=True, text=True, timeout=10).stdout.strip()
        return sha + ("-dirty" if dirty else "")
    except OSError:
        return "unknown"


def _run_config(smoke: bool) -> dict:
    import jax
    return {"smoke": smoke, "backend": jax.default_backend(),
            "devices": len(jax.devices())}


_CONFIG_KEYS = ("backend", "devices", "smoke")


def compare_rows(old_rows: list, new_rows: list, tol: float):
    """Regressions: matching rows whose us_per_call grew past tol.

    Rows match on name AND run config (backend/devices/smoke — the
    fields the rows carry precisely so that, e.g., an 8-device baseline
    is never timed against a 1-device run).  Returns ``(regressions,
    skipped)`` where regressions are ``(name, old_us, new_us, ratio)``
    tuples and skipped are names present in both runs that could not be
    compared (config mismatch, or a nonpositive baseline time).  Rows
    missing from either side are ignored — renames must not masquerade
    as wins or losses.

    Caveat (accepted trade-off of gating on absolute wall-clock): the
    baseline is only meaningful on hardware comparable to the machine
    that recorded it; a much slower CI host can trip the tolerance with
    no code change.  Re-record the baseline (``--json`` on a clean
    checkout) when the reference hardware changes.  On noisy reference
    hardware, record the committed baseline as a per-row MAX over a few
    clean-checkout runs (an envelope): run-to-run variance then stays
    inside the tolerance while the regressions this gate exists for
    (compile-in-the-loop, algorithmic blowups — historically 10x+)
    still trip it.
    """
    old = {r["name"]: r for r in old_rows}
    out, skipped = [], []
    for r in new_rows:
        base = old.get(r["name"])
        if base is None:
            continue
        if (base.get("us_per_call", 0) <= 0
                or any(base.get(k) != r.get(k) for k in _CONFIG_KEYS)):
            skipped.append(r["name"])    # matched but not comparable
            continue
        ratio = r["us_per_call"] / base["us_per_call"]
        if ratio > tol:
            out.append((r["name"], base["us_per_call"], r["us_per_call"],
                        ratio))
    return out, skipped


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-config subset for CI (exercises the stream "
                         "router in all routing/sync/pipeline modes)")
    ap.add_argument("--only", default=None,
                    help="run only benchmarks whose function name contains "
                         "this substring")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="additionally write the rows as a JSON array "
                         "(PR-over-PR perf tracking artifact; each row "
                         "carries the git sha and run config)")
    ap.add_argument("--compare", default=None, metavar="OLD.json",
                    help="compare this run against a baseline JSON and exit "
                         f"nonzero on a >{REGRESSION_TOLERANCE - 1:.0%} "
                         "us_per_call regression for any matching row name")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import paper_benchmarks as pb
    fns = [pb.smoke] if args.smoke else [
        fn for fn in pb.ALL
        if args.only is None or args.only in fn.__name__]
    if not fns:
        sys.exit(f"no benchmark matches --only {args.only!r}")
    sha, config = _git_sha(), _run_config(args.smoke)
    rows = []
    print("name,us_per_call,derived")
    for fn in fns:
        for (name, us, derived) in fn():
            rows.append({"name": name, "us_per_call": round(us, 1),
                         "derived": derived, "sha": sha, **config})
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
    if args.json:
        Path(args.json).write_text(json.dumps(rows, indent=2) + "\n")
    if args.compare:
        baseline = Path(args.compare)
        if not baseline.exists():
            sys.exit(f"--compare: baseline {baseline} does not exist — "
                     f"generate and commit one with --json first")
        old_rows = json.loads(baseline.read_text())
        old_sha = old_rows[0].get("sha", "?") if old_rows else "?"
        regressions, skipped = compare_rows(old_rows, rows,
                                            REGRESSION_TOLERANCE)
        matched = {r["name"] for r in rows} & {r["name"] for r in old_rows}
        print(f"compare: {len(matched)} matching rows vs {args.compare} "
              f"(baseline sha {old_sha})")
        for name in skipped:
            print(f"SKIP {name}: run config differs from baseline "
                  f"({'/'.join(_CONFIG_KEYS)}) — not comparable")
        for (name, base, now, ratio) in regressions:
            print(f"REGRESSION {name}: {base:.1f} -> {now:.1f} us_per_call "
                  f"({ratio:.2f}x, tolerance {REGRESSION_TOLERANCE:.2f}x)")
        if regressions:
            sys.exit(1)
        if len(skipped) >= len(matched):
            # a gate that compares nothing must fail loudly, not pass —
            # renamed rows or a config drift would otherwise disarm it
            sys.exit("--compare: no comparable rows (all matched rows "
                     "were renamed or run under a different config)")
        print(f"compare: no regressions "
              f"({len(matched) - len(skipped)} rows within "
              f"{REGRESSION_TOLERANCE:.2f}x)")


if __name__ == '__main__':
    main()
