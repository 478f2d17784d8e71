"""The ``trial_passes_per_group`` reader: the ratio on a run of the
ingest cell at a small size on the CPU, and silence on stats that lack
the counter (a program older than it) or hold none (the serial layout)."""
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "bench"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchlib import registry  # noqa: E402
from benchlib.harness import run_cell  # noqa: E402

SMOKE = {"engine": dict(n_cap=4096, m_cap=16384, d_cap=32, sn_cap=24, c=8,
                        batch=16, escape=0.3),
         "n_shards": 2, "router_chunk": 64}
SEED = 2 ** 31 + 13


def _reader():
    return registry.load_module("metrics", "trial_passes_per_group")


def test_ingest_cell_reports_trial_passes_per_group():
    layer = {m["name"]: m for m in
             registry.load_cell(ROOT, "ba_fd_1chip.ingest").per_layer}
    assert layer["trial_passes_per_group"]["moves"] == "changes_per_s"
    serve = registry.load_cell(ROOT, "ba_fd_1chip.serve").per_layer
    assert "trial_passes_per_group" not in {m["name"] for m in serve}


def test_trial_passes_per_group_reads_the_ratio(monkeypatch):
    # the vmapped replica layout, the one that runs speculative passes,
    # as on a TPU (the CPU default is the serial ``map`` layout)
    monkeypatch.setenv("REPRO_REPLICA_EXEC", "vmap")
    held = []
    res, _ = run_cell(ROOT, "ba_fd_1chip.ingest", SEED, 2.0, False,
                      require_tpu=False, cache=False, chips=1,
                      config_overrides=SMOKE, patch=held.append)
    assert res["correct"], res["checks"]
    run = held[0]
    s, cfg = run.stats, run.summ.cfg
    groups = s["engine_rounds"] * s["n_shards"] * 2 * cfg.batch
    value = _reader().read(run)
    assert value == pytest.approx(s["trial_passes"] / groups)
    # a live group runs at least one pass, a padding slot none, and every
    # pass past a group's first commits a move
    assert 0 < value <= 1 + s["accepted"] / groups
    assert s["trial_passes"] <= groups + s["accepted"]


@pytest.mark.parametrize("stats", [
    None,
    {"trials": 10, "accepted": 1, "skipped": 0, "n_shards": 2},
    {"trials": 10, "accepted": 1, "skipped": 0, "n_shards": 2,
     "engine_rounds": 3},
    # the serial ``map`` layout runs no pass
    {"trials": 10, "accepted": 1, "skipped": 0, "n_shards": 2,
     "engine_rounds": 3, "trial_passes": None},
])
def test_trial_passes_per_group_silent_without_the_counter(stats):
    run = types.SimpleNamespace(
        summ=types.SimpleNamespace(cfg=types.SimpleNamespace(batch=16, c=8)),
        stats=stats)
    assert _reader().read(run) is None
