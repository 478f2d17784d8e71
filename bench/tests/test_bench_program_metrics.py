"""The per-layer metrics read from the program's own spans and counters,
on both cells at a small size on the CPU, and their silence on a program
that records neither."""
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
SEED = 2 ** 31 + 11

METRICS = {"slot_fill_share": ["ba_fd_1chip.ingest"],
           "trial_fill_share": ["ba_fd_1chip.ingest"],
           "journal_ms_per_chunk": ["ba_fd_1chip.ingest"],
           "read_wait_ms_per_batch": ["ba_fd_1chip.serve"],
           "engine_programs": ["ba_fd_1chip.ingest", "ba_fd_1chip.serve"]}


@pytest.fixture(scope="module")
def runs():
    """Each cell run once through the harness, with its Run kept."""
    out = {}
    for cell in ("ba_fd_1chip.ingest", "ba_fd_1chip.serve"):
        held = []
        res, _ = run_cell(ROOT, cell, SEED, 2.0, False, require_tpu=False,
                          cache=False, chips=1, config_overrides=SMOKE,
                          patch=held.append)
        assert res["correct"], res["checks"]
        out[cell] = held[0]
    return out


def _read(name, run):
    return registry.load_module("metrics", name).read(run)


def test_cells_report_the_program_metrics():
    for name, cells in METRICS.items():
        for cell in ("ba_fd_1chip.ingest", "ba_fd_1chip.serve"):
            layer = {m["name"] for m in registry.load_cell(ROOT,
                                                           cell).per_layer}
            assert (name in layer) == (cell in cells), (name, cell)


@pytest.mark.parametrize("name,cell", [(n, c) for n, cs in METRICS.items()
                                       for c in cs])
def test_program_metric_reads(runs, name, cell):
    run = runs[cell]
    value = _read(name, run)
    assert value is not None and value > 0, (name, cell)
    if name.endswith("_share"):
        assert value <= 100.0
    if name == "slot_fill_share":
        cfg, s = run.summ.cfg, run.stats
        changes = sum(len(c) for c in run.chunks)
        assert s["engine_rounds"] >= len(run.chunks)
        assert value == pytest.approx(
            100.0 * changes / (s["engine_rounds"] * s["n_shards"]
                               * cfg.batch))
    if name == "engine_programs":
        assert value == run.stats["stage_programs"]["engine"]


@pytest.mark.parametrize("cell", ["ba_fd_1chip.ingest", "ba_fd_1chip.serve"])
def test_process_children_fit_inside_the_harness_span(runs, cell):
    """Every ``process()`` the harness timed holds one ``summarizer.process``
    root, whose children's time sums to no more than the harness's
    ``bench.process`` span around the same call."""
    run = runs[cell]
    spans = run.summ.obs.spans("summarizer.")
    calls = [(a, b) for n, a, b in run.spans if n == "bench.process"]
    assert len(calls) == len(run.chunks)
    for a, b in calls:
        inside = run.summ.obs.spans("summarizer.", a, b)
        roots = [s for s in inside if s.name == "summarizer.process"]
        assert len(roots) == 1
        kids = [s for s in spans if s.parent_id == roots[0].span_id]
        assert {s.name for s in kids} >= {"summarizer.journal",
                                          "summarizer.pack",
                                          "summarizer.route"}
        assert sum(s.seconds for s in kids) <= roots[0].seconds <= b - a


def test_program_metrics_silent_without_spans_or_counters():
    """A program that records no spans and counts no engine rounds (one
    older than these metrics) leaves every one of them out of the result
    line, rather than raising."""
    stats = {"phi": 1, "num_edges": 1, "trials": 10, "accepted": 1,
             "skipped": 0, "n_shards": 2, "router_drain_rounds": 0}
    run = types.SimpleNamespace(
        summ=types.SimpleNamespace(cfg=types.SimpleNamespace(batch=16, c=8)),
        stats=stats, chunks=[[(1, 2, True)]], t0=0.0, t1=1.0)
    for name in METRICS:
        assert _read(name, run) is None, name
    run.stats = None
    for name in METRICS:
        assert _read(name, run) is None, name
