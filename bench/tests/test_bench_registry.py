"""A later change adds a configuration, a mix and a metric as new files
plus entries in BENCHMARK.json; the harness finds them by name, and no
file that was there changes."""
import hashlib
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "bench"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchlib import registry  # noqa: E402
from benchlib.harness import run_cell  # noqa: E402


NEW_METRIC = '''"""Timed chunks per second of window."""


def read(run):
    return run.extra["timed_chunks"] / run.seconds
'''


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_cell_metric_and_files_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digest(tmp_path / "bench")

    cfg = json.loads((ROOT / "bench/configs/ba_fd_1chip.json").read_text())
    cfg.update(name="tiny_ba", n_shards=2, router_chunk=64,
               engine=dict(cfg["engine"], n_cap=4096, m_cap=16384, d_cap=32,
                           sn_cap=24, c=8, batch=16, escape=0.3))
    cfg["stream"] = dict(cfg["stream"], n_nodes=2048)
    (tmp_path / "bench/configs/tiny_ba.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/mixes/ingest_short.json").write_text(json.dumps(
        {"driver": "closed_loop", "trace_after": 1, "trace_chunks": 1}))
    (tmp_path / "bench/metrics/chunks_per_s.py").write_text(NEW_METRIC)

    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_ba", "source": "test",
                            "file": "bench/configs/tiny_ba.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny_ba.ingest_short",
                              "config": "tiny_ba", "traffic": "ingest_short",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "chunks_per_s", "unit": "chunks/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["tiny_ba.ingest_short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = tmp_path / "bench"
    cell = registry.load_cell(tmp_path, "tiny_ba.ingest_short", bench)
    assert cell.config["n_shards"] == 2 and cell.mix["trace_after"] == 1
    assert {m["name"] for m in cell.end_to_end} == {"chunks_per_s",
                                                     "setup_s"}
    assert registry.load_cell(tmp_path, "ba_fd_1chip.serve",
                              bench).end_to_end == registry.load_cell(
        ROOT, "ba_fd_1chip.serve").end_to_end

    res, _ = run_cell(tmp_path, "tiny_ba.ingest_short", 4, 1.5, False,
                      require_tpu=False, cache=False, bench=bench)
    assert res["correct"], res["checks"]
    assert res["metrics"]["chunks_per_s"]["value"] > 0
    assert res["metrics"]["chunks_per_s"]["unit"] == "chunks/s"

    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
