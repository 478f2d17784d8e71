"""Every cell's phases at a small size on the CPU: the result line, the
check, the faults it must catch, and the refusal without a TPU."""
import contextlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "bench"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import control  # noqa: E402
from benchlib.harness import run_cell  # noqa: E402


SMOKE = {"engine": dict(n_cap=4096, m_cap=16384, d_cap=32, sn_cap=24, c=8,
                        batch=16, escape=0.3),
         "n_shards": 2, "router_chunk": 64}
SEED = 2 ** 31 + 5


def smoke(cell, seconds=2.0, seed=SEED, over=SMOKE, **kw):
    return run_cell(ROOT, cell, seed, seconds, False, require_tpu=False,
                    cache=False, chips=1, config_overrides=over, **kw)


def _shape(result, metrics):
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == set(metrics)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(result)


def test_ingest_cell():
    res, info = smoke("ba_fd_1chip.ingest")
    _shape(res, {"changes_per_s", "setup_s"})
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert info["window_compiles"] == 0
    assert info["stats"]["router_syncs"] == 0


def test_serve_cell():
    res, info = smoke("ba_fd_1chip.serve")
    _shape(res, {"visible_p95_s", "summary_ratio", "setup_s"})
    assert res["correct"], res["checks"]
    assert info["window_reads"] > 0 and res["failed"] == 0
    assert info["window_compiles"] == 0
    assert res["checks"]["reads_unchecked"]["value"] == 0


@pytest.mark.parametrize("cell,variant", [
    ("ba_fd_1chip.ingest", "control"),
    ("ba_fd_1chip.ingest", "state_unchanged"),
    ("ba_fd_1chip.ingest", "half_chunk"),
    ("ba_fd_1chip.serve", "control"),
    ("ba_fd_1chip.serve", "state_unchanged"),
    ("ba_fd_1chip.serve", "answer_altered"),
])
def test_faults_come_out_not_correct(cell, variant):
    ctx = control.CONTEXTS.get(variant, contextlib.nullcontext)
    with ctx():
        res, _ = smoke(cell, patch=control.PATCHES.get(variant))
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ba_fd_1chip.ingest",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


@pytest.mark.parametrize("missing", ["_route", "_engine"])
def test_missing_stage_is_an_error(missing):
    from benchlib.harness import Run

    class Summ:
        _route = _engine = staticmethod(lambda *a: a)

    summ = Summ()
    setattr(summ, missing, None)
    run = Run(cell=None, seed=0, seconds=1.0, tracing=False, t_start=0.0,
              summ=summ)
    with pytest.raises(AttributeError, match=missing):
        run.instrument()
    fault = {"_route": control._half_chunk,
             "_engine": control._state_unchanged}[missing]
    with pytest.raises(AttributeError, match=missing):
        fault(run)
