"""chip_smoke.py's phases, rehearsed on the CPU at smoke_config() size.

The script's ``main`` refuses to run without a TPU; its phase functions
take a config, so every check the chip run makes (phi refold, lossless
decode, sampled queries, view-vs-donation contract, bitwise recovery,
mesh-vs-one-device equality) is guarded here on every change.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_single_chip_phase_on_cpu(tmp_path):
    from repro.configs.mosso_stream import smoke_config

    res = _chip_smoke().single_chip_phase(
        smoke_config(), n_shards=2, chunk=64, n_queries=32,
        workdir=str(tmp_path))
    assert res["n_changes"] == 4 * 64
    assert res["n_shards"] == 2
    assert res["state_bytes_per_shard"] > 0


def test_main_refuses_without_tpu(capsys):
    assert _chip_smoke().main([]) != 0
    out = capsys.readouterr()
    assert "no TPU" in out.err
    assert '"ok"' not in out.out


def test_multi_chip_phase_on_four_cpu_devices():
    """The --chips 4 phase on four virtual CPU devices (fresh interpreter:
    the device count is fixed when the backend starts)."""
    code = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs.mosso_stream import smoke_config
        res = cs.multi_chip_phase(smoke_config(), n_shards=8, chunk=64)
        print("RESULT", json.dumps(res))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    assert res["mesh_devices"] == 4 and res["one_device_devices"] == 1
    assert "check mesh_vs_one_device.leaf_bitwise" in proc.stdout
