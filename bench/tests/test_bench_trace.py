"""The trace reduction: busy and idle time, time per stage, and idle gaps
named by the host span they fall in."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "bench"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchlib import trace  # noqa: E402


def _xspace(devices, host):
    """An XSpace text proto: ``devices`` is a list of module-event lists,
    ``host`` a list of harness spans, every event ``(name, start, end)``
    in nanoseconds."""
    planes = []
    for i, evs in enumerate(devices + [host]):
        names = sorted({n for n, _, _ in evs})
        meta = "".join(f'event_metadata {{ key: {k + 1} value {{ id: {k + 1}'
                       f' name: "{n}" }} }}\n' for k, n in enumerate(names))
        events = "".join(
            f"events {{ metadata_id: {names.index(n) + 1} offset_ps: "
            f"{a * 1000} duration_ps: {(b - a) * 1000} }}\n"
            for n, a, b in evs)
        pname, lname = ((f"/device:TPU:{i}", "XLA Modules")
                        if i < len(devices) else ("/host:CPU", "python"))
        planes.append(f'planes {{ id: {i + 1} name: "{pname}"\n'
                      f'lines {{ id: 1 name: "{lname}" timestamp_ns: 0\n'
                      f'{events}}}\n{meta}}}\n')
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto("".join(planes))


HOST = [("bench.process", 0, 100), ("bench.route_launch", 10, 20),
        ("bench.engine_launch", 30, 40), ("bench.wait", 100, 1100),
        ("bench.read.neighbors", 1100, 1300), ("bench.process", 1300, 1400),
        ("bench.wait", 1400, 2000)]
DEV0 = [("jit_local(3)", 50, 150), ("jit_local(4)", 150, 1050),
        ("jit_nbrs_local(7)", 1150, 1250), ("jit_local(3)", 1450, 1500),
        ("jit_local(4)", 1500, 1900)]
DEV1 = [("jit_local(3)", 60, 160), ("jit_local(4)", 160, 700),
        ("jit_nbrs_local(7)", 1150, 1250), ("jit_local(3)", 1450, 1500),
        ("jit_local(4)", 1500, 1710)]
LAUNCHES = ["route", "engine", "route", "engine"]


def test_busy_stages_and_gaps():
    s = trace.reduce_xspace(_xspace([DEV0, DEV1], HOST), LAUNCHES)
    assert s.n_devices == 2
    assert s.window_s == pytest.approx(2000e-9)
    assert s.busy_s == pytest.approx((1550 + 1000) / 2 * 1e-9)
    assert s.stage_ms("route") == pytest.approx(75e-6)
    assert s.stage_ms("engine") == pytest.approx((900 + 400 + 540 + 210)
                                                 / 4 * 1e-6)
    assert s.stage_ms("query") == pytest.approx(100e-6)
    assert s.stage_seconds("query") == pytest.approx(100e-9)
    assert s.span_count("bench.read.") == 1
    assert [(n, round(g * 1e9)) for n, g in s.gaps] == [
        ("bench.process", 200), ("bench.read.neighbors", 100),
        ("bench.wait", 100), ("bench.process", 50)]
    b = s.breakdown()
    assert b["device_ops"][0][0] == "engine:jit_local"
    assert b["device_ops"][0][1] == pytest.approx(1025e-9)
    assert len(b["idle_gaps"]) == 4


def test_stages_when_launches_disagree():
    # two program ids: each takes the stage of its first execution
    s = trace.reduce_xspace(_xspace([DEV0], HOST), LAUNCHES[:3])
    assert s.stage_ms("route") == pytest.approx(75e-6)
    assert s.stage_ms("engine") == pytest.approx(650e-6)
    # one program name for both: left unnamed
    same = [("jit_local" if n.startswith("jit_local") else n, a, b)
            for n, a, b in DEV0]
    s = trace.reduce_xspace(_xspace([same], HOST), LAUNCHES[:3])
    assert s.stage_ms("route") is None and s.stage_ms("engine") is None
    assert s.stage_ms("query") == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(1550e-9)


def test_events_outside_the_stretch_are_clipped():
    dev = [("jit_local(3)", -500, 50), ("jit_local(4)", 1900, 2600)]
    s = trace.reduce_xspace(_xspace([dev], HOST), ["route", "engine"])
    assert s.busy_s == pytest.approx(150e-9)


def test_device_clock_offset():
    far = [(n, a + 10 ** 9, b + 10 ** 9) for n, a, b in DEV0]
    s = trace.reduce_xspace(_xspace([far], HOST), LAUNCHES)
    assert s.busy_s == pytest.approx(1550e-9)
    assert s.stage_ms("engine") == pytest.approx(650e-6)
    assert s.span_count("bench.read.") == 1
    assert {n for n, _ in s.gaps} == {"unattributed"}


def test_no_harness_spans_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_xspace(_xspace([DEV0], []), LAUNCHES)
