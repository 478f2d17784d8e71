"""Reduce a profiler trace to device busy time, time per stage and idle gaps.

The trace is one ``.xplane.pb`` taken with ``tpu_trace_mode`` =
``TRACE_ONLY_XLA``: one event per program execution on each device plane's
``XLA Modules`` line, read with ``jax.profiler.ProfileData`` alone.

* busy: the union of the module intervals inside the traced stretch, per
  device, averaged over the devices; the stretch runs from the first to
  the last of the harness's own host spans (``bench.*``) in the trace.
* stages: the query kernels are told apart by their module names.  The
  route and engine stages compile under one name (``jit_local``); the
  harness records the order in which it launched them (``launches``), and
  a device runs one stream of programs in launch order, so the k-th such
  execution in the trace is the k-th launch inside the stretch.  Where the
  counts disagree, two distinct program names (``jit_local(<id>)``) still
  tell them apart; otherwise the two stages are left unnamed and their
  metrics silent.
* idle gaps: the longest stretches without a module on the first device,
  each named by the innermost harness span the host was in at its middle
  (``unattributed`` if the device events do not share the host's clock).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

QUERY_MODULES = ("jit_nbrs_local", "jit_deg_local", "jit_he_local")
STAGE_MODULE = "jit_local"          # the route and the engine stage


def module_base(name: str) -> str:
    """``jit_local(123)`` -> ``jit_local``."""
    return name.split("(", 1)[0].strip()


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    stage_s: Dict[str, List[float]]        # stage -> seconds per execution
    modules: List[Tuple[str, float]]       # (stage or module, seconds)
    gaps: List[Tuple[str, float]]          # (host span, seconds)
    span_names: collections.Counter        # harness spans in the stretch
    n_devices: int

    def stage_seconds(self, stage: str) -> float:
        return sum(self.stage_s.get(stage, ())) / max(self.n_devices, 1)

    def stage_ms(self, stage: str) -> Optional[float]:
        times = self.stage_s.get(stage)
        return 1e3 * sum(times) / len(times) if times else None

    def span_count(self, prefix: str) -> int:
        return sum(c for n, c in self.span_names.items()
                   if n.startswith(prefix))

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.modules[:10]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def _union(intervals: Sequence[Tuple[float, float]]) -> List[list]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _stage_names(staged, launches) -> List[Optional[str]]:
    """The stage of each ``jit_local`` execution, in device order: the
    launch order where the counts agree; else, where the executions carry
    two distinct program names (``jit_local(<id>)``), each program takes
    the stage of the launch its first execution lines up with."""
    if len(staged) == len(launches):
        return list(launches)
    first: Dict[str, str] = {}
    for (name, _, _), stage in zip(staged, launches):
        first.setdefault(name, stage)
    if len({m[0] for m in staged}) == 2 and len(set(first.values())) == 2:
        return [first.get(m[0]) for m in staged]
    return [None] * len(staged)


def reduce_xspace(pd, launches: Sequence[str] = ()) -> TraceSummary:
    """Reduce a loaded ``ProfileData``; ``launches`` is the harness's
    ordered list of stage launches (``route``/``engine``) in the stretch."""
    host_spans: List[Tuple[str, float, float]] = []
    devices: List[List[Tuple[str, float, float]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "Core" not in plane.name:
            devices.append([ev for line in plane.lines
                            if line.name == "XLA Modules"
                            for ev in _events(line)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [ev for ev in _events(line)
                               if ev[0].startswith("bench.")]
    if not host_spans:
        raise ValueError("the trace holds none of the harness's spans")
    w0 = min(a for _, a, _ in host_spans)
    w1 = max(b for _, _, b in host_spans)
    window_s = (w1 - w0) / 1e9
    attribute = host_spans
    starts = [a for mods in devices for _, a, _ in mods]
    if starts and not (w0 - (w1 - w0) <= min(starts) <= w1):
        # device and host events on different clocks: the harness starts
        # and stops the trace with the device idle, so every execution in
        # it belongs to the stretch; idle gaps cannot be named then
        shift = min(starts) - w0
        devices = [[(n, a - shift, b - shift) for n, a, b in mods]
                   for mods in devices]
        attribute = []

    busy = []
    stage_s: Dict[str, List[float]] = collections.defaultdict(list)
    per_module: Dict[str, float] = collections.defaultdict(float)
    for mods in devices:
        mods = sorted((m for m in mods if m[2] > w0 and m[1] < w1),
                      key=lambda m: m[1])
        union = _union([(max(a, w0), min(b, w1)) for _, a, b in mods])
        busy.append(sum(b - a for a, b in union) / 1e9)
        staged = [m for m in mods if module_base(m[0]) == STAGE_MODULE]
        label = {id(m): n for m, n in zip(staged, _stage_names(staged,
                                                                launches))}
        for m in mods:
            base = module_base(m[0])
            secs = (m[2] - m[1]) / 1e9
            if base in QUERY_MODULES:
                stage = "query"
            else:
                stage = label.get(id(m))
            if stage is not None:
                stage_s[stage].append(secs)
            per_module[f"{stage or 'other'}:{base}"] += secs
    n_dev = len(devices)
    busy_s = sum(busy) / n_dev if n_dev else 0.0
    modules = sorted(((n, s / max(n_dev, 1)) for n, s in per_module.items()),
                     key=lambda x: -x[1])

    gaps: List[Tuple[str, float]] = []
    if devices:
        union = _union([(max(a, w0), min(b, w1)) for _, a, b in devices[0]
                        if b > w0 and a < w1])
        edges = [w0] + [x for iv in union for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            inside = [(e - s, n) for n, s, e in attribute if s <= mid <= e]
            name = min(inside)[1] if inside else "unattributed"
            gaps.append((name, (b - a) / 1e9))
        gaps.sort(key=lambda g: -g[1])
    names = collections.Counter(n for n, a, b in host_spans)
    return TraceSummary(window_s=window_s, busy_s=busy_s,
                        stage_s=dict(stage_s), modules=modules, gaps=gaps,
                        span_names=names, n_devices=n_dev)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return paths[0]


def reduce_dir(trace_dir: str, launches: Sequence[str] = ()) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_xspace(ProfileData.from_file(find_xplane(trace_dir)),
                         launches)
