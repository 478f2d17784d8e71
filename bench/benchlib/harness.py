"""One run of one cell: set-up, the measured window, the check, the result.

The system under test is ``repro.core.engine.ShardedSummarizer`` with every
path left as the code chooses it on a TPU (device routing, the pipelined
sync-free dispatch, the default replica layout, the XLA probe backend) and
the write-ahead journal on.  The cell's driver (``bench/drivers``) feeds it
from the configuration's generator (``bench/generators``) and records host
times into a :class:`Run`; the reference (``reference.py``) then holds the
final epoch and the sampled reads to a host replay of what was handed in,
and one reader per metric (``bench/metrics``) turns the record into
numbers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

from benchlib import reference, registry
from benchlib.registry import Cell


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """Everything a driver records and a metric reader may read."""

    cell: Cell
    seed: int
    seconds: float
    tracing: bool
    t_start: float                      # process start, host clock
    summ: object = None
    stream: object = None
    chunks: List[list] = dataclasses.field(default_factory=list)
    done: Dict[int, float] = dataclasses.field(default_factory=dict)
    spans: List[tuple] = dataclasses.field(default_factory=list)
    t0: Optional[float] = None          # window start (first timed hand-off)
    t1: Optional[float] = None          # window end
    compiles: int = 0                   # compile events inside the window
    extra: dict = dataclasses.field(default_factory=dict)
    samples: List[tuple] = dataclasses.field(default_factory=list)
    stats: Optional[dict] = None
    trace: Optional[object] = None      # benchlib.trace.TraceSummary
    trace_dir: Optional[str] = None
    launches: List[str] = dataclasses.field(default_factory=list)
    _trace_on: bool = False
    _compile_mark: int = 0

    # ---------------------------------------------------------------- spans
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span, also written into the profiler's trace."""
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t, time.perf_counter()))

    def span_times(self, name: str, t_from: float = -math.inf,
                   t_to: float = math.inf) -> List[float]:
        return [b - a for n, a, b in self.spans
                if n == name and a >= t_from and b <= t_to]

    # ------------------------------------------------------------ the system
    @property
    def chunk(self) -> int:
        return self.summ.router_chunk

    def hand_off(self, changes: list) -> None:
        """Hand one chunk to ``process()`` (which returns before the
        device has finished it)."""
        with self.span("bench.process"):
            self.summ.process(changes)
        self.chunks.append(changes)

    def wait_epoch(self) -> int:
        """Block until the newest dispatched epoch is complete on the
        device; record when it was seen complete."""
        import jax
        with self.span("bench.wait"):
            jax.block_until_ready(self.summ.state)
        epoch = self.summ.flush_epoch
        self.done.setdefault(epoch, time.perf_counter())
        return epoch

    def warm_up(self) -> None:
        """Compile (or load) and run the route and engine stages on two
        chunks of the stream, which stay in the summary as epochs 1 and 2.
        Two, because the engine stage compiles again on its second call,
        the first whose state an engine call left; from the third on
        nothing compiles."""
        with self.span("bench.warm_up"):
            for _ in range(2):
                chunk = self.stream.take(self.chunk)
                if chunk is None:
                    raise RuntimeError("the stream is shorter than the "
                                       "warm-up")
                self.hand_off(chunk)
            self.summ.flush()
            self.wait_epoch()

    def pause(self, seconds: float) -> None:
        """Sleep until the traffic's next event.  Inside the traced stretch
        the sleep is a span of its own (``bench.idle``), so that the trace
        puts a device idle for want of work down to the traffic."""
        if not self._trace_on:
            time.sleep(seconds)
            return
        import jax
        with jax.profiler.TraceAnnotation("bench.idle"):
            time.sleep(seconds)

    def begin_window(self) -> float:
        self.t0 = time.perf_counter()
        self._compile_mark = _COMPILES[0]
        self.extra["setup_compiles"] = _COMPILES[0]
        return self.t0

    def end_window(self) -> None:
        self.t1 = time.perf_counter()
        self.compiles = _COMPILES[0] - self._compile_mark

    # ---------------------------------------------------------------- trace
    def instrument(self) -> None:
        """Host spans around the summarizer's two device stages, and the
        order they are launched in while the trace runs: the route and the
        engine stage compile under one module name, and the trace tells
        them apart only by that order.  A summarizer that no longer
        exposes a stage is an error, not a silent metric."""
        import jax
        for attr, stage in (("_route", "route"), ("_engine", "engine")):
            fn = stage_callable(self.summ, attr)

            def launch(*args, _fn=fn, _stage=stage, **kwargs):
                if self._trace_on:
                    self.launches.append(_stage)
                with jax.profiler.TraceAnnotation(f"bench.{_stage}_launch"):
                    return _fn(*args, **kwargs)

            setattr(self.summ, attr, launch)

    def start_trace(self) -> None:
        import jax
        if not self.tracing or self._trace_on or self.trace_dir is not None:
            return
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # module-level device events only: the engine's loops would emit
        # millions of op events
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
        try:
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        except (RuntimeError, ValueError) as e:    # a runtime without it
            print(f"trace: {e!r}; tracing with the default mode",
                  file=sys.stderr)
            opts.advanced_configuration = {}
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._trace_on = True

    def stop_trace(self) -> None:
        import jax
        if not self._trace_on:
            return
        jax.profiler.stop_trace()
        self._trace_on = False


def stage_callable(summ, attr: str):
    """The summarizer's device stage ``attr`` (``_route``/``_engine``),
    which the harness wraps and the fault checks replace."""
    fn = getattr(summ, attr, None)
    if not callable(fn):
        raise AttributeError(f"{type(summ).__name__} has no callable stage "
                             f"{attr!r}: the harness cannot time or fault it")
    return fn


_COMPILES = [0]


def _count_compiles() -> None:
    import jax
    if getattr(_count_compiles, "on", False):
        return

    def listen(event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            _COMPILES[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    _count_compiles.on = True


def require_chips(chips: int) -> list:
    """The local TPU devices, or :class:`NoAccelerator`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices


def enable_cache() -> str:
    """JAX's persistent compile cache at the program's fixed directory
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for
    every program however quickly it compiles."""
    import jax
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_summarizer(config: dict, chips: int, checkpoint_dir: str):
    from repro.core.engine import EngineConfig, ShardedSummarizer
    from repro.dist.router import default_replica_exec
    from repro.launch.mesh import make_engine_mesh

    cfg = EngineConfig(**config["engine"])
    summ = ShardedSummarizer(cfg, mesh=make_engine_mesh(chips),
                             n_shards=int(config["n_shards"]),
                             router_chunk=int(config["router_chunk"]),
                             checkpoint_dir=checkpoint_dir)
    on_chip = (summ.routing == "device" and summ.pipeline and summ.sync_free
               and summ.trial_backend == "xla"
               and summ.replica_exec == default_replica_exec())
    if not on_chip:
        raise RuntimeError(
            f"the summarizer did not take the paths it takes on a TPU: "
            f"routing={summ.routing} pipeline={summ.pipeline} "
            f"sync_free={summ.sync_free} trial_backend={summ.trial_backend} "
            f"replica_exec={summ.replica_exec}")
    return summ


def peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


def _parts(summ) -> list:
    """Per-shard output in caller labels, as plain containers."""
    out = summ.materialize()
    return [({sid: set(m) for sid, m in p.supernodes.items()},
             set(p.superedges), set(p.c_plus), set(p.c_minus))
            for p in out.shards]


def check(run: Run) -> Dict[str, dict]:
    """Every number compared, with its limit (all exact: limit 0)."""
    numbers: Dict[str, int] = {}
    try:
        got, run.extra["reference"] = reference.check_summary(
            _parts(run.summ), run.summ.phi,
            [c for ch in run.chunks for c in ch], run.summ.n_shards)
        numbers.update(got)
    except Exception as e:                      # a broken state is a result
        print(f"check: the final epoch could not be read: {e!r}",
              file=sys.stderr)
        numbers["final_unreadable"] = 1
    if "reads" in run.extra:
        got = reference.check_reads(run.samples, run.chunks)
        numbers["reads_wrong"] = got["reads_wrong"]
        numbers["reads_failed"] = run.extra.get("reads_failed", 0)
        numbers["reads_unchecked"] = (run.extra["reads_served"]
                                      - got["reads_checked"])
    if run.stats is None:
        numbers["stats_unreadable"] = 1
    return {k: {"value": int(v), "limit": 0} for k, v in numbers.items()}


def run_cell(root: Path, name: str, seed: int, seconds: float,
             trace: bool, *, t_start: Optional[float] = None,
             require_tpu: bool = True, bench: Path = registry.BENCH,
             cache: bool = True, chips: Optional[int] = None,
             config_overrides: Optional[dict] = None,
             mix_overrides: Optional[dict] = None,
             patch=None) -> tuple:
    """Run cell ``name`` once; return the result line (a dict, ``checks``
    last) and a dict of further readings for standard error.

    ``require_tpu=False``, ``cache=False``, ``chips``, ``config_overrides``
    (merged into the configuration's groups and top level),
    ``mix_overrides`` and ``patch`` (called with the Run once the
    summarizer exists, to plant a fault) serve the tests, which run the
    same phases on the CPU at a small size, and the calibration tools
    ``bench/control.py`` and ``bench/sweep.py``."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = registry.load_cell(root, name, bench)
    if config_overrides:
        cfg = dict(cell.config)
        for k, v in config_overrides.items():
            cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
        cell = dataclasses.replace(cell, config=cfg)
    if mix_overrides:
        cell = dataclasses.replace(cell, mix={**cell.mix, **mix_overrides})
    if chips is not None:
        cell = dataclasses.replace(cell, chips=chips)
    import jax
    devices = (require_chips(cell.chips) if require_tpu
               else jax.devices()[:cell.chips])
    cache = enable_cache() if cache else None
    _count_compiles()
    gen = registry.load_module("generators",
                               cell.config["stream"]["generator"], bench)
    driver = registry.load_module("drivers", cell.mix["driver"], bench)
    workdir = tempfile.mkdtemp(prefix="bench_run_")
    run = Run(cell=cell, seed=seed, seconds=seconds, tracing=trace,
              t_start=t_start)
    try:
        t_setup = time.perf_counter()
        with run.span("bench.stream"):
            run.stream = gen.Stream(cell.config["stream"], seed)
        with run.span("bench.build"):
            run.summ = build_summarizer(cell.config, cell.chips,
                                        os.path.join(workdir, "journal"))
        run.instrument()
        if patch is not None:
            patch(run)
        driver.run(run)
        peak = peak_bytes(devices)
        try:
            run.stats = run.summ.stats()
        except RuntimeError as e:       # e.g. a dropped change: a result
            print(f"check: stats() raised {e!r}", file=sys.stderr)
        if run.trace_dir is not None:
            from benchlib import trace as trace_mod
            run.trace = trace_mod.reduce_dir(run.trace_dir, run.launches)
        checks = check(run)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        if run.trace_dir is not None:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = registry.load_module("metrics", m["name"], bench).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    result = {"correct": correct,
              "attempted": int(run.extra.get("attempted", 0)),
              "failed": int(run.extra.get("failed", 0)),
              "metrics": metrics, "device": device}
    if run.trace is not None:
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = checks
    setup = {"start": t_setup - t_start}
    for name, a, b in run.spans:
        if run.t0 is not None and b <= run.t0:
            setup[name] = setup.get(name, 0.0) + (b - a)
    info = {"cache": cache, "stats": run.stats, "setup_s": setup,
            "setup_compiles": run.extra.get("setup_compiles"),
            "window_compiles": run.compiles,
            "reference": run.extra.get("reference"),
            "chunks_handed": len(run.chunks), **run.extra.get("info", {})}
    return result, info
