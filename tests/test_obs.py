"""The in-program span recorder (repro/obs.py): nesting, the ring bound,
windowing on the ``perf_counter`` clock, the off switch, and the spans'
place in a profiler trace."""
import glob
import os
import time

import jax
import jax.numpy as jnp

from repro.obs import SpanRecorder


def test_nesting_parent_ids_and_request_ids():
    rec = SpanRecorder()
    with rec.span("a", request_id=7):
        with rec.span("b"):
            with rec.span("c", request_id=9):
                pass
        with rec.span("d"):
            pass
    with rec.span("e"):
        pass
    by = {s.name: s for s in rec.spans()}
    assert [s.name for s in rec.spans()] == ["c", "b", "d", "a", "e"]
    assert by["a"].parent_id == 0 and by["e"].parent_id == 0
    assert by["b"].parent_id == by["a"].span_id
    assert by["d"].parent_id == by["a"].span_id
    assert by["c"].parent_id == by["b"].span_id
    assert len({s.span_id for s in by.values()}) == 5
    # a child without a request id takes its parent's
    assert [by[n].request_id for n in "abcde"] == [7, 7, 9, 7, None]
    assert [s.name for s in rec.spans() if s.parent_id == 0] == ["a", "e"]
    for s in by.values():
        assert s.end_ns >= s.start_ns and s.seconds >= 0
    assert by["a"].start_ns <= by["b"].start_ns <= by["c"].start_ns
    assert by["c"].end_ns <= by["b"].end_ns <= by["a"].end_ns
    # the stack unwinds through an exception
    try:
        with rec.span("boom"):
            raise ValueError
    except ValueError:
        pass
    with rec.span("after"):
        pass
    assert rec.spans("after")[0].parent_id == 0


def test_ring_bound_counts_dropped_spans():
    rec = SpanRecorder(maxlen=8)
    for i in range(20):
        with rec.span("s", request_id=i):
            pass
    kept = rec.spans()
    assert len(kept) == 8 and rec.dropped == 12
    assert [s.request_id for s in kept] == list(range(12, 20))


def test_window_on_the_perf_counter_clock():
    rec = SpanRecorder()
    with rec.span("x.before"):
        pass
    time.sleep(0.002)
    t0 = time.perf_counter()
    with rec.span("x.inside"):
        time.sleep(0.001)
    with rec.span("y.inside"):
        pass
    t1 = time.perf_counter()
    time.sleep(0.002)
    with rec.span("x.after"):
        pass
    assert [s.name for s in rec.spans("x.", t0, t1)] == ["x.inside"]
    assert [s.name for s in rec.spans("", t0, t1)] == ["x.inside",
                                                       "y.inside"]
    assert len(rec.spans("x.")) == 3
    inside = rec.spans("x.inside")[0]
    assert 0.001 <= inside.seconds <= t1 - t0


def test_disabled_records_nothing():
    rec = SpanRecorder()
    rec.enabled = False
    with rec.span("a"):
        with rec.span("b"):
            pass
    assert rec.spans() == [] and rec.dropped == 0
    rec.enabled = True
    with rec.span("c"):
        pass
    assert [s.name for s in rec.spans()] == ["c"]


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    rec = SpanRecorder()
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("summarizer.process"):
            with rec.span("summarizer.engine"):
                f(jnp.ones(4)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"summarizer.process", "summarizer.engine"} <= names
