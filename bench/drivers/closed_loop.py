"""Closed-loop, write-only ingest, as fast as the system goes.

Chunk k+1 is handed to ``process()`` only once epoch k-1 is complete on
the device: the pipeline's own depth (chunk k's engine stage is dispatched
by the hand-off of chunk k+1).  Chunks are handed in until the window's
``seconds`` have passed, or until the configuration's finite stream has no
whole chunk left (a program fast enough to get there never outgrows the
configured capacities); then the last one is flushed and waited for, so
only whole chunks count.  The next chunk is taken while the device works,
never between a completion and the next hand-off.

Mix parameters: ``trace_after`` steady completions before the traced
stretch, ``trace_chunks`` completions traced (with ``--trace 1``).
"""
from __future__ import annotations

import time


def run(r) -> None:
    n = r.chunk
    r.warm_up()
    nxt = r.stream.take(n)
    trace_after = int(r.cell.mix["trace_after"])
    trace_stop = trace_after + int(r.cell.mix["trace_chunks"])
    t_end = r.begin_window() + r.seconds
    timed = 0
    completions = 0
    for _ in range(2):
        if nxt is None:
            break
        r.hand_off(nxt)
        timed += 1
        nxt = r.stream.take(n)
    while True:
        r.wait_epoch()
        completions += 1
        if completions == trace_after:
            r.start_trace()
        elif completions == trace_stop:
            r.stop_trace()
        if time.perf_counter() >= t_end or nxt is None:
            break
        r.hand_off(nxt)
        timed += 1
        nxt = r.stream.take(n)
    r.summ.flush()
    r.wait_epoch()
    r.stop_trace()
    r.end_window()
    r.extra.update(timed_chunks=timed, changes=timed * n,
                   t_last_done=r.done[max(r.done)],
                   attempted=timed * n, failed=0,
                   info={"timed_chunks": timed,
                         "stream_exhausted": nxt is None})
