"""Open-loop serving: writes at a fixed rate, reads due on their own clock.

Writes arrive as a Poisson stream of ``write_rate`` changes per second; a
chunk is handed to ``process()`` once its last change has arrived, and
``flush()`` is never called: when a change becomes visible is the
program's own policy (chunk k's engine stage is dispatched by the hand-off
of chunk k+1).  Arrivals continue past the window until every change that
arrived inside it is visible.  An epoch's completion is observed with
``is_ready()`` on the state it leaves, or waited for just before the next
hand-off would donate that state.

Reads arrive as a Poisson stream of ``reads_per_write * write_rate`` per
second; each is a ``neighbors``, ``degree`` or ``has_edge`` read, in the
shares ``read_shares``, keyed by Zipf(``zipf_theta``) ranks over the labels
visible at the view's epoch (rank 0 = the first label the stream showed).
Due reads are served from a fresh ``query()`` view in batches of exactly
``read_batch`` (padded with the batch's first key), one kind at a time.
Every read due inside the window is checked against the replay of its
view's epoch.

Every seed sees the same multiset of gaps between arrivals, of read kinds
and of ranks' uniforms, in its own order.  With ``--trace 1`` the trace
covers the hand-offs ``trace_from_handoff`` to ``trace_from_handoff +
trace_handoffs`` (one chunk cycle: an engine stage and the reads beside
it).
"""
from __future__ import annotations

import math
import time

import numpy as np

KINDS = ("neighbors", "degree", "has_edge")


def exp_gaps(count: int, rate: float, rng) -> np.ndarray:
    """``count`` exponential gaps of mean ``1 / rate``: the fixed set of
    quantiles, in a seeded order."""
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    return gaps


def first_seen(chunks) -> tuple:
    """Labels in order of first appearance, and for every epoch ``e`` how
    many of them the chunks before it showed (``chunks[:e]``)."""
    order, seen, visible = [], set(), [0]
    for ch in chunks:
        for u, v, _ in ch:
            for x in (u, v):
                if x not in seen:
                    seen.add(x)
                    order.append(x)
        visible.append(len(order))
    return order, visible


class ZipfRanks:
    """Zipf(theta) ranks over ``n`` items from uniforms, exactly (inverse
    CDF), for any ``n`` the views show."""

    def __init__(self, theta: float) -> None:
        self.theta = theta
        self._cdf = {}

    def __call__(self, n: int, u: np.ndarray) -> np.ndarray:
        if n not in self._cdf:
            w = np.arange(1, n + 1, dtype=np.float64) ** -self.theta
            self._cdf[n] = np.cumsum(w) / w.sum()
        return np.minimum(np.searchsorted(self._cdf[n], u, side="right"),
                          n - 1)


def _read(view, kind: str, keys):
    if kind == "neighbors":
        return view.neighbors_batch(keys)
    if kind == "degree":
        return view.degree_batch(keys)
    return view.has_edge_batch(keys)


def run(r) -> None:
    mix, n = r.cell.mix, r.chunk
    rate = float(mix["write_rate"])
    batch = int(mix["read_batch"])
    rng = np.random.default_rng(np.random.SeedSequence([r.seed % 2 ** 64, 7]))

    # ---- the schedule, fixed before the window ---------------------------
    n_sched = (math.ceil(rate * r.seconds * 1.25 / n) + 2) * n
    arrive = np.cumsum(exp_gaps(n_sched, rate, rng))
    n_window = int(np.searchsorted(arrive, r.seconds, side="left"))
    # the chunk after the last window arrival's chunk dispatches its engine
    n_chunks = (n_window - 1) // n + 2
    if n_chunks * n > n_sched:
        raise RuntimeError("the write schedule is too short for the window")
    read_rate = rate * float(mix["reads_per_write"])
    horizon = float(arrive[n_chunks * n - 1]) + 60.0
    n_reads = int(math.ceil(read_rate * horizon))
    due = np.cumsum(exp_gaps(n_reads, read_rate, rng))
    shares = np.array([float(mix["read_shares"][k]) for k in KINDS])
    counts = np.floor(shares / shares.sum() * n_reads).astype(int)
    counts[0] += n_reads - counts.sum()
    kind_of = np.repeat(np.arange(len(KINDS)), counts)
    rng.shuffle(kind_of)
    u1, u2 = rng.random(n_reads), rng.random(n_reads)
    zipf = ZipfRanks(float(mix["zipf_theta"]))

    # ---- set-up: warm-up chunks, the stream, the read kernels -----------
    r.warm_up()
    n_warm = len(r.chunks)          # timed chunk j is held by epoch j+n_warm+1
    need_epoch = n_chunks - 1 + n_warm
    chunks = [r.stream.take(n) for _ in range(n_chunks)]
    if chunks[-1] is None:
        raise RuntimeError("the configuration's stream is too short for "
                           "the window at this write rate")
    order, visible = first_seen(r.chunks + chunks)

    def keys_for(kind, idx, n_vis):
        a = [order[i] for i in zipf(n_vis, u1[idx])]
        if kind != "has_edge":
            return a
        return list(zip(a, [order[i] for i in zipf(n_vis, u2[idx])]))

    view = r.summ.query()
    warm_failed = 0                 # warm-up reads refused
    with r.span("bench.warm_up"):
        for kind in KINDS:
            keys = keys_for(kind, np.arange(batch), visible[view.epoch])
            try:
                _read(view, kind, keys)
            except LookupError:
                warm_failed += batch

    # ---- the window ------------------------------------------------------
    t0 = r.begin_window()
    arr_t = t0 + arrive
    due_t = t0 + due
    hand_at = arr_t[np.arange(1, n_chunks + 1) * n - 1]
    answered = np.full(n_reads, np.nan)
    late = []
    queues = {k: [] for k in range(len(KINDS))}
    c = ir = failed = served = 0
    pending = None                  # (epoch, a leaf of the state it leaves)
    view = None
    trace_from = int(mix["trace_from_handoff"])
    trace_to = trace_from + int(mix["trace_handoffs"])
    n_win_reads = int(np.searchsorted(due, r.seconds, side="left"))
    while True:
        now = time.perf_counter()
        if pending is not None and pending[1].is_ready():
            r.done.setdefault(pending[0], now)
            pending = None
        finishing = need_epoch in r.done
        if finishing:
            # every window change is visible: answer the window's reads
            # still queued, take no new ones, then stop
            for q in queues.values():
                q[:] = [i for i in q if i < n_win_reads]
            if ir >= n_win_reads and not any(queues.values()):
                break
        if c < n_chunks and now >= hand_at[c]:
            if pending is not None:             # the hand-off donates it
                with r.span("bench.wait"):
                    pending[1].block_until_ready()
                r.done.setdefault(pending[0], time.perf_counter())
                pending = None
            # the device is idle here: the traced stretch holds whole
            # executions only
            if c == trace_from:
                r.start_trace()
            elif c == trace_to:
                r.stop_trace()
            late.append(time.perf_counter() - hand_at[c])
            r.hand_off(chunks[c])
            c += 1
            if r.summ.flush_epoch not in r.done:
                pending = (r.summ.flush_epoch, r.summ.state.phi)
            view = None
            continue
        while ir < n_reads and due_t[ir] <= now and not (
                finishing and ir >= n_win_reads):
            queues[int(kind_of[ir])].append(ir)
            ir += 1
        busy = False
        for k, q in queues.items():
            if not q:
                continue
            if view is None:
                view = r.summ.query()
            idx, q[:] = q[:batch], q[batch:]
            kind = KINDS[k]
            keys = keys_for(kind, np.asarray(idx), visible[view.epoch])
            padded = keys + [keys[0]] * (batch - len(keys))
            try:
                with r.span("bench.read." + kind):
                    got = _read(view, kind, padded)[:len(keys)]
            except LookupError:
                failed += len(idx)
                got = None
            answered[idx] = time.perf_counter()
            inside = [j for j, i in enumerate(idx) if i < n_win_reads]
            if got is not None and inside:
                r.samples.append((view.epoch, kind,
                                  [keys[j] for j in inside],
                                  [got[j] for j in inside]))
                served += len(inside)
            busy = True
        if busy:
            continue
        nxt = min(hand_at[c] if c < n_chunks else math.inf,
                  due_t[ir] if ir < n_reads else math.inf,
                  now + 0.001)
        r.pause(max(0.0, nxt - time.perf_counter()))
    r.stop_trace()
    r.end_window()

    epoch_done = np.array([r.done[j // n + n_warm + 1]
                           for j in range(n_window)])
    # per chunk: its epoch's completion after its last change arrived;
    # flat below the knee, growing by a chunk's overload above it
    backlog = [r.done[k + n_warm + 1] - hand_at[k] for k in range(n_chunks)
               if k + n_warm + 1 in r.done]
    in_window = np.arange(n_reads) < n_win_reads
    unanswered = int(np.isnan(answered[in_window]).sum())
    r.extra.update(
        reads=True, reads_served=served,
        reads_failed=failed + unanswered + warm_failed,
        visible_s=epoch_done - arr_t[:n_window],
        attempted=n_window + int(in_window.sum()),
        failed=failed + unanswered,
        info={"window_changes": n_window, "window_reads":
              int(in_window.sum()), "timed_chunks": c,
              "handoff_late_p95_s": float(np.percentile(late, 95)),
              "read_p95_s": float(np.nanpercentile(
                  answered[in_window] - due_t[in_window], 95)),
              "chunk_full_to_visible_s": backlog})
