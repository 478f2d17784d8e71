"""Pallas TPU kernel: blocked CSR segment-reduce (the graph hot loop).

This is the bulk compute primitive behind (a) `summary_spmm` — neighborhood
aggregation directly on the summarized representation (the paper's
"Queryable" property as a compute kernel), (b) GNN message passing for the
assigned GNN architectures, and (c) the RecSys embedding-bag.

TPU adaptation (DESIGN.md): instead of GPU-style atomics/scatter, edges are
pre-sorted by destination row and the kernel walks one *row block* per grid
step, accumulating gathered source rows into a VMEM-resident output tile.
The TPU grid is sequential, so no cross-step races exist; the feature axis
is tiled to the 128-lane VPU/MXU width.

Layout:
  senders  int32[E_pad]   source node per edge (sorted by destination row)
  row_off  int32[NB + 1]  CSR offsets of each row *block* into senders
  dst_loc  int32[E_pad]   destination row within its block (0..BN-1)
  x        f32[N, F]      dense features (HBM; rows DMA'd on demand)
  out      f32[N, F]      segment-reduced output

`reduce` in {"sum", "min", "max"} (min/max power the min-hash signatures).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INIT = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}


def _kernel(row_off_ref, senders_ref, dst_loc_ref, x_ref, out_ref, *,
            bn: int, bf: int, reduce: str, e_cap: int):
    ib = pl.program_id(0)      # row-block index
    # fj = pl.program_id(1)    # feature-block index (implicit via BlockSpec)
    start = row_off_ref[ib]
    stop = row_off_ref[ib + 1]

    acc0 = jnp.full((bn, bf), _INIT[reduce], dtype=jnp.float32)
    cnt0 = jnp.zeros((bn, 1), dtype=jnp.int32)

    def body(e, carry):
        acc, cnt = carry
        src = senders_ref[e]
        loc = dst_loc_ref[e]
        row = x_ref[pl.ds(src, 1), :]  # [1, bf]
        onehot = (jax.lax.iota(jnp.int32, bn) == loc)[:, None]  # [bn, 1]
        cnt = cnt + onehot.astype(jnp.int32)
        if reduce == "sum":
            return acc + jnp.where(onehot, row, 0.0), cnt
        upd = jnp.where(onehot, row, _INIT[reduce])
        if reduce == "min":
            return jnp.minimum(acc, upd), cnt
        return jnp.maximum(acc, upd), cnt

    acc, cnt = jax.lax.fori_loop(start, stop, body, (acc0, cnt0))
    if reduce != "sum":
        # zero EMPTY rows only (rows with zero in-edges keep the ±inf
        # init); an isfinite mask would also clobber ±inf inputs, which
        # must flow through min/max exactly as segment_reduce_ref keeps
        # them
        acc = jnp.where(cnt > 0, acc, 0.0)
    out_ref[...] = acc.astype(out_ref.dtype)


def csr_segment_reduce(senders: jax.Array, row_off: jax.Array,
                       dst_loc: jax.Array, x: jax.Array, n_out: int,
                       *, bn: int = 128, bf: int = 128,
                       reduce: str = "sum", interpret: bool = False,
                       ) -> jax.Array:
    """Blocked segment-reduce: out[r] = reduce_{e: dst[e]==r} x[senders[e]].

    Callers prepare the blocked CSR layout with :func:`build_blocked_csr`.
    """
    n_pad = ((n_out + bn - 1) // bn) * bn
    f = x.shape[1]
    f_pad = ((f + bf - 1) // bf) * bf
    if f_pad != f:
        x = jnp.pad(x, ((0, 0), (0, f_pad - f)))
    nb = n_pad // bn

    out = pl.pallas_call(
        functools.partial(_kernel, bn=bn, bf=bf, reduce=reduce,
                          e_cap=senders.shape[0]),
        grid=(nb, f_pad // bf),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),             # row_off (small)
            pl.BlockSpec(memory_space=pl.ANY),             # senders
            pl.BlockSpec(memory_space=pl.ANY),             # dst_loc
            pl.BlockSpec((x.shape[0], bf), lambda i, j: (0, j)),  # x feature tile
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), x.dtype),
        interpret=interpret,
    )(row_off, senders, dst_loc, x)
    return out[:n_out, :f]


def build_blocked_csr(receivers, n_out: int, bn: int = 128):
    """Host/XLA-side layout pass: sort edges by destination row block.

    Returns (order, row_off, dst_loc): ``order`` permutes edge arrays into
    block order, ``row_off[i]`` is the first edge of row-block i and
    ``dst_loc`` the within-block destination row.
    """
    receivers = jnp.asarray(receivers, jnp.int32)
    order = jnp.argsort(receivers)
    sorted_r = receivers[order]
    nb = (n_out + bn - 1) // bn
    blk = sorted_r // bn
    row_off = jnp.searchsorted(blk, jnp.arange(nb + 1, dtype=jnp.int32)).astype(jnp.int32)
    dst_loc = (sorted_r % bn).astype(jnp.int32)
    return order, row_off, dst_loc
