"""Segment sums over sorted rows, each accumulated from its own terms.

``sorted_segment_sum(x, starts, ends)`` sums the rows of ``x`` [N, R],
sorted by segment, into [J, R]; a segment with no rows gets exact zeros.
The segments' boundaries (``starts`` per row, ``ends`` per segment) depend
only on the sorted ids, so :func:`segment_bounds` builds them once on the
host and the device never sorts or searches. Every segment's sum is
accumulated from its own rows only, so its rounding error is bounded by
c * eps * sum|own terms|, with c of the order of the tile size below. A
difference of one running total over all rows, as ``cumsum`` followed by
``diff`` computes, is bounded instead by eps times the running total,
which for a rare segment after a long run of heavy ones is many times the
segment's own mass.

On a TPU the sum is a Pallas kernel, ``segmented_cumsum_pallas``: a
segmented prefix sum over the rows, read at each segment's last row. It
walks the rows in order, in blocks of ``block`` rows and each block in
tiles of ``tile``; the partial sum of the segment left open at a tile's end
is carried to the next tile, and across blocks in VMEM scratch. Within a
tile the segmented prefix is one f32 matmul at ``HIGHEST`` precision: a
[T, T] 0/1 mask, set where row j lies in row i's segment and j <= i, times
the [T, R] tile, so every product is exact and only the f32 sums round.
The kernel reads and writes each row once, in place of its input.

Elsewhere (CPU, GPU) the same sums come from ``jax.ops.segment_sum`` over
the rows' segment ids, read back from ``ends``, which adds each segment's
rows in order.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["segment_bounds", "segment_starts", "segmented_cumsum_pallas",
           "sorted_segment_sum"]

_TILE = 128
_BLOCK = 2048


def sorted_segment_sum(x: jax.Array, starts: jax.Array,
                       ends: jax.Array) -> jax.Array:
    """Sum the rows x [S * N, R] into [J, R] (see the module doc).

    ``x`` holds S runs of N rows, each run sorted by segment; ``starts``
    bool [S * N] is set on each segment's first row (a run's row 0
    included) and ``ends`` int [S, J] holds one past each segment's last
    row within its run (:func:`segment_bounds` builds both). Rows of a run
    past its ``ends[s, -1]`` belong to no segment and are dropped. The sums
    of the S runs are added. The Pallas kernel on a TPU, ``segment_sum``
    elsewhere."""
    S, J = ends.shape
    N = x.shape[0] // S
    if not _on_tpu():
        # segment of each row, the runs' segments numbered apart; a run's
        # rows past its last segment fall in its spare segment J
        local = jax.vmap(lambda e: jnp.searchsorted(
            e, jnp.arange(N, dtype=e.dtype), side="right"))(ends)
        ids = (local + (J + 1) * jnp.arange(S, dtype=ends.dtype)[:, None])
        out = jax.ops.segment_sum(x, ids.reshape(-1), S * (J + 1),
                                  indices_are_sorted=True)
        return out.reshape(S, J + 1, -1)[:, :J].sum(0)
    y = segmented_cumsum_pallas(x, starts).reshape(S, N, -1)
    begins = jnp.concatenate([jnp.zeros((S, 1), ends.dtype), ends[:, :-1]], 1)
    last = jnp.take_along_axis(y, jnp.maximum(ends - 1, 0)[..., None], axis=1)
    return jnp.where((ends > begins)[..., None], last, 0).sum(0)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def segment_starts(ids: np.ndarray) -> np.ndarray:
    """bool [..., N]: set on row 0 and wherever sorted ``ids`` change along
    the last axis. Host-side (NumPy), like :func:`segment_bounds`."""
    ids = np.asarray(ids)
    starts = np.ones(ids.shape, bool)
    starts[..., 1:] = ids[..., 1:] != ids[..., :-1]
    return starts


def segment_bounds(ids: np.ndarray, num_segments: int):
    """Host-side boundaries of S runs of sorted segment ids [S, N] for
    :func:`sorted_segment_sum`: ``(starts`` bool [S * N], ``ends`` int32
    [S, num_segments])``. Ids of ``num_segments`` or more mark rows that
    belong to no segment; they must sort after every real id."""
    ids = np.asarray(ids)
    stops = np.arange(1, num_segments + 1)
    ends = np.stack([np.searchsorted(run, stops) for run in ids])
    return segment_starts(ids).reshape(-1), ends.astype(np.int32)


def _segscan_kernel(flags_ref, x_ref, out_ref, carry_ref, *, tile: int,
                    n_valid: int):
    b = pl.program_id(0)
    block = x_ref.shape[0]

    @pl.when(b == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    i = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)   # output row
    j = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)   # input row
    lower = j <= i
    row = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    carry = carry_ref[...]                                   # [1, R]
    for s in range(block // tile):
        lo = s * tile
        x = x_ref[lo:lo + tile, :]
        # rows past the end of the array (the last block's overhang) read
        # unspecified values: zero them before they meet the mask
        x = jnp.where(b * block + lo + row < n_valid, x, jnp.zeros_like(x))
        f = flags_ref[s:s + 1, :]                            # [1, T]
        # start of row i's segment inside the tile, -1 where it opened in an
        # earlier tile
        first = jnp.max(jnp.where(lower & (f != 0), j, -1), axis=1,
                        keepdims=True)                       # [T, 1]
        same = lower & (j >= first)
        # 0/1 times f32 rows: every product is exact, the sums are f32
        y = jnp.dot(same.astype(jnp.float32), x,
                    precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        y = y + jnp.where(first < 0, carry, jnp.zeros_like(carry))
        out_ref[lo:lo + tile, :] = y
        carry = y[tile - 1:tile, :]
    carry_ref[...] = carry


@functools.partial(jax.jit, static_argnames=("tile", "block", "interpret"))
def segmented_cumsum_pallas(x: jax.Array, starts: jax.Array, *,
                            tile: int = _TILE, block: int = _BLOCK,
                            interpret: Optional[bool] = None) -> jax.Array:
    """The Pallas segmented prefix sum: x f32 [N, R], starts bool [N] ->
    f32 [N, R], y[i] the sum of the rows of i's segment up to row i.

    The grid runs over blocks of ``block`` rows in order (the carry makes
    it sequential); ``block`` is a multiple of ``tile``, and is cut to the
    array where that is shorter. The output takes the input's buffer."""
    N, R = x.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block = min(block, pl.cdiv(N, tile) * tile)
    nb = pl.cdiv(N, block)
    flags = jnp.pad(starts.astype(jnp.int32), (0, nb * block - N))
    flags = flags.reshape(nb * block // tile, tile)
    return pl.pallas_call(
        functools.partial(_segscan_kernel, tile=tile, n_valid=N),
        grid=(nb,),
        in_specs=[pl.BlockSpec((block // tile, tile), lambda b: (b, 0)),
                  pl.BlockSpec((block, R), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((block, R), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((N, R), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, R), x.dtype)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(flags, x)
