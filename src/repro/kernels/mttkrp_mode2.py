"""Pallas TPU kernel — SPARTan mode-2 MTTKRP, compact compute stage.

Computes  A[k] = (Y_k^T H) * W(k,:)  for the kept columns only (paper Fig. 3);
the J-space sum is a separate memory-bound stage
(`spartan.mode2_scatter`). The C x R result per subject stays in VMEM;
C is tiled for large kept-column counts. H (R x R) is small and replicated to
every grid step (the paper's "size imbalance" property).

``col_mask`` [K,C] zeroes rows for padded columns inside the kernel (so the
downstream scatter of slot-0 column ids stays harmless); ``subject_mask`` [K]
is folded into W(k,:) — both make the kernel drop-in equal to
``spartan.mode2_bucket_compact``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import accum_dtype, fold_subject_mask

__all__ = ["mode2_compact_pallas"]


def _kernel(yc_ref, h_ref, wb_ref, cm_ref, out_ref, *, acc):
    # yc [1, R, bc]; h [R, R]; wb [1, 1, R]; cm [1, 1, bc]; out [1, bc, R]
    ytH = jnp.dot(yc_ref[0].T, h_ref[...], preferred_element_type=acc)
    out_ref[0] = (ytH * wb_ref[0, 0].astype(acc)[None, :]
                  * cm_ref[0, 0].astype(acc)[:, None])


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def mode2_compact_pallas(
    Yc: jax.Array,
    H: jax.Array,
    Wb: jax.Array,
    col_mask: Optional[jax.Array] = None,
    subject_mask: Optional[jax.Array] = None,
    *,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Yc [K,R,C], H [R,R], Wb [K,R] -> A [K,C,R]. Optional ``col_mask``
    [K,C] / ``subject_mask`` [K] zero padded columns / subjects."""
    K, R, C = Yc.shape
    acc = accum_dtype(Yc)
    if K == 0:
        return jnp.zeros((K, C, R), acc)
    Wb = fold_subject_mask(Wb, subject_mask)
    if col_mask is None:
        col_mask = jnp.ones((K, C), jnp.float32)
    bc = min(block_c, C)
    nc = pl.cdiv(C, bc)
    C_pad = nc * bc
    if C % bc:
        Yc = jnp.pad(Yc, ((0, 0), (0, 0), (0, C_pad - C)))
        col_mask = jnp.pad(col_mask, ((0, 0), (0, C_pad - C)))
    grid = (K, nc)
    out = pl.pallas_call(
        functools.partial(_kernel, acc=acc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, R, bc), lambda k, c: (k, 0, c)),
            pl.BlockSpec((R, R), lambda k, c: (0, 0)),
            pl.BlockSpec((1, 1, R), lambda k, c: (k, 0, 0)),
            pl.BlockSpec((1, 1, bc), lambda k, c: (k, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, bc, R), lambda k, c: (k, c, 0)),
        out_shape=jax.ShapeDtypeStruct((K, C_pad, R), acc),
        interpret=interpret,
    )(Yc, H, Wb[:, None, :], col_mask[:, None, :])
    return out[:, :C, :]
