"""Pallas TPU kernel — SPARTan mode-1 MTTKRP.

Computes  M1 = sum_k (Y_k V) * W(k,:)  with the per-k R x C slice and the
gathered C x R V-rows streamed HBM -> VMEM, the R x C @ C x R product on the
MXU, the row-wise Hadamard with W(k,:) on the VPU, and the R x R accumulator
resident in the output VMEM window across the whole grid (classic revisited-
window reduction). Optionally tiles C for large kept-column counts.

Two entry points:

* :func:`mode1_pallas` — full gather+matmul path. ``subject_mask`` is folded
  into W(k,:) (the Hadamard is linear in W, so masking W masks the subject's
  whole contribution exactly).
* :func:`mode1_reuse_pallas` — the ``mode1_reuse`` path: Y_k V ([K,R,R]) is
  already cached from the Procrustes step (Y_k V = Q_k^T (X_k V)), so only
  the Hadamard + subject reduction remain (pure VPU work).

Alignment: best MXU utilization wants R padded to 8 (sublane) and C to 128
(lane); the bucketizer's ``col_align=128`` produces that. Works (slower) for
odd shapes too; interpret=True is bit-exact on CPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import accum_dtype, fold_subject_mask

__all__ = ["mode1_pallas", "mode1_reuse_pallas"]


def _kernel(yc_ref, vg_ref, wb_ref, out_ref, *, acc):
    k = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when((k == 0) & (c == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    yv = jnp.dot(yc_ref[0], vg_ref[0], preferred_element_type=acc)  # [R, R]
    out_ref[...] += yv * wb_ref[0, 0].astype(acc)[None, :]


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def mode1_pallas(
    Yc: jax.Array,
    Vg: jax.Array,
    Wb: jax.Array,
    subject_mask: Optional[jax.Array] = None,
    *,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Yc [K,R,C], Vg [K,C,R], Wb [K,R] -> [R,R]. ``subject_mask`` [K] (1.0 =
    real subject) is folded into Wb so padded subjects contribute nothing."""
    K, R, C = Yc.shape
    acc = accum_dtype(Yc)
    if K == 0:
        return jnp.zeros((R, R), acc)
    Wb = fold_subject_mask(Wb, subject_mask)
    bc = min(block_c, C)
    nc = pl.cdiv(C, bc)
    if C % bc:  # zero-pad partial tile (zero columns contribute nothing)
        pad = nc * bc - C
        Yc = jnp.pad(Yc, ((0, 0), (0, 0), (0, pad)))
        Vg = jnp.pad(Vg, ((0, 0), (0, pad), (0, 0)))
    grid = (K, nc)
    return pl.pallas_call(
        functools.partial(_kernel, acc=acc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, R, bc), lambda k, c: (k, 0, c)),
            pl.BlockSpec((1, bc, R), lambda k, c: (k, c, 0)),
            pl.BlockSpec((1, 1, R), lambda k, c: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((R, R), lambda k, c: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, R), acc),
        interpret=interpret,
    )(Yc, Vg, Wb[:, None, :])


def _reuse_kernel(ykv_ref, wb_ref, out_ref, *, acc):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ykv = ykv_ref[0].astype(acc)
    out_ref[...] += ykv * wb_ref[0, 0].astype(acc)[None, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mode1_reuse_pallas(
    YkV: jax.Array,
    Wb: jax.Array,
    subject_mask: Optional[jax.Array] = None,
    *,
    interpret: bool = False,
) -> jax.Array:
    """YkV [K,R,R] (= Y_k V, cached), Wb [K,R] -> [R,R]: Hadamard with W(k,:)
    plus the subject-axis reduction only — the matmul was paid upstream."""
    K, R, _ = YkV.shape
    acc = accum_dtype(YkV)
    if K == 0:
        return jnp.zeros((R, R), acc)
    Wb = fold_subject_mask(Wb, subject_mask)
    return pl.pallas_call(
        functools.partial(_reuse_kernel, acc=acc),
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, R, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda k: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((R, R), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, R), acc),
        interpret=interpret,
    )(YkV, Wb[:, None, :])
