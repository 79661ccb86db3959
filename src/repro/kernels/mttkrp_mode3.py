"""Pallas TPU kernel — SPARTan mode-3 MTTKRP.

Computes  M3(k,:) = coldot(H, Y_k V): the R x R product Y_k V is formed on the
MXU (tiled over C), then contracted column-wise against H on the VPU. One
output row per subject. The C-tiling accumulates the R x R partial product in
a VMEM scratch buffer; the coldot runs on the final tile.

Two entry points mirror mode-1: :func:`mode3_pallas` (full gather+matmul) and
:func:`mode3_reuse_pallas` (Y_k V pre-computed — only the coldot remains).
``subject_mask`` zeroes the output rows of padded subjects, matching
``spartan.mode3_bucket``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import accum_dtype

__all__ = ["mode3_pallas", "mode3_reuse_pallas"]


def _mask_rows(out: jax.Array, subject_mask: Optional[jax.Array]) -> jax.Array:
    if subject_mask is None:
        return out
    return out * subject_mask[:, None].astype(out.dtype)


def _kernel(yc_ref, vg_ref, h_ref, out_ref, acc_ref, *, nc: int, acc):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(yc_ref[0], vg_ref[0], preferred_element_type=acc)

    @pl.when(c == nc - 1)
    def _fin():
        out_ref[0, 0] = jnp.sum(h_ref[...].astype(acc) * acc_ref[...], axis=0)  # coldot


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def mode3_pallas(
    Yc: jax.Array,
    Vg: jax.Array,
    H: jax.Array,
    subject_mask: Optional[jax.Array] = None,
    *,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Yc [K,R,C], Vg [K,C,R], H [R,R] -> [K,R]. ``subject_mask`` [K] zeroes
    rows of padded subjects."""
    K, R, C = Yc.shape
    acc = accum_dtype(Yc)
    if K == 0:
        return jnp.zeros((K, R), acc)
    bc = min(block_c, C)
    nc = pl.cdiv(C, bc)
    if C % bc:  # zero-pad partial tile
        pad = nc * bc - C
        Yc = jnp.pad(Yc, ((0, 0), (0, 0), (0, pad)))
        Vg = jnp.pad(Vg, ((0, 0), (0, pad), (0, 0)))
    grid = (K, nc)
    out = pl.pallas_call(
        functools.partial(_kernel, nc=nc, acc=acc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, R, bc), lambda k, c: (k, 0, c)),
            pl.BlockSpec((1, bc, R), lambda k, c: (k, c, 0)),
            pl.BlockSpec((R, R), lambda k, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, R), lambda k, c: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, 1, R), acc),
        scratch_shapes=[pltpu.VMEM((R, R), acc)],
        interpret=interpret,
    )(Yc, Vg, H)
    return _mask_rows(out[:, 0], subject_mask)


def _reuse_kernel(ykv_ref, h_ref, out_ref, *, acc):
    ykv = ykv_ref[0].astype(acc)
    out_ref[0, 0] = jnp.sum(h_ref[...].astype(acc) * ykv, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mode3_reuse_pallas(
    YkV: jax.Array,
    H: jax.Array,
    subject_mask: Optional[jax.Array] = None,
    *,
    interpret: bool = False,
) -> jax.Array:
    """YkV [K,R,R] (= Y_k V, cached), H [R,R] -> [K,R]: per-subject coldot
    only — the matmul was paid upstream."""
    K, R, _ = YkV.shape
    acc = accum_dtype(YkV)
    if K == 0:
        return jnp.zeros((K, R), acc)
    out = pl.pallas_call(
        functools.partial(_reuse_kernel, acc=acc),
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, R, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((R, R), lambda k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, R), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, 1, R), acc),
        interpret=interpret,
    )(YkV, H)
    return _mask_rows(out[:, 0], subject_mask)
