"""Fused Pallas ALS stages — one VMEM pass over each subject's CC slab.

The staged path round-trips every intermediate through HBM between separate
kernel launches: ``X_k V`` (xkv), ``B_k`` (Procrustes input), the projected
slices ``Y_k`` (project), and ``Y_k V`` (ykv) are each written by one stage
and re-read by the next. The fused stages here collapse that per bucket per
ALS iteration: each subject's kept-column slab ``vals[k]`` ([I_pad, C_pad],
the only large operand) is streamed through VMEM in double-buffered DMA
chunks and every product that touches it is computed in the same grid step,
so only the small per-subject results ([I,R] / [R,R] / [C,R]) ever reach HBM
— ``Y_k`` is NEVER materialized (the fused backend carries ``Q_k`` instead,
exactly like the SCOO-native route).

Why four launches and not one: exact Gauss-Seidel ALS parity admits at most
four fused dispatches per bucket per iteration, because the eigendecomposition
inside ``solve_q`` and the H-/V- normal-equation solves are global
synchronization points — ``Q_k`` depends on all of ``B_k``, the mode-2 stage
needs the UPDATED ``H``, and the ykv/fit stage needs the UPDATED ``V``. The
floor is

  F1 ``fused_procrustes_b``  xkv + B formation       (streams vals, 1st pass)
       --- eigh (solve_q) ---
  F2 ``fused_mode1_xkv``     YkV = Q^T XkV + M1 partial sum   ([I,R] operands)
       --- H solve ---
  F3 ``fused_mode2_compact`` project + mode-2 compact (streams vals, 2nd pass)
       --- V solve ---
  F4 ``fused_ykv``           project + Y_k V          (streams vals, 3rd pass)
  (mode-3 is a trivial [R,R] coldot on F4's output — no large operands left.)

versus the five streaming stage launches of the staged path (procrustes_b,
project, mode1-from-XkV, mode2, ykv). ``core.backend.dispatch_tally``
measures exactly this 5 -> 4 collapse.

Traffic tradeoff (documented, not hidden): fused reads ``vals`` three times
and writes no ``Y_k``; staged reads ``vals`` twice plus one write + two reads
of ``Y_k`` [R, C] and the XkV/B round-trips. Fused wins outright when
I_pad ≲ 3R — the compressed regime (``--compress rsvd:r`` cores have
I' ≈ r) — and on launch/round-trip overhead everywhere; with
``precision="bf16"`` the streamed slab bytes halve again while every dot
still accumulates in f32 (``preferred_element_type`` = ``accum_dtype``).

All wrappers accept f32/f64 (f64 accumulates f64 — unlike ``PallasBackend``
there is no silent demotion; Mosaic rejects f64 on real TPUs, but the fused
route is gated to f32/bf16 there by ``AutoBackend._fused_ok``) and bf16/f16
inputs (accumulate f32). ``interpret=True`` runs everywhere via the Pallas
interpreter — the CI parity path on CPU.

VMEM budget per grid step (one subject): the double buffer dominates at
``2 * I_pad * block_c * itemsize``; ``block_c`` is halved until it fits
``VMEM_BUDGET`` (8 MiB, leaving headroom for Vg [C_pad, R], the [I, R]
accumulator, and the output windows on a 16 MiB part).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import accum_dtype

__all__ = [
    "fused_procrustes_b",
    "fused_mode1_xkv",
    "fused_mode2_compact",
    "fused_ykv",
]

VMEM_BUDGET = 8 * 1024 * 1024  # double-buffer byte cap per grid step


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_block_c(C: int, I: int, itemsize: int, block_c: int) -> int:
    """Largest chunk width <= block_c whose double buffer fits VMEM_BUDGET."""
    bc = min(block_c, C)
    while bc > 128 and 2 * I * bc * itemsize > VMEM_BUDGET:
        bc //= 2
    return max(bc, 1)


def _pad_c(x: jax.Array, axis: int, C_pad: int) -> jax.Array:
    """Zero-pad axis ``axis`` to C_pad (zero columns contribute nothing)."""
    C = x.shape[axis]
    if C == C_pad:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, C_pad - C)
    return jnp.pad(x, pads)


# ---------------------------------------------------------------------------
# F1: xkv + Procrustes B formation (first slab pass)
# ---------------------------------------------------------------------------

def _procrustes_b_kernel(vals_hbm, vg_ref, wb_ref, h_ref, xkv_ref, b_ref,
                         vbuf, sem, *, nc: int, bc: int, acc):
    k = pl.program_id(0)
    I, R = xkv_ref.shape[1], xkv_ref.shape[2]

    def dma(slot, c):
        return pltpu.make_async_copy(
            vals_hbm.at[k, :, pl.ds(c * bc, bc)], vbuf.at[slot], sem.at[slot])

    dma(0, 0).start()

    def step(c, xkv):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nc)
        def _prefetch():
            dma(1 - slot, c + 1).start()

        dma(slot, c).wait()
        vg_c = vg_ref[0, pl.ds(c * bc, bc), :]            # [bc, R]
        return xkv + jnp.dot(vbuf[slot], vg_c, preferred_element_type=acc)

    xkv = jax.lax.fori_loop(0, nc, step, jnp.zeros((I, R), acc))
    xkv_ref[0] = xkv
    # B_k = (X_k V * w_k) H^T in the same dispatch — XkV never leaves VMEM
    # before its second use.
    w = wb_ref[0, 0].astype(acc)
    b_ref[0] = jnp.dot(xkv * w[None, :], h_ref[...].astype(acc).T,
                       preferred_element_type=acc)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def fused_procrustes_b(
    vals: jax.Array,
    Vg: jax.Array,
    Wb: jax.Array,
    H: jax.Array,
    *,
    block_c: int = 512,
    interpret: bool = False,
):
    """vals [K,I,C], Vg [K,C,R], Wb [K,R], H [R,R] ->
    (XkV [K,I,R], B [K,I,R]) with B_k = (X_k V * w_k) H^T."""
    K, I, C = vals.shape
    R = Vg.shape[-1]
    acc = accum_dtype(vals)
    if K == 0:
        z = jnp.zeros((K, I, R), acc)
        return z, z
    bc = _pick_block_c(C, I, vals.dtype.itemsize, block_c)
    nc = pl.cdiv(C, bc)
    vals = _pad_c(vals, 2, nc * bc)
    Vg = _pad_c(Vg, 1, nc * bc)
    out = pl.pallas_call(
        functools.partial(_procrustes_b_kernel, nc=nc, bc=bc, acc=acc),
        grid=(K,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # vals: manual DMA
            pl.BlockSpec((1, nc * bc, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((R, R), lambda k: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, I, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((1, I, R), lambda k: (k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, I, R), acc),
            jax.ShapeDtypeStruct((K, I, R), acc),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, I, bc), vals.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(vals, Vg, Wb[:, None, :], H)
    return out[0], out[1]


# ---------------------------------------------------------------------------
# F2: YkV = Q^T XkV + mode-1 partial sum (no slab pass — [I,R] operands)
# ---------------------------------------------------------------------------

def _mode1_xkv_kernel(q_ref, xkv_ref, wb_ref, out_ref, *, acc):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ykv = jnp.dot(q_ref[0].astype(acc).T, xkv_ref[0].astype(acc),
                  preferred_element_type=acc)             # [R, R]
    out_ref[...] += ykv * wb_ref[0, 0].astype(acc)[None, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_mode1_xkv(
    Q: jax.Array,
    XkV: jax.Array,
    Wb: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Q [K,I,R], XkV [K,I,R], Wb [K,R] (subject mask pre-folded) ->
    partial M1 [R,R] = sum_k (Q_k^T X_k V) * w_k via the mode-1 reuse
    identity Y_k V = Q_k^T (X_k V): the per-subject YkV is formed and
    reduced in the same dispatch, never written back."""
    K, I, R = Q.shape
    acc = accum_dtype(Q)
    if K == 0:
        return jnp.zeros((R, R), acc)
    return pl.pallas_call(
        functools.partial(_mode1_xkv_kernel, acc=acc),
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, I, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((1, I, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda k: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((R, R), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, R), acc),
        interpret=interpret,
    )(Q, XkV, Wb[:, None, :])


# ---------------------------------------------------------------------------
# F3: projection + mode-2 compact (second slab pass; Yc tiles stay in VMEM)
# ---------------------------------------------------------------------------

def _mode2_kernel(vals_hbm, q_ref, h_ref, wb_ref, cm_ref, out_ref,
                  vbuf, sem, *, nc: int, bc: int, acc):
    k = pl.program_id(0)

    def dma(slot, c):
        return pltpu.make_async_copy(
            vals_hbm.at[k, :, pl.ds(c * bc, bc)], vbuf.at[slot], sem.at[slot])

    dma(0, 0).start()
    q = q_ref[0].astype(acc)                              # [I, R]
    h = h_ref[...].astype(acc)                            # [R, R]
    w = wb_ref[0, 0].astype(acc)                          # [R]

    def step(c, _):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nc)
        def _prefetch():
            dma(1 - slot, c + 1).start()

        dma(slot, c).wait()
        # Yc tile transposed: (vals_chunk^T Q) = (Q^T vals_chunk)^T  [bc, R]
        ycT = jnp.dot(vbuf[slot].T, q, preferred_element_type=acc)
        a = jnp.dot(ycT, h, preferred_element_type=acc)   # (Y_k^T H) tile
        cm = cm_ref[0, 0, pl.ds(c * bc, bc)].astype(acc)
        out_ref[0, pl.ds(c * bc, bc), :] = a * w[None, :] * cm[:, None]
        return 0

    jax.lax.fori_loop(0, nc, step, 0)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def fused_mode2_compact(
    vals: jax.Array,
    Q: jax.Array,
    H: jax.Array,
    Wb: jax.Array,
    col_mask: jax.Array,
    *,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """vals [K,I,C], Q [K,I,R], H [R,R], Wb [K,R] (mask pre-folded),
    col_mask [K,C] -> A [K,C,R] = (Y_k^T H) * W(k,:) with Y_k = Q_k^T X_k
    recomputed tile-wise in VMEM — the projection never reaches HBM."""
    K, I, C = vals.shape
    R = Q.shape[-1]
    acc = accum_dtype(vals)
    if K == 0:
        return jnp.zeros((K, C, R), acc)
    bc = _pick_block_c(C, I, vals.dtype.itemsize, block_c)
    nc = pl.cdiv(C, bc)
    C_pad = nc * bc
    vals = _pad_c(vals, 2, C_pad)
    col_mask = _pad_c(col_mask, 1, C_pad)
    out = pl.pallas_call(
        functools.partial(_mode2_kernel, nc=nc, bc=bc, acc=acc),
        grid=(K,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # vals: manual DMA
            pl.BlockSpec((1, I, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((R, R), lambda k: (0, 0)),
            pl.BlockSpec((1, 1, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((1, 1, C_pad), lambda k: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C_pad, R), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, C_pad, R), acc),
        scratch_shapes=[
            pltpu.VMEM((2, I, bc), vals.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(vals, Q, H, Wb[:, None, :], col_mask[:, None, :])
    return out[:, :C, :]


# ---------------------------------------------------------------------------
# F4: projection + Y_k V (third slab pass; feeds mode-3 and the fit)
# ---------------------------------------------------------------------------

def _ykv_kernel(vals_hbm, q_ref, vg_ref, out_ref, vbuf, sem,
                *, nc: int, bc: int, acc):
    k = pl.program_id(0)
    R = out_ref.shape[1]

    def dma(slot, c):
        return pltpu.make_async_copy(
            vals_hbm.at[k, :, pl.ds(c * bc, bc)], vbuf.at[slot], sem.at[slot])

    dma(0, 0).start()
    q = q_ref[0].astype(acc)                              # [I, R]

    def step(c, g):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nc)
        def _prefetch():
            dma(1 - slot, c + 1).start()

        dma(slot, c).wait()
        yc = jnp.dot(q.T, vbuf[slot].astype(acc),
                     preferred_element_type=acc)          # Yc tile [R, bc]
        vg_c = vg_ref[0, pl.ds(c * bc, bc), :].astype(acc)
        return g + jnp.dot(yc, vg_c, preferred_element_type=acc)

    out_ref[0] = jax.lax.fori_loop(0, nc, step, jnp.zeros((R, R), acc))


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def fused_ykv(
    vals: jax.Array,
    Q: jax.Array,
    Vg: jax.Array,
    *,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """vals [K,I,C], Q [K,I,R], Vg [K,C,R] -> G [K,R,R] = (Q_k^T X_k) V,
    the shared mode-3 / fit product, with the projection tile-local."""
    K, I, C = vals.shape
    R = Q.shape[-1]
    acc = accum_dtype(vals)
    if K == 0:
        return jnp.zeros((K, R, R), acc)
    bc = _pick_block_c(C, I, vals.dtype.itemsize, block_c)
    nc = pl.cdiv(C, bc)
    vals = _pad_c(vals, 2, nc * bc)
    Vg = _pad_c(Vg, 1, nc * bc)
    return pl.pallas_call(
        functools.partial(_ykv_kernel, nc=nc, bc=bc, acc=acc),
        grid=(K,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # vals: manual DMA
            pl.BlockSpec((1, I, R), lambda k: (k, 0, 0)),
            pl.BlockSpec((1, nc * bc, R), lambda k: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, R), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, R, R), acc),
        scratch_shapes=[
            pltpu.VMEM((2, I, bc), vals.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(vals, Q, Vg)
