"""Operations and HBM bytes of the four fused Pallas kernels, per call.

Counted from a CC bucket's shapes (Kb subjects, I = I_pad rows, C = C_pad
kept columns, rank R), as ``repro/kernels/fused.py`` states its work: each
kernel streams every subject's [I, C] slab once, or only [I, R] tiles. f32
everywhere (4 bytes). The column block padding a kernel adds inside VMEM is
not counted; it only lowers the count, so the share can only read low.
"""
from __future__ import annotations

F32 = 4


def fused_costs(kb: int, i_pad: int, c_pad: int, rank: int) -> dict:
    """{kernel name: (flops, bytes)} for one call on one bucket."""
    I, C, R = i_pad, c_pad, rank
    slab = I * C * F32
    return {
        # xkv = vals @ Vg, B = (xkv * w) @ H^T
        "procrustes_b": (kb * (2 * I * C * R + 2 * I * R * R),
                         kb * (slab + C * R * F32 + R * F32 + 2 * I * R * F32)
                         + R * R * F32),
        # ykv = Q^T xkv, reduced into M1
        "mode1_xkv": (kb * 2 * I * R * R,
                      kb * (2 * I * R * F32 + R * F32) + R * R * F32),
        # ycT = vals^T Q, a = ycT H, masked and scaled
        "mode2": (kb * (2 * I * C * R + 2 * C * R * R),
                  kb * (slab + I * R * F32 + R * F32 + C * F32 + C * R * F32)
                  + R * R * F32),
        # yc = Q^T vals, g = yc Vg
        "ykv": (kb * (2 * I * C * R + 2 * R * C * R),
                kb * (slab + I * R * F32 + C * R * F32 + R * R * F32)),
    }


def als_iteration_flops(subjects: int, rows: int, nnz: int,
                        distinct_cols: int, rank: int) -> float:
    """The least operations one PARAFAC2-ALS iteration (SPARTan's) needs on
    a cohort, from its unpadded sizes: K subjects, sum(I_k) rows, nnz
    nonzeros, sum(c_k) distinct columns per subject.

    Per subject: X_k V and Q_k^T X_k (two passes over the nonzeros); B_k =
    (X_k V S_k) H^T, its Gram B_k^T B_k and Q_k from it (three [I_k, R] x
    [R, R] products); one [R, R] x [R, R] product for the polar factor; and
    the CP step's mode-1 and mode-2 products on the [R, c_k] slice Y_k.
    Padding, the eigendecomposition's own iterations and the nonnegative
    updates are not counted, so a share of a peak can only read low."""
    R = rank
    return float(2 * 2 * nnz * R + 3 * 2 * rows * R * R
                 + subjects * 2 * R ** 3 + 2 * 2 * distinct_cols * R * R)
