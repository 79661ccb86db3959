"""Per-kernel allclose validation of the four fused Pallas kernels
(interpret=True on CPU) against the jnp SPARTan math of core/spartan.py,
swept over shapes and dtypes."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import spartan
from repro.kernels import fused
from repro.kernels.common import fold_subject_mask


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


SHAPES = [  # (K, R, C, I_pad)
    (1, 8, 128, 8),
    (3, 8, 128, 24),
    (4, 16, 256, 8),
    (2, 40, 384, 168),
    (5, 8, 640, 24),   # multiple C tiles with block_c=512
]
DTYPES = [jnp.float32, jnp.bfloat16]
KERNELS = ["procrustes_b", "mode1_xkv", "mode2_compact", "ykv"]


def _f32(x):
    return x.astype(jnp.float32)


def _operands(K, R, C, I, dtype):
    """One bucket's operands: the vals slab, gathered V rows, Q, H, W rows,
    a column mask with padding and a subject mask whose last slot is a
    padding subject (for K > 1)."""
    vals = _rand((K, I, C), dtype, 0)
    Vg = _rand((K, C, R), dtype, 1)
    Q = _rand((K, I, R), dtype, 2)
    H = _rand((R, R), dtype, 3)
    Wb = _rand((K, R), dtype, 4)
    col_mask = jnp.asarray(np.arange(C) < C - 5, dtype)[None].repeat(K, 0)
    smask = jnp.ones((K,), dtype)
    if K > 1:
        smask = smask.at[-1].set(0)
    return vals, Vg, Q, H, Wb, col_mask, smask


def _run(kernel, vals, Vg, Q, H, Wb, col_mask, smask):
    """-> (kernel output, the spartan reference on the same inputs in f32)."""
    Wm = fold_subject_mask(Wb, smask)
    XkV = jnp.einsum("kic,kcr->kir", _f32(vals), _f32(Vg))
    Yc = jnp.einsum("kir,kic->krc", _f32(Q), _f32(vals))
    if kernel == "procrustes_b":
        xkv, B = fused.fused_procrustes_b(vals, Vg, Wb, H, interpret=True)
        want_B = jnp.einsum("kir,lr->kil", XkV * _f32(Wb)[:, None, :],
                            _f32(H))
        return jnp.concatenate([xkv, B], 1), jnp.concatenate([XkV, want_B], 1)
    if kernel == "mode1_xkv":
        out = fused.fused_mode1_xkv(Q, XkV.astype(Q.dtype), Wm,
                                    interpret=True)
        YkV = jnp.einsum("kir,kil->krl", _f32(Q), _f32(XkV.astype(Q.dtype)))
        return out, spartan.mode1_bucket(None, None, _f32(Wb), _f32(smask),
                                         YkV=YkV)
    if kernel == "mode2_compact":
        out = fused.fused_mode2_compact(vals, Q, H, Wm, col_mask,
                                        interpret=True)
        return out, spartan.mode2_bucket_compact(
            Yc, _f32(H), _f32(Wb), _f32(col_mask), _f32(smask))
    # Y_k V, the product spartan.mode1_bucket / mode3_bucket form from Yc
    out = fused.fused_ykv(vals, Q, Vg, interpret=True)
    return out, jnp.einsum("krc,kcl->krl", Yc, _f32(Vg))


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_kernel(kernel, shape, dtype):
    K, R, C, I = shape
    out, want = _run(kernel, *_operands(K, R, C, I, dtype))
    assert out.dtype == jnp.float32 and out.shape == want.shape
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, want, rtol=tol,
                               atol=tol * np.abs(want).max())
