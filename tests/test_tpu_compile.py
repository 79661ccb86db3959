"""Compile every Pallas kernel the backends can route to for a described TPU
v5e, at the CHOA rank-40 widths, with no chip attached.

Interpret mode (the CPU parity tests) cannot see what Mosaic refuses, such
as a block whose last two dims are neither (8, 128)-aligned nor the whole
array. These tests lower each kernel with ``interpret=False`` against a
described ``v5e:2x2`` topology, compile it with the TPU compiler, and check
that the Mosaic kernel is in the compiled HLO (``tpu_custom_call``). One ALS
step on the fused backend is compiled the same way.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc
from jax.sharding import SingleDeviceSharding

from repro.core import Parafac2Options, als_step, init_state
from repro.core.irregular import Bucket, Bucketed
from repro.kernels import fused, scoo
from repro.kernels.mttkrp_mode1 import mode1_pallas, mode1_reuse_pallas
from repro.kernels.mttkrp_mode2 import mode2_compact_pallas
from repro.kernels.mttkrp_mode3 import mode3_pallas, mode3_reuse_pallas
from repro.kernels.ykv import ykv_pallas

R = 40                     # the paper's CHOA rank
K = 64                     # subjects per compiled bucket (the grid length)
GEOMS = [(32, 128), (168, 256)]        # (I_pad, C_pad) of CHOA buckets
SCOO_GEOMS = [(32, 128, 256), (168, 256, 1024)]   # (I_pad, C_pad, N_pad)
f32, i32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def chip_config():
    """Compile as the drivers run on the chip: 32-bit (conftest turns x64 on
    for the f64 algebra tests; Mosaic wants i32 indices), and outside the
    persistent cache, which stores a compile for a described chip but
    cannot read it back without one."""
    prev = (jax.config.jax_enable_x64, jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_x64", prev[0])
    jax.config.update("jax_enable_compilation_cache", prev[1])
    cc.reset_cache()


def _compile_for_chip(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases():
    cases = []
    for I, C in GEOMS:
        cases += [
            (f"fused_procrustes_b-I{I}-C{C}",
             lambda v, g, w, h: fused.fused_procrustes_b(v, g, w, h),
             [((K, I, C), f32), ((K, C, R), f32), ((K, R), f32), ((R, R), f32)]),
            (f"fused_mode1_xkv-I{I}",
             lambda q, x, w: fused.fused_mode1_xkv(q, x, w),
             [((K, I, R), f32), ((K, I, R), f32), ((K, R), f32)]),
            (f"fused_mode2_compact-I{I}-C{C}",
             lambda v, q, h, w, m: fused.fused_mode2_compact(v, q, h, w, m),
             [((K, I, C), f32), ((K, I, R), f32), ((R, R), f32),
              ((K, R), f32), ((K, C), f32)]),
            (f"fused_ykv-I{I}-C{C}",
             lambda v, q, g: fused.fused_ykv(v, q, g),
             [((K, I, C), f32), ((K, I, R), f32), ((K, C, R), f32)]),
        ]
    for _, C in GEOMS:
        cases += [
            (f"mode1-C{C}", lambda y, g, w, m: mode1_pallas(y, g, w, m),
             [((K, R, C), f32), ((K, C, R), f32), ((K, R), f32), ((K,), f32)]),
            (f"mode2_compact-C{C}",
             lambda y, h, w, cm, m: mode2_compact_pallas(y, h, w, cm, m),
             [((K, R, C), f32), ((R, R), f32), ((K, R), f32), ((K, C), f32),
              ((K,), f32)]),
            (f"mode3-C{C}", lambda y, g, h, m: mode3_pallas(y, g, h, m),
             [((K, R, C), f32), ((K, C, R), f32), ((R, R), f32), ((K,), f32)]),
            (f"ykv-C{C}", lambda y, g: ykv_pallas(y, g),
             [((K, R, C), f32), ((K, C, R), f32)]),
        ]
    cases += [
        ("mode1_reuse", lambda y, w, m: mode1_reuse_pallas(y, w, m),
         [((K, R, R), f32), ((K, R), f32), ((K,), f32)]),
        ("mode3_reuse", lambda y, h, m: mode3_reuse_pallas(y, h, m),
         [((K, R, R), f32), ((R, R), f32), ((K,), f32)]),
    ]
    for I, C, N in SCOO_GEOMS:
        cases += [
            (f"scoo_xk_times_v-I{I}-N{N}",
             lambda v, r, c, g, n, I=I: scoo.xk_times_v_pallas(
                 v, r, c, g, I, nnz_counts=n),
             [((K, N), f32), ((K, N), i32), ((K, N), i32), ((K, C, R), f32),
              ((K,), i32)]),
            (f"scoo_project-C{C}-N{N}",
             lambda v, r, c, q, n, C=C: scoo.project_pallas(
                 v, r, c, q, C, nnz_counts=n),
             [((K, N), f32), ((K, N), i32), ((K, N), i32), ((K, I, R), f32),
              ((K,), i32)]),
        ]
    return cases


_CASES = _kernel_cases()


@pytest.mark.parametrize("name", [c[0] for c in _CASES])
def test_kernel_compiles_for_v5e(name, one_chip):
    _, fn, shapes = next(c for c in _CASES if c[0] == name)
    assert "tpu_custom_call" in _compile_for_chip(fn, shapes, one_chip)


def test_fused_als_step_compiles_for_v5e(one_chip, monkeypatch):
    """One whole ALS step on the fused route, both CHOA bucket geometries:
    the four fused kernels plus the eigh/solve glue compile together."""
    monkeypatch.setattr(fused, "_interpret", lambda: False)

    def spec(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    data = Bucketed(
        buckets=[Bucket(vals=spec((K, I, C)), cols=spec((K, C), i32),
                        col_mask=spec((K, C)), subject_ids=spec((K,), i32),
                        subject_mask=spec((K,)), row_counts=spec((K,), i32))
                 for I, C in GEOMS],
        n_subjects=K * len(GEOMS), n_cols=1_328, norm_sq=1.0)
    opts = Parafac2Options(rank=R, constraints={"v": "nonneg", "w": "nonneg"},
                           backend="fused")
    state = jax.eval_shape(lambda: init_state(data, opts, 0))
    state = jax.tree_util.tree_map(lambda x: spec(x.shape, x.dtype), state)
    text = jax.jit(lambda d, s: als_step(d, s, opts)).lower(
        data, state).compile().as_text()
    # F1-F4 per bucket: procrustes_b, mode1_xkv, mode2_compact, ykv
    assert text.count('custom_call_target="tpu_custom_call"') >= 4 * len(GEOMS)
