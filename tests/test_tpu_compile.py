"""Compile every Pallas kernel the router can send work to for a described
TPU v5e, at the CHOA rank-40 widths, with no chip attached.

Interpret mode (the CPU parity tests) cannot see what Mosaic refuses, such
as a block whose last two dims are neither (8, 128)-aligned nor the whole
array. These tests lower each kernel with ``interpret=False`` against a
described ``v5e:2x2`` topology, compile it with the TPU compiler, and check
that the Mosaic kernel is in the compiled HLO (``tpu_custom_call``): the
fused kernels at two CHOA bucket shapes and at every bucket shape of the
``choa-r40.cc`` plan. One ALS step on the fused route is compiled the same
way, and one routed step per SCOO bucket shape of the ``choa-r40.auto`` and
``movielens-r40.auto`` plans, whose mode-1 stage must hold no Pallas call.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc
from jax.sharding import SingleDeviceSharding

from repro.core import Parafac2Options, als_step, bucketize, init_state
from repro.core import backend
from repro.core.irregular import Bucket, Bucketed, ScatterIndex, SparseBucket
from repro.kernels import fused, segsum
from repro.sparse import random_irregular

R = 40                     # the paper's CHOA rank
K = 64                     # subjects per compiled bucket (the grid length)
GEOMS = [(32, 128), (168, 256)]        # (I_pad, C_pad) of CHOA buckets
# (Kb, I_pad, C_pad) of every bucket of the choa-r40.cc plan (PERF.md §4)
CC_PLAN = [(22_586, 8, 128), (19_874, 16, 128), (3_738, 48, 128),
           (292, 168, 128)]
# (I_pad, C_pad, N_pad) of every SCOO bucket of the choa-r40.auto and
# movielens-r40.auto plans (PERF.md §4), with each config's J
SCOO_PLANS = [
    ("choa", 1_328, [(8, 128, 32), (24, 128, 96), (56, 128, 224),
                     (168, 128, 648)]),
    ("ml", 26_096, [(8, 256, 256), (8, 768, 768), (8, 1_920, 1_920),
                    (24, 6_656, 6_544)]),
]
SCOO_KB = 8                # subjects per compiled SCOO bucket
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


def _fused_cases(Kb, I, C, tag):
    """The four fused kernels of one CC bucket [Kb, I, C] at rank R."""
    return [
        (f"fused_procrustes_b-{tag}I{I}-C{C}",
         lambda v, g, w, h: fused.fused_procrustes_b(v, g, w, h),
         [((Kb, I, C), f32), ((Kb, C, R), f32), ((Kb, R), f32),
          ((R, R), f32)]),
        (f"fused_mode1_xkv-{tag}I{I}",
         lambda q, x, w: fused.fused_mode1_xkv(q, x, w),
         [((Kb, I, R), f32), ((Kb, I, R), f32), ((Kb, R), f32)]),
        (f"fused_mode2_compact-{tag}I{I}-C{C}",
         lambda v, q, h, w, m: fused.fused_mode2_compact(v, q, h, w, m),
         [((Kb, I, C), f32), ((Kb, I, R), f32), ((R, R), f32),
          ((Kb, R), f32), ((Kb, C), f32)]),
        (f"fused_ykv-{tag}I{I}-C{C}",
         lambda v, q, g: fused.fused_ykv(v, q, g),
         [((Kb, I, C), f32), ((Kb, I, R), f32), ((Kb, C, R), f32)]),
    ]


def _kernel_cases():
    cases = []
    for I, C in GEOMS:
        cases += _fused_cases(K, I, C, "")
    for Kb, I, C in CC_PLAN:
        cases += _fused_cases(Kb, I, C, f"K{Kb}-")
    for _, C in GEOMS:
        # the mode-2 segment sum over a bucket's K * C sorted compact rows,
        # and over a count that leaves the last block short
        for n in (K * C, K * C + 200):
            cases.append((
                f"segmented_cumsum-N{n}",
                lambda x, s: segsum.segmented_cumsum_pallas(x, s,
                                                           interpret=False),
                [((n, R), f32), ((n,), jnp.bool_)]))
    return cases


_CASES = _kernel_cases()


@pytest.mark.parametrize("name", [c[0] for c in _CASES])
def test_kernel_compiles_for_v5e(name, one_chip):
    _, fn, shapes = next(c for c in _CASES if c[0] == name)
    assert "tpu_custom_call" in _compile_for_chip(fn, shapes, one_chip)


def _segsum_on_chip(monkeypatch):
    """Route the mode-2 segment sum as on a TPU, to the compiled kernel."""
    monkeypatch.setattr(segsum, "_on_tpu", lambda: True)
    monkeypatch.setattr(segsum, "segmented_cumsum_pallas", functools.partial(
        segsum.segmented_cumsum_pallas, interpret=False))


def _index_spec(Kb, C, J, sharding):
    """Shape specs of a bucket's scatter index, every compact slot real."""
    n = Kb * C
    return ScatterIndex(
        perm=jax.ShapeDtypeStruct((1, n), i32, sharding=sharding),
        starts=jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=sharding),
        ends=jax.ShapeDtypeStruct((1, J), i32, sharding=sharding))


def test_mode2_scatter_kernel_compiles_under_f32_matmuls(one_chip,
                                                         monkeypatch):
    """``als_step`` traces under f32 matmul precision; the segment-sum
    kernel's bf16 matmuls must still compile for the chip there."""
    from repro.core import spartan

    _segsum_on_chip(monkeypatch)
    n = K * 128
    with jax.default_matmul_precision("float32"):
        text = _compile_for_chip(
            lambda a, p, st, e: spartan.mode2_scatter(
                a, ScatterIndex(perm=p, starts=st, ends=e)),
            [((K, 128, R), f32), ((1, n), i32), ((n,), jnp.bool_),
             ((1, 1_328), i32)], one_chip)
    assert "tpu_custom_call" in text


def _cc_bucket(Kb, I, C, J, sharding):
    """A CC Bucket of shape specs [Kb, I_pad, C_pad] over J columns."""
    def spec(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    return Bucket(vals=spec((Kb, I, C)), cols=spec((Kb, C), i32),
                  col_mask=spec((Kb, C)), subject_ids=spec((Kb,), i32),
                  subject_mask=spec((Kb,)), row_counts=spec((Kb,), i32),
                  scatter=_index_spec(Kb, C, J, sharding))


def test_fused_als_step_compiles_for_v5e(one_chip, monkeypatch):
    """One whole ALS step on the fused route, both CHOA bucket geometries:
    the four fused kernels plus the eigh/solve glue compile together."""
    monkeypatch.setattr(backend, "_on_tpu", lambda: True)
    monkeypatch.setattr(fused, "_interpret", lambda: False)

    def spec(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    data = Bucketed(
        buckets=[_cc_bucket(K, I, C, 1_328, one_chip) for I, C in GEOMS],
        n_subjects=K * len(GEOMS), n_cols=1_328, norm_sq=1.0)
    opts = Parafac2Options(rank=R, constraints={"v": "nonneg", "w": "nonneg"},
                           backend="auto")
    assert {backend.get_backend("auto").route(b, R)
            for b in data.buckets} == {"fused"}
    state = jax.eval_shape(lambda: init_state(data, opts, 0))
    state = jax.tree_util.tree_map(lambda x: spec(x.shape, x.dtype), state)
    text = jax.jit(lambda d, s: als_step(d, s, opts)).lower(
        data, state).compile().as_text()
    # F1-F4 per bucket: procrustes_b, mode1_xkv, mode2_compact, ykv
    assert text.count('custom_call_target="tpu_custom_call"') >= 4 * len(GEOMS)


def _scoo_bucket(template, I, C, N, J, sharding):
    """A SparseBucket of shape specs [Kb = SCOO_KB] at (I_pad, C_pad, N_pad)
    over J columns, each leaf with the dtype ``bucketize`` gives it."""
    dims = {"vals": (SCOO_KB, N), "rows": (SCOO_KB, N),
            "lcols": (SCOO_KB, N), "cperm": (SCOO_KB, N),
            "row_ends": (SCOO_KB, I), "col_ends": (SCOO_KB, C),
            "cols": (SCOO_KB, C), "col_mask": (SCOO_KB, C)}
    leaves = {f.name: getattr(template, f.name)
              for f in dataclasses.fields(template)
              if f.name not in ("n_rows_pad", "scatter")}
    return SparseBucket(
        n_rows_pad=I, scatter=_index_spec(SCOO_KB, C, J, sharding), **{
            name: jax.ShapeDtypeStruct(dims.get(name, (SCOO_KB,)), leaf.dtype,
                                       sharding=sharding)
            for name, leaf in leaves.items()})


def _scoo_template():
    return bucketize(random_irregular(n_subjects=4, n_cols=30, max_rows=5,
                                      avg_nnz_per_subject=10, seed=0),
                     max_buckets=1, format="scoo").buckets[0]


def _compile_step_for_chip(data, sharding) -> str:
    """One routed ALS step over ``data`` (shape specs), compiled for the
    described chip."""
    opts = Parafac2Options(rank=R, constraints={"v": "nonneg", "w": "nonneg"},
                           backend="auto")
    state = jax.eval_shape(lambda: init_state(data, opts, 0))
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        state)
    return jax.jit(lambda d, s: als_step(d, s, opts)).lower(
        data, state).compile().as_text()


_SCOO_CASES = [(f"{cfg}-I{I}-C{C}-N{N}", J, (I, C, N))
               for cfg, J, shapes in SCOO_PLANS for I, C, N in shapes]
_CUSTOM_CALL = re.compile(r'custom_call_target="tpu_custom_call".*'
                          r'op_name="[^"]*als/mode1/')


@pytest.mark.parametrize("name", [c[0] for c in _SCOO_CASES])
def test_scoo_als_step_compiles_for_v5e(name, one_chip, monkeypatch):
    """One routed ALS step on a SCOO bucket of a benchmark plan, the router
    answering as on a TPU: the step compiles, and its mode-1 stage is jnp
    (no Mosaic kernel under ``als/mode1``), as its mode-3 stage is."""
    _, J, (I, C, N) = next(c for c in _SCOO_CASES if c[0] == name)
    monkeypatch.setattr(backend, "_on_tpu", lambda: True)
    b = _scoo_bucket(_scoo_template(), I, C, N, J, one_chip)
    data = Bucketed(buckets=[b], n_subjects=SCOO_KB, n_cols=J, norm_sq=1.0)
    assert backend.get_backend("auto").route(b, R) == "scoo"
    text = _compile_step_for_chip(data, one_chip)
    assert "als/mode1/" in text
    assert not _CUSTOM_CALL.search(text)


_SCATTER_SORT = re.compile(r' sort\(.*op_name="[^"]*als/scatter/')


@pytest.mark.parametrize("cell", ["choa-r40.auto", "choa-r40.cc"])
def test_routed_als_step_sorts_nothing_in_the_scatter(cell, one_chip,
                                                      monkeypatch):
    """The routed ALS step over every bucket shape of the cell's plan,
    compiled for the chip with the segment sum on its kernel: the mode-2
    scatter gathers and sums in the order the layout fixed, so no sort op
    sits under ``als/scatter`` (``eigh`` sorts its eigenvalues elsewhere)."""
    monkeypatch.setattr(backend, "_on_tpu", lambda: True)
    monkeypatch.setattr(fused, "_interpret", lambda: False)
    _segsum_on_chip(monkeypatch)
    J = 1_328
    if cell == "choa-r40.cc":
        buckets = [_cc_bucket(Kb, I, C, J, one_chip) for Kb, I, C in CC_PLAN]
    else:
        template = _scoo_template()
        buckets = [_scoo_bucket(template, I, C, N, J, one_chip)
                   for cfg, _, shapes in SCOO_PLANS if cfg == "choa"
                   for I, C, N in shapes]
    data = Bucketed(buckets=buckets, n_subjects=sum(b.kb for b in buckets),
                    n_cols=J, norm_sq=1.0)
    text = _compile_step_for_chip(data, one_chip)
    assert "als/scatter/gather" in text and "tpu_custom_call" in text
    assert not _SCATTER_SORT.search(text)
