"""Device-resident ALS execution engines: scan chunks, while_loop, shard_map.

The reference fitting loop (``core/parafac2.py::fit`` with ``engine="host"``)
dispatches one jitted ``als_step`` per iteration and forces a device sync
every iteration via ``float(state.fit)`` — at small ranks the host loop, not
the MTTKRP math, is the wall-clock floor. This module runs the same algebra
as compiled device-resident programs:

``engine="scan"``
    ``lax.scan`` over fixed chunks of ``opts.check_every`` iterations per
    dispatch. The ``Parafac2State`` carry is donated back to the runtime
    (no per-iteration realloc), the per-iteration fit history is accumulated
    on device as the scan's ys, and the host only syncs ONCE per chunk to run
    the tol check on the chunk's fit values. Convergence is therefore
    detected at chunk granularity: up to ``check_every - 1`` extra
    iterations may run past the tol crossing (harmless — ALS fit is
    monotone), and ``history[-1]`` always equals the returned state's fit.

``opts.check_every = 0`` (while_loop variant)
    The whole run is ONE dispatch: ``lax.while_loop`` with the tol check
    evaluated on device, reproducing the host loop's stopping rule exactly
    (stop after the first iteration whose fit change is below tol). The fit
    history lands in a preallocated ``[max_iters]`` device buffer that the
    host truncates once, after the loop returns.

``engine="mesh"``
    The scanned (or while'd) step additionally wrapped in ``shard_map`` over
    the subjects bucket axis: every ``Bucket`` leaf and every bucketed-W
    shard splits over the mesh axes the ``"subjects"`` rule resolves to
    (:func:`repro.dist.sharding.subject_mesh_axes`), H/V/global-W/fit stay
    replicated, and the cross-subject reductions inside ``als_step`` go
    through :func:`repro.dist.sharding.psum_subjects`, which lowers to
    explicit ``lax.psum`` over those axes inside the body (and is the
    identity everywhere else). This is where the PR-1 mesh machinery and the
    PR-2 backend layer meet on one compiled hot path.

Shard_map needs exact divisibility: each bucket's ``Kb`` must divide by the
number of subject shards — pass ``bucketize(subject_align=n_shards)`` (the
launchers do this automatically for ``--engine mesh``).

See docs/ARCHITECTURE.md (stage 6) for the full story.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import parafac2 as p2
from repro.core.spans import span
from repro.dist import sharding as dsh

__all__ = ["ENGINES", "als_chunk_fn", "fit_device", "make_als_chunk",
           "make_als_while", "make_subject_update", "mesh_wrap"]

ENGINES = ("host", "scan", "mesh")


# ---------------------------------------------------------------------------
# mesh plumbing
# ---------------------------------------------------------------------------

def _default_mesh() -> Mesh:
    """All local devices as a 1-D data mesh (when no mesh is installed)."""
    n = len(jax.devices())
    return dsh.make_mesh((n,), ("data",))


def _n_shards(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.devices.shape[mesh.axis_names.index(a)]
    return n


def _check_divisible(data, state, n_shards: int) -> None:
    for i, b in enumerate(data.buckets):
        if b.kb % n_shards:
            raise ValueError(
                f"engine='mesh' needs every bucket's subject count to divide "
                f"the {n_shards} subject shards, but bucket {i} has Kb={b.kb}; "
                f"re-bucketize with bucketize(subject_align={n_shards})")
        runs = b.scatter.perm.shape[0]
        if runs % n_shards:
            raise ValueError(
                f"engine='mesh' needs every bucket's scatter index laid out "
                f"per subject shard, but bucket {i} has {runs} run(s) for "
                f"{n_shards} shards; re-bucketize with "
                f"bucketize(subject_align={n_shards})")
    if isinstance(state.W, tuple):
        for i, wb in enumerate(state.W):
            if wb.shape[0] % n_shards:
                raise ValueError(
                    f"bucketed W shard {i} has Kb={wb.shape[0]}, not divisible "
                    f"by {n_shards} subject shards")


def _mesh_specs(data, state, axes: Tuple[str, ...]):
    """(data_specs, state_specs) pytrees for shard_map over the subject axis.

    Every Bucket leaf is Kb-leading (the scatter index leads with its
    per-shard runs) → split over `axes`; H/V/fit (and a
    global [K,R] W) are replicated; a bucketed W tuple splits like the data.
    Constraint aux state (ADMM duals) follows its owning factor: the "w" aux
    of a bucketed W splits over the subject axes, everything else replicates.
    """
    lead = P(axes if len(axes) > 1 else axes[0])
    d_specs = jax.tree_util.tree_map(lambda _: lead, data)
    W = state.W
    w_spec = tuple(lead for _ in W) if isinstance(W, tuple) else P()
    aux = state.aux
    if isinstance(aux, dict):
        aux_specs = {
            k: jax.tree_util.tree_map(
                lambda _: lead if (k == "w" and isinstance(W, tuple)) else P(),
                sub)
            for k, sub in aux.items()}
    else:
        aux_specs = jax.tree_util.tree_map(lambda _: P(), aux)
    s_specs = p2.Parafac2State(H=P(), V=P(), W=w_spec, fit=P(), aux=aux_specs)
    return d_specs, s_specs


def _resolve_mesh() -> Tuple[Mesh, Tuple[str, ...]]:
    mesh = dsh.current_mesh()
    if mesh is None:
        mesh = _default_mesh()
    axes = dsh.subject_mesh_axes(mesh)
    if not axes:
        raise ValueError(
            f"engine='mesh': no 'subjects' rule axis present on mesh "
            f"{mesh.axis_names}; install axis_rules with a subjects entry")
    return mesh, axes


def _donate(donate: Optional[bool], argnum: int) -> Tuple[int, ...]:
    """State-carry donation argnums; defaults off on CPU (not implemented
    there — donating would just emit a warning per dispatch)."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    return (argnum,) if donate else ()


def mesh_wrap(fn: Callable, data, state, mesh: Optional[Mesh] = None,
              axes: Optional[Tuple[str, ...]] = None) -> Callable:
    """Wrap a ``(data, state) -> outputs`` ALS body in shard_map over the
    subjects bucket axis. `data`/`state` may be arrays or ShapeDtypeStructs
    (the dry-run lowers against specs). Every Bucket leaf (and bucketed-W
    shard) splits over the subject mesh axes; all other outputs — factor
    matrices, fit history, iteration counters — are replicated. Inside the
    body, cross-subject reductions route through
    :func:`repro.dist.sharding.psum_subjects` as explicit psums."""
    if mesh is None or axes is None:
        r_mesh, r_axes = _resolve_mesh()
        mesh = mesh if mesh is not None else r_mesh
        axes = axes if axes is not None else dsh.subject_mesh_axes(mesh)
    _check_divisible(data, state, _n_shards(mesh, axes))
    d_specs, s_specs = _mesh_specs(data, state, axes)

    def mapped_body(dd, ss):
        # entered during tracing of the shard_map body: psum_subjects
        # becomes lax.psum over `axes`, shard() constraints no-op
        with dsh.subject_collectives(axes):
            return fn(dd, ss)

    # out specs: probe the output structure (state leaves follow the input
    # state spec; everything else — fit history, counters — is replicated
    # R×R/scalar algebra).
    out_shapes = jax.eval_shape(fn, data, state)
    n_state = len(jax.tree_util.tree_leaves(s_specs))
    flat, treedef = jax.tree_util.tree_flatten(out_shapes)
    state_flat = jax.tree_util.tree_leaves(s_specs)
    out_flat = state_flat + [P()] * (len(flat) - n_state)
    out_specs = jax.tree_util.tree_unflatten(treedef, out_flat)
    return shard_map(mapped_body, mesh=mesh, in_specs=(d_specs, s_specs),
                     out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# compiled chunk builders
# ---------------------------------------------------------------------------

def als_chunk_fn(opts: "p2.Parafac2Options", length: int) -> Callable:
    """The raw ``(data, state) -> (state, fits[length])`` chunk body:
    ``lax.scan`` over `length` ALS iterations, fit history as the scan ys.
    The dry-run lowers this directly; :func:`make_als_chunk` compiles it."""

    def chunk(d, s):
        def body(c, _):
            c2 = p2.als_step(d, c, opts)
            return c2, c2.fit
        return lax.scan(body, s, None, length=length)

    return chunk


def make_als_chunk(data, opts: "p2.Parafac2Options", length: int,
                   *, donate: Optional[bool] = None) -> Callable:
    """Compiled ``state -> (state, fits[length])``: `length` ALS iterations
    in one dispatch (``lax.scan``), fit history as the scan ys. For
    ``opts.engine == "mesh"`` the scan body runs inside shard_map with the
    data split over the subject axes."""
    return _compile(als_chunk_fn(opts, length), data, opts, donate=donate)


def make_als_while(data, opts: "p2.Parafac2Options", max_iters: int,
                   tol: float, *, donate: Optional[bool] = None) -> Callable:
    """Compiled ``state -> (state, hist[max_iters], n_iters)``: the whole
    fitting loop as ONE dispatch with on-device tol-based convergence —
    ``lax.while_loop`` with the host loop's exact stopping rule (stop after
    the first iteration ``i > 0`` with ``|fit_i - fit_{i-1}| < tol``)."""

    def run(d, s):
        hist0 = jnp.full((max_iters,), -jnp.inf, opts.dtype)

        def cond(carry):
            _, _, i, _, stop = carry
            return (i < max_iters) & ~stop

        def body(carry):
            s, hist, i, prev, _ = carry
            s2 = p2.als_step(d, s, opts)
            f = s2.fit.astype(hist.dtype)
            hist = lax.dynamic_update_index_in_dim(hist, f, i, 0)
            stop = (i > 0) & (jnp.abs(f - prev) < tol)
            return (s2, hist, i + 1, f, stop)

        init = (s, hist0, jnp.asarray(0, jnp.int32),
                jnp.asarray(-jnp.inf, opts.dtype), jnp.asarray(False))
        s, hist, n, _, _ = lax.while_loop(cond, body, init)
        return s, hist, n

    return _compile(run, data, opts, donate=donate)


def _compile(fn, data, opts, *, donate: Optional[bool]) -> Callable:
    """jit (and, for the mesh engine, shard_map) a (data, state) -> ... body;
    returns a state-only callable with `data` bound.

    Every engine passes the data as a runtime ARGUMENT, like the host loop's
    jitted step (:func:`repro.core.parafac2.fit`): a closed-over dataset
    would be embedded in the compiled program as constants (gigabytes at
    cohort scale, held on the device twice), and under shard_map it would
    be replicated per shard, double-counting every psum."""
    if opts.engine == "mesh":
        mapped = None

        def call(d, s):
            nonlocal mapped
            if mapped is None:
                mapped = jax.jit(mesh_wrap(fn, d, s),
                                 donate_argnums=_donate(donate, argnum=1))
            return mapped(d, s)

        return lambda s: call(data, s)

    jitted = jax.jit(fn, donate_argnums=_donate(donate, argnum=1))
    return lambda s: jitted(data, s)


def make_subject_update(opts: "p2.Parafac2Options", *, smooth_lam: float = 0.0,
                        inner_iters: int = 1) -> Callable:
    """Compiled ``(batch, H, V, w_init, w_prev, prev_mask) -> (W, resid)``
    incremental-subject dispatch (:func:`repro.core.parafac2.update_subjects`).

    Unlike the fitting chunks, the DATA is a runtime argument here: the
    streaming service re-dispatches the same compiled program on every
    request batch, so the batch must not be baked in as a constant. jit's
    cache keys on the batch pytree structure + shapes — a service that pins
    its batch geometry (``repro.sparse.bucketing.fixed_plan`` + constant
    ``Bucketed`` aux metadata) compiles exactly once per (geometry, format)
    and every later flush is a cache hit.
    """

    def f(batch, H, V, w_init, w_prev, prev_mask):
        return p2.update_subjects(
            batch, H, V, opts, w_init=w_init, w_prev=w_prev,
            prev_mask=prev_mask, smooth_lam=smooth_lam,
            inner_iters=inner_iters)

    return jax.jit(f)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def fit_device(
    data,
    opts: "p2.Parafac2Options",
    *,
    max_iters: int = 100,
    tol: float = 1e-6,
    seed: int = 0,
    verbose: bool = False,
    state: Optional["p2.Parafac2State"] = None,
) -> Tuple["p2.Parafac2State", List[float]]:
    """Device-resident fitting loop (the ``engine="scan"|"mesh"`` halves of
    :func:`repro.core.parafac2.fit`; same signature and return contract)."""
    if opts.engine not in ENGINES:
        raise ValueError(f"unknown engine {opts.engine!r}; choose from {ENGINES}")
    if opts.engine == "host":
        raise ValueError("fit_device handles the device engines; "
                         "engine='host' is parafac2.fit's own loop")
    if opts.compress not in ("", "none"):
        # direct callers: the compression pass is host-side preprocessing
        # and lives ABOVE the engines — parafac2.fit compresses, then calls
        # back here with compress="none" on the core dataset.
        raise ValueError(
            f"fit_device runs the core ALS only (compress={opts.compress!r}); "
            f"route compressed fits through repro.core.parafac2.fit")
    if state is None:
        state = p2.init_state(data, opts, seed)

    if opts.check_every <= 0:
        # while_loop variant: one dispatch, on-device convergence
        run = make_als_while(data, opts, max_iters, tol)
        with span("fit.dispatch"):
            state, hist, n = run(state)
        with span("fit.sync"):
            n = int(n)
            history = [float(f) for f in np.asarray(hist[:n])]
        if verbose:
            print(f"[engine:{opts.engine}/while] {n} iters in one dispatch, "
                  f"fit={history[-1] if history else float('nan'):.6f}")
        return state, history

    # chunked-scan variant: ceil(max_iters / check_every) dispatches, one
    # host sync per chunk. Compiled chunks are cached by length (at most two
    # lengths: check_every and the final remainder).
    chunks: dict = {}
    history: List[float] = []
    prev = -np.inf
    done = False
    while len(history) < max_iters and not done:
        n = min(opts.check_every, max_iters - len(history))
        if n not in chunks:
            chunks[n] = make_als_chunk(data, opts, n)
        with span("fit.dispatch"):
            state, fits = chunks[n](state)
        with span("fit.sync"):
            fits = np.asarray(fits)        # ONE device sync per chunk
        for f in fits:
            history.append(float(f))
            if len(history) > 1 and abs(f - prev) < tol:
                done = True                # stop dispatching; keep the full
            prev = f                       # chunk so history[-1] == state.fit
        if verbose:
            print(f"[engine:{opts.engine}] iter {len(history) - 1:3d}  "
                  f"fit={history[-1]:.6f}")
    return state, history
