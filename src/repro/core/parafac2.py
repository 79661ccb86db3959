"""PARAFAC2-ALS with the SPARTan MTTKRP — the paper's full fitting algorithm.

One ALS iteration (Algorithm 2 of the paper) on the bucketed CC format:

  1. Procrustes step (batched over subjects): B_k = X_k V S_k H^T,
     Q_k = polar(B_k)  (Gram-eigh by default — see procrustes.py).
  2. Project: Y_k = Q_k^T X_k  (CC: shares X_k's kept-column ids).
  3. ONE CP-ALS iteration on {Y_k} via the SPARTan mode-1/2/3 MTTKRPs; each
     factor update (H from M1, V from M2, W from M3 and its Gram) routes
     through the per-mode constraint layer (:mod:`repro.core.constraints`,
     ``opts.constraints`` — COPA-style AO-ADMM; the default reproduces the
     paper's H <- unconstrained solve, V/W <- HALS nnls bitwise, and
     ADMM-routed constraints carry their dual state in ``state.aux``);
     S_k = diag(W(k,:)).
  4. Fit = 1 - sqrt(sum_k ||X_k - Q_k H S_k V^T||^2) / ||X||_F.

Everything inside :func:`als_step` is jit/pjit-compatible; subjects shard over
the leading bucket axis (the "subjects" rule in :mod:`repro.dist.sharding`;
``launch/dryrun.py::run_parafac2_cell`` lowers this step on a production
mesh). ``mode1_reuse=True`` enables the beyond-paper optimization
Y_k V = Q_k^T (X_k V) (cached from step 1). The three MTTKRPs dispatch
through the MTTKRP router (``opts.backend``: "auto" | "jnp" — see
:mod:`repro.core.backend`), which runs each bucket on one route: the O(nnz)
SCOO segment sums, the fused Pallas kernels of a CC bucket on a TPU, or the
pure-jnp SPARTan math — per bucket, since a ``bucketize(format="auto")``
dataset mixes CC and SCOO buckets. See docs/ARCHITECTURE.md (stages 3-5 and
the SCOO stage) for the full data flow and sharding story.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Mapping, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.irregular import Bucket, Bucketed
from repro.core.backend import MttkrpBackend, get_backend
from repro.core import compress as _compress
from repro.core import constraints as cst
from repro.core.cp import normalize_columns
from repro.core.procrustes import solve_q
from repro.core.spans import span
from repro.dist.sharding import psum_subjects

__all__ = ["Parafac2State", "Parafac2Options", "STAGES", "constraints_for",
           "init_state", "als_step", "fit", "reconstruct_uk", "update_subjects",
           "w_global"]

# The ALS stages, in the order ``als_step`` runs them. Each is a
# ``jax.named_scope`` inside the step's own ``als`` scope, so every op of the
# compiled step carries ``.../als/<stage>/...`` in its HLO ``op_name``
# metadata and a profiler trace can be summed per stage. The stages that
# ``dispatch_tally`` counts are spelled as it spells them. Names stay at 12
# characters or fewer, so that an op's name with its scope stays short.
STAGES = ("procrustes_b", "polar", "project", "mode1", "h_solve", "mode2",
          "scatter", "v_solve", "ykv", "mode3", "w_solve", "fit")


class Parafac2State(NamedTuple):
    H: jax.Array          # [R, R]
    V: jax.Array          # [J, R]
    W: jax.Array          # [K, R]  (S_k = diag(W[k]))
    fit: jax.Array        # scalar, model fit in [−inf, 1]
    # opaque per-mode constraint-solver state (ADMM duals), carried across
    # iterations by every engine like any other leaf: {"h": .., "v": .., "w": ..}
    # with () for modes whose constraint is direct (none/nonneg).
    aux: Any = ()


@dataclasses.dataclass(frozen=True)
class Parafac2Options:
    rank: int
    # Per-mode constraint specs, {"v": "nonneg+l1:0.1", "w": "smooth:0.5", ...}
    # (modes "h"/"v"/"w"; missing modes unconstrained — see
    # repro.core.constraints for the spec grammar and registry). None selects
    # the legacy behaviour: nonneg on V and W as in the paper.
    constraints: Optional[Union[Mapping[str, str], Tuple]] = None
    # Preprocessing stage spec ("none" | "rsvd[:r[:p[:q]]]" | any registered
    # preprocessor — see repro.core.compress). Non-identity stages make fit()
    # compress the data first, run the UNCHANGED core ALS on the small cores,
    # and expand + residual-correct at the end.
    compress: str = "none"
    # REMOVED (was deprecated in the constraint-layer PR): the
    # pre-constraint-layer nonneg bool. Passing it raises TypeError with the
    # migration hint below; the InitVar keeps the error message better than
    # a bare "unexpected keyword argument".
    nonneg: dataclasses.InitVar[Optional[bool]] = None
    procrustes: str = "gram_eigh"       # "svd" | "gram_eigh" | "newton_schulz"
    mode1_reuse: bool = True            # beyond-paper: reuse X_k V from step 1
    nnls_sweeps: int = 5
    # inner AO-ADMM iterations per factor update (admm-routed constraints;
    # warm-started duals make a handful sufficient — COPA §3)
    admm_iters: int = 10
    # Tikhonov damping added to every factor update's R x R Gram
    # (A + ridge*I). 0.0 — the default — is a STATIC no-op: the term is
    # gated at trace time, so the emitted HLO (and therefore the fit
    # trajectory) is bitwise the historical one. The fault supervisor
    # (repro.dist.supervisor) raises it on its tightened-regularization
    # retry after repeated numerical-health rollbacks.
    ridge: float = 0.0
    dtype: Any = jnp.float32
    # MTTKRP backend: "auto" (each bucket on one route: SCOO buckets on the
    # O(nnz) segment sums, CC buckets on the fused Pallas kernels on a TPU
    # at kernel-friendly geometry, jnp otherwise) or "jnp" (the pure-jnp
    # spartan math on every bucket, the exact reference). See
    # repro.core.backend.
    backend: str = "auto"
    # Compute precision for the streamed operands: "f32" (default — bitwise
    # the historical behaviour), "bf16" or "f16" (slab values staged
    # half-width, every dot still accumulates f32 via accum_dtype; pairs
    # with dtype=f32 factors). See repro.kernels.common.
    precision: str = "f32"
    # W layout: "global" [K,R] (simple, interpretable) or "bucketed" (tuple of
    # per-bucket [Kb,R] rows aligned with the data shards — no W gathers under
    # pjit; the layout production runs use, §Perf 'bucketed W').
    w_layout: str = "global"
    # Execution engine for fit() (see repro.core.engine):
    #   "host"  — one jitted als_step dispatch per iteration, host-side
    #             convergence check (the exact reference loop);
    #   "scan"  — device-resident lax.scan over chunks of `check_every`
    #             iterations per dispatch, donated state carry, fit history
    #             accumulated on device (convergence checked per chunk);
    #   "mesh"  — the scanned step additionally wrapped in shard_map over the
    #             subjects bucket axis (explicit psums at the cross-subject
    #             reductions; dist.sharding.subject_mesh_axes picks the axes).
    engine: str = "host"
    # Iterations per device dispatch for the scan/mesh engines. 0 selects the
    # lax.while_loop variant: the whole run is ONE dispatch with the tol
    # check evaluated on device (exact host stopping semantics).
    check_every: int = 10

    def __post_init__(self, nonneg):
        if nonneg is not None:
            raise TypeError(
                "Parafac2Options(nonneg=...) was removed (it shipped one "
                "release as a DeprecationWarning shim); migrate to "
                "constraints={'v': 'nonneg', 'w': 'nonneg'} for nonneg=True "
                "or {'v': 'none', 'w': 'none'} for nonneg=False")
        if self.constraints is not None:
            # normalize to a hashable, canonically ordered tuple of pairs
            object.__setattr__(
                self, "constraints", tuple(sorted(dict(self.constraints).items())))
        # fail fast on a bad preprocessing spec (ValueError listing the
        # registered preprocessors), exactly like constraint specs do
        _compress.parse_preprocess_spec(self.compress)
        if self.ridge < 0.0:
            raise ValueError(f"ridge must be >= 0, got {self.ridge}")
        from repro.kernels.common import PRECISIONS
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"unknown precision {self.precision!r}; "
                f"choose from {PRECISIONS}")
        if self.precision != "f32" and jnp.dtype(self.dtype) == jnp.float64:
            raise ValueError(
                "precision='bf16'/'f16' casts the streamed operands below "
                "the requested f64 factor dtype; use dtype=float32 with "
                "reduced precision, or precision='f32' with f64")

    def constraint_specs(self) -> Dict[str, str]:
        """Resolved per-mode constraint specs (``constraints=None`` keeps the
        paper's nonnegative V/W default)."""
        if self.constraints is not None:
            return dict(self.constraints)
        return {"v": "nonneg", "w": "nonneg"}


def constraints_for(opts: Parafac2Options) -> Dict[str, cst.Constraint]:
    """Parsed per-mode :class:`repro.core.constraints.Constraint` bundle for
    ``opts`` (parse results are cached on the spec strings), with the
    layout/constraint compatibility checks applied."""
    cons = cst.bundle(opts.constraint_specs())
    if opts.w_layout == "bucketed" and cons["w"].smooth_lam:
        raise ValueError(
            "constraint 'smooth' on mode 'w' couples W rows across subjects "
            "and needs w_layout='global' (the bucketed layout splits rows "
            "across data shards)")
    return cons


def init_state(data: Bucketed, opts: Parafac2Options, seed: int = 0) -> Parafac2State:
    """H = I, V random (nonneg if constrained), W = 1 — Kiers-style init.

    ADMM-routed constraints get their ``(Z, U)`` dual state initialized here
    so the carried ``aux`` pytree has a static structure for the engines.
    """
    R = opts.rank
    cons = constraints_for(opts)
    key = jax.random.PRNGKey(seed)
    H = jnp.eye(R, dtype=opts.dtype)
    if cons["v"].nonneg:
        V = jax.random.uniform(key, (data.n_cols, R), opts.dtype)
    else:
        V = jax.random.normal(key, (data.n_cols, R), opts.dtype)
    if opts.w_layout == "bucketed":
        W = tuple(jnp.ones((b.kb, R), opts.dtype) * b.subject_mask[:, None]
                  for b in data.buckets)
    else:
        W = jnp.ones((data.n_subjects, R), opts.dtype)
    if isinstance(W, tuple):
        # per-bucket aux (a LIST, so pytree structure distinguishes it from
        # the global layout's single (Z, U) pair)
        aux_w = [cons["w"].init_aux(wb) for wb in W] if cons["w"].admm else ()
    else:
        aux_w = cons["w"].init_aux(W)
    aux = {"h": cons["h"].init_aux(H), "v": cons["v"].init_aux(V), "w": aux_w}
    return Parafac2State(H=H, V=V, W=W, fit=jnp.asarray(-jnp.inf, opts.dtype),
                         aux=aux)


def _w_rows(W, b: Bucket, i: int):
    """W rows for bucket i (no gather in the bucketed layout)."""
    if isinstance(W, tuple):
        return W[i]
    return jnp.take(W, b.subject_ids, axis=0)


def _w_gram(W):
    if isinstance(W, tuple):
        # bucketed W is sharded with the data: the gram is a cross-subject
        # reduction (global W is replicated, so no psum on that branch)
        return psum_subjects(sum(wb.T @ wb for wb in W))
    return W.T @ W


def w_global(data: Bucketed, W) -> jnp.ndarray:
    """Assemble a global [K, R] W from either layout (interpretation)."""
    if not isinstance(W, tuple):
        return W
    R = W[0].shape[1]
    out = jnp.zeros((data.n_subjects, R), W[0].dtype)
    for b, wb in zip(data.buckets, W):
        out = out.at[b.subject_ids].add(wb * b.subject_mask[:, None])
    return out


def _ridged(A: jax.Array, opts: Parafac2Options) -> jax.Array:
    """A + ridge*I on an R x R Gram; trace-time no-op at ridge == 0 (the
    default emits the identical HLO — bitwise-safe)."""
    if opts.ridge:
        return A + jnp.asarray(opts.ridge, A.dtype) * jnp.eye(
            A.shape[-1], dtype=A.dtype)
    return A


def _procrustes_project(
    b: Bucket, H: jax.Array, V: jax.Array, W: jax.Array, opts: Parafac2Options,
    i: int = 0, be: Optional[MttkrpBackend] = None,
) -> Tuple[Any, jax.Array, jax.Array]:
    """Steps 1+2 for one bucket -> (proj, XkV, Q).

    ``proj`` is the backend's per-bucket projected representation
    (:meth:`MttkrpBackend.project_bucket`): the compact Yc [Kb, R, C] on the
    jnp route, Q itself on the SCOO and fused routes (where Y_k is
    never materialized). ``als_step`` only ever hands it back to the same
    backend.
    """
    be = get_backend(opts.backend, opts.precision) if be is None else be
    with jax.named_scope("procrustes_b"):
        Vg = b.gather_v(V)                                   # [Kb, C, R]
        Wb = _w_rows(W, b, i)                                # [Kb, R]
        # B_k = X_k V S_k H^T  == (XkV * w_k) @ H^T — one fused slab pass on
        # the fused route, xkv + a small einsum on the others
        XkV, B = be.procrustes_b_bucket(b, H, Wb, V, Vg)     # [Kb, I, R] x2
    with jax.named_scope("polar"):
        Q = solve_q(B, opts.procrustes)                      # [Kb, I, R]
        Q = be.shard_subjects(Q * b.subject_mask[:, None, None])
    with jax.named_scope("project"):
        proj = be.project_bucket(b, Q)
    return proj, XkV, Q


def _f32_matmuls(fn):
    """Trace ``fn`` with f32 matmul precision. A TPU runs an f32 matmul as a
    single bf16 pass by default; at that precision the Procrustes Gram
    spectra and the fit identity (which assumes Q_k^T Q_k = I) break down,
    and on a v5e the fit read 1.0 from the first iteration. Sub-f32
    ``opts.precision`` casts its operands, which this leaves as they are."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)

    return wrapped


@_f32_matmuls
@jax.named_scope("als")
def als_step(
    data: Bucketed,
    state: Parafac2State,
    opts: Parafac2Options,
) -> Parafac2State:
    """One full PARAFAC2-ALS iteration (jit-compatible).

    Every factor update routes through the per-mode constraint bundle
    (:func:`constraints_for`); ADMM-routed constraints read and write their
    dual state in ``state.aux`` — the engines carry it like any other leaf.
    Each stage runs under its name in :data:`STAGES`, inside one ``als``
    scope; each ``psum_subjects`` sits in the stage whose sum it reduces.
    """
    H, V, W = state.H, state.V, state.W
    R, J, K = opts.rank, data.n_cols, data.n_subjects
    be = get_backend(opts.backend, opts.precision)
    cons = constraints_for(opts)
    solve_kw = dict(nnls_sweeps=opts.nnls_sweeps, admm_iters=opts.admm_iters)
    aux = state.aux if isinstance(state.aux, dict) else cst.empty_aux()

    bucketed = isinstance(W, tuple)

    def scale_w(W, norms):
        if isinstance(W, tuple):
            return tuple(wb * norms[None, :] for wb in W)
        return W * norms[None, :]

    # ---- 1+2: Procrustes + projection, per bucket --------------------------
    per_bucket = [_procrustes_project(b, H, V, W, opts, i, be)
                  for i, b in enumerate(data.buckets)]

    # ---- 3a: H update (mode-1 MTTKRP) --------------------------------------
    with jax.named_scope("mode1"):
        M1 = jnp.zeros((R, R), opts.dtype)
        for i, (b, (proj, XkV, Q)) in enumerate(zip(data.buckets, per_bucket)):
            Wb = _w_rows(W, b, i)
            if opts.mode1_reuse:
                # Y_k V = Q_k^T (X_k V): skip the gather+matmul on sparse
                # data (the fused route reduces M1 in the same dispatch that
                # forms YkV)
                M1 = M1 + be.mode1_xkv_bucket(b, Q, XkV, Wb)
            else:
                M1 = M1 + be.mode1_bucket(b, proj, Wb, V)
        M1 = psum_subjects(M1)
    with jax.named_scope("h_solve"):
        H_new, aux_h = cons["h"].update(
            M1, _ridged(_w_gram(W) * (V.T @ V), opts), H, aux["h"],
            **solve_kw)
        aux_w = aux["w"]
        if not cons["h"].penalized:
            # absorb scale into W (model-invariant for indicator constraints;
            # penalized modes keep their natural scale — see
            # Constraint.penalized)
            H_new, h_norms = normalize_columns(H_new)
            aux_h = cst.scale_aux(aux_h, 1.0 / jnp.maximum(h_norms, 1e-12))
            W = scale_w(W, h_norms)
            aux_w = cst.scale_aux(aux_w, h_norms)

    # ---- 3b: V update (mode-2 MTTKRP) --------------------------------------
    M2 = jnp.zeros((J, R), opts.dtype)
    for i, (b, (proj, _, _)) in enumerate(zip(data.buckets, per_bucket)):
        with jax.named_scope("mode2"):
            Wb = _w_rows(W, b, i)
            A = be.mode2_bucket(b, proj, H_new, Wb)
        with jax.named_scope("scatter"):
            M2 = M2 + be.mode2_scatter(A, b.scatter).astype(M2.dtype)
    with jax.named_scope("scatter"):
        M2 = psum_subjects(M2)
    with jax.named_scope("v_solve"):
        V_new, aux_v = cons["v"].update(
            M2, _ridged(_w_gram(W) * (H_new.T @ H_new), opts), V,
            aux["v"], **solve_kw)
        if not cons["v"].penalized:
            V_new, v_norms = normalize_columns(V_new)
            aux_v = cst.scale_aux(aux_v, 1.0 / jnp.maximum(v_norms, 1e-12))
            W = scale_w(W, v_norms)
            aux_w = cst.scale_aux(aux_w, v_norms)

    # ---- 3c: W update (mode-3 MTTKRP) --------------------------------------
    with jax.named_scope("w_solve"):
        VtV = V_new.T @ V_new
        gram3 = _ridged(VtV * (H_new.T @ H_new), opts)
    rows_per_bucket = []
    Gs = []   # G_k = Y_k V_new per bucket, shared with the fit computation
    for b, (proj, _, _) in zip(data.buckets, per_bucket):
        with jax.named_scope("ykv"):
            G = be.ykv_bucket(b, proj, V_new)
        Gs.append(G)
        with jax.named_scope("mode3"):
            rows_per_bucket.append(
                be.mode3_bucket(b, proj, H_new, YkV=G))
    if bucketed:
        # per-bucket W rows update in place — no K-wide scatter, no gathers;
        # per-bucket aux rides in a list aligned with the buckets
        with jax.named_scope("w_solve"):
            aux_w_list = (aux_w if isinstance(aux_w, list)
                          else [() for _ in data.buckets])
            upd = [cons["w"].update(rows.astype(wb.dtype), gram3, wb, awb,
                                    **solve_kw)
                   for rows, wb, awb in zip(rows_per_bucket, W, aux_w_list)]
            W_new = tuple(wn * b.subject_mask[:, None]
                          for (wn, _), b in zip(upd, data.buckets))
            aux_w = [a for _, a in upd] if cons["w"].admm else ()
    else:
        with jax.named_scope("mode3"):
            M3 = jnp.zeros((K, R), opts.dtype)
            for b, rows in zip(data.buckets, rows_per_bucket):
                M3 = M3.at[b.subject_ids].add(rows.astype(M3.dtype))
            M3 = psum_subjects(M3)
        with jax.named_scope("w_solve"):
            W_new, aux_w = cons["w"].update(M3, gram3, W, aux_w, **solve_kw)

    # ---- 4: fit ------------------------------------------------------------
    # ||X_k - Q_k H S_k V^T||^2 = ||X||^2 - 2 tr(S H^T G_k) + tr(S Φ S V^T V),
    # with G_k = Y_k V_new and Φ = H^T H — all R x R algebra.
    with jax.named_scope("fit"):
        Phi = H_new.T @ H_new
        delta = jnp.zeros((), opts.dtype)
        for i, b in enumerate(data.buckets):
            G = Gs[i]                                          # [Kb, R, R]
            Wb = _w_rows(W_new, b, i)                          # [Kb, R]
            cross = jnp.einsum("rl,krl,kl,k->", H_new, G, Wb, b.subject_mask)
            model = jnp.einsum("rl,rl,kr,kl,k->", Phi, VtV, Wb, Wb,
                               b.subject_mask)
            delta = delta - 2.0 * cross + model
        resid = jnp.asarray(data.norm_sq, opts.dtype) + psum_subjects(delta)
        fit_val = 1.0 - jnp.sqrt(jnp.maximum(resid, 0.0)) / jnp.sqrt(
            jnp.asarray(data.norm_sq, opts.dtype))

    return Parafac2State(H=H_new, V=V_new, W=W_new, fit=fit_val,
                         aux={"h": aux_h, "v": aux_v, "w": aux_w})


def fit(
    data: Bucketed,
    opts: Parafac2Options,
    *,
    max_iters: int = 100,
    tol: float = 1e-6,
    seed: int = 0,
    verbose: bool = False,
    state: Optional[Parafac2State] = None,
) -> Tuple[Parafac2State, List[float]]:
    """Full fitting loop with fit-change convergence.

    ``opts.compress`` (a :mod:`repro.core.compress` spec) runs the whole loop
    on randomized small cores: compress -> this same function with
    ``compress="none"`` on the core dataset -> exact expand + residual
    correction. ``opts.engine`` picks the execution engine: "host" is the
    reference loop below (one jitted dispatch + one device sync per
    iteration); "scan" and "mesh" run device-resident compiled chunks (see
    :mod:`repro.core.engine`).
    """
    if not _compress.parse_preprocess_spec(opts.compress).identity:
        return _compress.fit_compressed(data, opts, max_iters=max_iters,
                                        tol=tol, seed=seed, verbose=verbose,
                                        state=state)
    if opts.engine != "host":
        from repro.core import engine as _engine
        return _engine.fit_device(data, opts, max_iters=max_iters, tol=tol,
                                  seed=seed, verbose=verbose, state=state)
    if state is None:
        state = init_state(data, opts, seed)
    # one iteration per dispatch, compiled as the body of a loop whose trip
    # count is a runtime argument: XLA then compiles the step as it does the
    # scan and while engines' loop bodies (the data-only work hoisted out of
    # the body), which keeps the engines' trajectories bitwise equal
    step = jax.jit(lambda d, s, n: jax.lax.fori_loop(
        0, n, lambda _, c: als_step(d, c, opts), s))
    history: List[float] = []
    prev = -np.inf
    for it in range(max_iters):
        with span("fit.dispatch"):
            state = step(data, state, 1)
        with span("fit.sync"):
            f = float(state.fit)
        history.append(f)
        if verbose:
            print(f"iter {it:3d}  fit={f:.6f}")
        if it > 0 and abs(f - prev) < tol:
            break
        prev = f
    return state, history


@_f32_matmuls
def update_subjects(
    batch: Bucketed,
    H: jax.Array,
    V: jax.Array,
    opts: Parafac2Options,
    *,
    w_init: Optional[jax.Array] = None,
    w_prev: Optional[jax.Array] = None,
    prev_mask: Optional[jax.Array] = None,
    smooth_lam: float = 0.0,
    inner_iters: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """Incremental per-subject solve with the factors ``H``/``V`` FIXED.

    This is the streaming/serving entry point (ROADMAP item 1, the tPARAFAC2
    append workload): given a fitted model, a new or touched subject only
    needs its own Procrustes basis ``Q_k`` and its own W row — both
    independent across subjects, so a request batch is ONE padded, jitted
    dispatch. Per inner iteration (all batched over subjects, per bucket,
    through the same bucket-level backend stages ``als_step`` uses — the
    CC/SCOO format split is free):

      1. ``B_k = X_k V S_k H^T``, ``Q_k = polar(B_k)``   (Procrustes at the
         current w_k; ``w_init`` on the first pass),
      2. ``G_k = Y_k V`` and the mode-3 MTTKRP row, then the W-row solve
         through ``opts``' "w" constraint — exactly the ``als_step`` stage-3c
         update (with ``smooth_lam == 0`` and ``inner_iters == 1`` this IS
         that stage, on a batch holding only the touched subjects).

    ``smooth_lam > 0`` adds the tPARAFAC2-style temporal anchor
    ``lam * ||w_k - w_k^prev||^2`` for subjects with a previous row
    (``prev_mask``): a quadratic penalty folds EXACTLY into the normal
    equations (``M += lam w_prev``, ``A += lam I``), so every solver route
    (ridge/HALS/ADMM) stays exact — but ``A`` becomes per-subject, so that
    branch solves rows under ``vmap``. New subjects (mask 0) are unpenalized.

    ADMM-routed W constraints start from fresh duals here (requests are
    independent one-shot solves; there is no outer ALS loop to warm-start
    across) — raise ``opts.admm_iters`` if a tight ADMM solve matters.

    Returns ``(W_rows [batch.n_subjects, R], resid [batch.n_subjects])``
    where ``resid[k] = ||X_k - Q_k H S_k V^T||_F^2`` at the returned row
    (same algebra as the ``als_step`` fit, per subject) — the streaming
    service's drift tracker sums these into an exact union-dataset fit.
    Jit-compatible; compile once per batch geometry via
    :func:`repro.core.engine.make_subject_update`.
    """
    if inner_iters < 1:
        raise ValueError(f"inner_iters must be >= 1, got {inner_iters}")
    R = opts.rank
    be = get_backend(opts.backend, opts.precision)
    cons_w = constraints_for(opts)["w"]
    solve_kw = dict(nnls_sweeps=opts.nnls_sweeps, admm_iters=opts.admm_iters)
    VtV = V.T @ V
    Phi = H.T @ H
    gram3 = VtV * Phi                                     # [R, R]

    if w_init is None:
        w_init = jnp.ones((batch.n_subjects, R), opts.dtype)
    if w_prev is None:
        w_prev = jnp.zeros((batch.n_subjects, R), opts.dtype)
    if prev_mask is None:
        prev_mask = jnp.zeros((batch.n_subjects,), opts.dtype)

    def _row_solve(rows, wb, prevb, pmaskb):
        """The stage-3c W solve for one bucket's rows [Kb, R]."""
        if smooth_lam <= 0.0:
            wn, _ = cons_w.update(rows.astype(wb.dtype), gram3, wb, (),
                                  **solve_kw)
            return wn
        # temporal anchor: per-subject lam_k = smooth_lam * has_prev, folded
        # into the normal equations -> per-subject Gram, vmapped row solves
        lam_k = jnp.asarray(smooth_lam, wb.dtype) * pmaskb        # [Kb]
        M = rows.astype(wb.dtype) + lam_k[:, None] * prevb        # [Kb, R]
        eye = jnp.eye(R, dtype=wb.dtype)
        A = gram3.astype(wb.dtype)[None] + lam_k[:, None, None] * eye  # [Kb,R,R]

        def one(m, a, w0):
            x, _ = cons_w.update(m[None, :], a, w0[None, :], (), **solve_kw)
            return x[0]

        return jax.vmap(one)(M, A, prevb * pmaskb[:, None] +
                             wb * (1.0 - pmaskb)[:, None])

    # maintain the batch rows as a per-bucket tuple (the _w_rows layout)
    wbs = [jnp.take(w_init, b.subject_ids, axis=0) * b.subject_mask[:, None]
           for b in batch.buckets]
    Gs: List[jax.Array] = [None] * len(batch.buckets)
    for _ in range(inner_iters):
        Wt = tuple(wbs)
        for i, b in enumerate(batch.buckets):
            proj, _, _ = _procrustes_project(b, H, V, Wt, opts, i, be)
            G = be.ykv_bucket(b, proj, V)                 # [Kb, R, R]
            Gs[i] = G
            rows = be.mode3_bucket(b, proj, H, YkV=G)     # [Kb, R]
            prevb = jnp.take(w_prev, b.subject_ids, axis=0)
            pmaskb = jnp.take(prev_mask, b.subject_ids, axis=0) * b.subject_mask
            wbs[i] = _row_solve(rows, wbs[i], prevb, pmaskb) \
                * b.subject_mask[:, None]

    # per-subject residual at the final rows (Q from the last Procrustes —
    # the same staleness convention as the als_step fit)
    W_out = jnp.zeros((batch.n_subjects, R), opts.dtype)
    resid = jnp.zeros((batch.n_subjects,), opts.dtype)
    for b, wb, G in zip(batch.buckets, wbs, Gs):
        sq = b.sq_norms().astype(opts.dtype)
        cross = jnp.einsum("rl,krl,kl->k", H, G, wb).astype(opts.dtype)
        model = jnp.einsum("rl,rl,kr,kl->k", Phi, VtV, wb, wb).astype(opts.dtype)
        rb = (sq - 2.0 * cross + model) * b.subject_mask.astype(opts.dtype)
        W_out = W_out.at[b.subject_ids].add(
            wb.astype(opts.dtype) * b.subject_mask[:, None].astype(opts.dtype))
        resid = resid.at[b.subject_ids].add(rb)
    return W_out, resid


def reconstruct_uk(
    data: Bucketed, state: Parafac2State, opts: Parafac2Options
) -> Dict[int, np.ndarray]:
    """Assemble U_k = Q_k H per subject (host-side, for interpretation)."""
    out: Dict[int, np.ndarray] = {}
    for i, b in enumerate(data.buckets):
        _, _, Q = _procrustes_project(b, state.H, state.V, state.W, opts, i)
        Uk = np.asarray(jnp.einsum("kir,rl->kil", Q, state.H))
        sids = np.asarray(b.subject_ids)
        smask = np.asarray(b.subject_mask)
        rows = np.asarray(b.row_counts)
        for slot in range(b.kb):
            if smask[slot] > 0:
                out[int(sids[slot])] = Uk[slot, : rows[slot], :]
    return out
