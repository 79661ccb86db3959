"""Named-axis sharding rules and the logical->mesh resolution machinery.

Model code annotates arrays with LOGICAL axis names (``"batch"``, ``"heads"``,
``"subjects"``, ...) via :func:`shard`; a rule table maps each logical name to
zero or more PHYSICAL mesh axes (``"pod"``, ``"data"``, ``"model"``). The
mapping is installed with the :func:`axis_rules` context manager, so the same
model code lowers unsharded on one CPU device (tests), data-parallel on a
small host-device mesh, or fully sharded on a production pod — with no code
changes, only a different ``(rules, mesh)`` pair.

Rule tables
-----------
``LM_RULES`` is the standard megatron-style layout: batch-like axes over the
data-parallel axes ``("pod", "data")``, head/ffn/vocab/expert axes over
``"model"`` (tensor/expert parallelism), residual stream replicated over
``"model"``. ``SP_RULES`` additionally shards the residual-stream sequence
axis ``"seq_res"`` over ``"model"`` (sequence parallelism: norms and
elementwise work also parallelize over ``"model"``, at the cost of
all-gathers at each block boundary).

The ``"subjects"`` axis is the PARAFAC2 workload: SPARTan's per-subject
partial MTTKRP results are plain adds over this axis, so constraining it onto
the mesh makes the bucket reductions lower to all-reduces (the paper's "sum
partial results in parallel"); :mod:`repro.core.backend` applies the
constraint uniformly around the MTTKRP math. It maps to EVERY
mesh axis — the decomposition has no tensor-parallel dimension, so leaving
``"model"`` idle would waste its memory and compute (subject-wide sharding;
see ``launch/dryrun.py::parafac2_shardings``).

Parameter sharding is PATH-based, not shape-based: :func:`param_spec` matches
the pytree path of each leaf ("attn/wq", "mlp/w_down", "embed/tokens", ...)
and returns a :class:`~jax.sharding.PartitionSpec` that puts the contraction
or output dimension on ``"model"`` and the complementary dimension on the
fsdp axis ``"data"`` (ZeRO-style: optimizer state flattens through the same
paths, so it partitions identically for free — see ``optim/adamw.py``).

Every resolved spec passes through :func:`enforce_divisible`, which silently
replicates any dimension a mesh axis does not divide evenly — annotations are
best-effort hints, never hard failures.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "LM_RULES",
    "SP_RULES",
    "axis_rules",
    "current_mesh",
    "current_rules",
    "enforce_divisible",
    "logical_spec",
    "make_mesh",
    "param_shardings",
    "param_spec",
    "barrier",
    "psum_subjects",
    "shard",
    "subject_collectives",
    "subject_mesh_axes",
    "unroll_active",
    "unroll_loops",
]

# One rule table entry: logical axis name -> mesh axis name(s) or None.
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

_DP = ("pod", "data")   # data-parallel mesh axes (pod absent on 1-pod meshes)

LM_RULES: Rules = {
    # batch-like axes: data-parallel
    "batch": _DP,
    "tokens": _DP,          # flattened [B*S(*k)] token axes (moe dispatch)
    # PARAFAC2 subjects: subject-wide — over every axis incl. "model"
    "subjects": ("pod", "data", "model"),
    # residual stream: replicated over "model" (megatron TP)
    "seq": None,
    "seq_res": None,
    "embed": None,
    # tensor-parallel axes
    "heads": "model",
    "mlp": "model",
    "vocab": "model",
    # expert-parallel axes
    "experts": "model",
    "expert_cap": "model",  # flattened [E*capacity] dispatch buffers
}

# Sequence-parallel variant: the residual stream's seq axis also shards over
# "model" between blocks (attention/mlp still gather seq internally).
SP_RULES: Rules = {**LM_RULES, "seq_res": "model"}


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``. The rule tables resolve
    through GSPMD ``with_sharding_constraint``, which refuses the
    ``Explicit`` axes ``jax.make_mesh`` builds by default."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


# ---------------------------------------------------------------------------
# context stack: (rules, mesh) pairs + the scan-unrolling switch
# ---------------------------------------------------------------------------

class _Ctx(threading.local):
    def __init__(self):
        self.stack = []     # [(rules, mesh), ...]
        self.unroll = 0
        self.collective = []  # [axis_names, ...] — inside shard_map bodies


_CTX = _Ctx()


@contextlib.contextmanager
def axis_rules(rules: Rules, mesh: Optional[Mesh] = None):
    """Install a (rules, mesh) pair for :func:`shard` / :func:`logical_spec`."""
    _CTX.stack.append((rules, mesh))
    try:
        yield
    finally:
        _CTX.stack.pop()


def current_rules() -> Optional[Rules]:
    return _CTX.stack[-1][0] if _CTX.stack else None


def current_mesh() -> Optional[Mesh]:
    return _CTX.stack[-1][1] if _CTX.stack else None


@contextlib.contextmanager
def unroll_loops():
    """Unroll `lax.scan` layer/kv-block loops while active.

    XLA cost analysis counts a while-loop body ONCE regardless of trip count,
    so the dry-run's roofline probes lower fully unrolled models; training
    and tests keep the compact scanned HLO.
    """
    _CTX.unroll += 1
    try:
        yield
    finally:
        _CTX.unroll -= 1


def unroll_active() -> bool:
    return _CTX.unroll > 0


# ---------------------------------------------------------------------------
# manual-collective mode (shard_map bodies)
# ---------------------------------------------------------------------------

def subject_mesh_axes(mesh: Mesh, rules: Optional[Rules] = None) -> Tuple[str, ...]:
    """Mesh axes the "subjects" logical axis resolves to on `mesh` (the axes a
    shard_map over subjects maps manually, and psums reduce over)."""
    rules = rules if rules is not None else (current_rules() or LM_RULES)
    entry = rules.get("subjects")
    if entry is None:
        return ()
    names = entry if isinstance(entry, tuple) else (entry,)
    return tuple(n for n in names if n in mesh.axis_names)


@contextlib.contextmanager
def subject_collectives(axis_names: Sequence[str]):
    """Mark the enclosed trace as a shard_map body manually mapped over the
    subjects axis: :func:`psum_subjects` becomes ``lax.psum`` over
    `axis_names`, and :func:`shard` constraints become no-ops (inside
    shard_map the mesh axes are already manual — ``with_sharding_constraint``
    over them is meaningless). The mesh execution engine
    (:mod:`repro.core.engine`) enters this around the scanned ALS step.
    """
    _CTX.collective.append(tuple(axis_names))
    _CTX.stack.append((None, None))   # suppress shard() inside the body
    try:
        yield
    finally:
        _CTX.stack.pop()
        _CTX.collective.pop()


def psum_subjects(x: jax.Array) -> jax.Array:
    """Cross-subject reduction hook: identity under pjit/GSPMD (sharding
    constraints make XLA insert the all-reduces), an explicit
    ``lax.psum`` over the subjects mesh axes inside a
    :func:`subject_collectives` (shard_map) body. The ALS step calls this on
    every value produced by a reduction over the subject axis (MTTKRP partial
    sums, W grams, fit residual terms)."""
    if not _CTX.collective:
        return x
    axes = _CTX.collective[-1]
    if not axes:
        return x
    return jax.lax.psum(x, axes)


# ---------------------------------------------------------------------------
# logical -> physical resolution
# ---------------------------------------------------------------------------

def _mesh_axis_size(mesh: Mesh, names: Sequence[str]) -> int:
    n = 1
    for nm in names:
        if nm in mesh.axis_names:
            n *= mesh.devices.shape[mesh.axis_names.index(nm)]
    return n


def _resolve_entry(entry, mesh: Optional[Mesh]):
    """Rule value -> PartitionSpec entry: filter missing mesh axes, collapse
    1-tuples to bare names, empty to None."""
    if entry is None:
        return None
    names = entry if isinstance(entry, tuple) else (entry,)
    if mesh is not None:
        names = tuple(n for n in names if n in mesh.axis_names)
    if not names:
        return None
    return names if len(names) > 1 else names[0]


def logical_spec(axes: Sequence[Optional[str]],
                 mesh: Optional[Mesh] = None) -> P:
    """Resolve logical axis names to a PartitionSpec under the current rules.

    Unknown names and names with no surviving mesh axis resolve to None
    (replicated); with no rules installed the spec is empty (fully
    replicated).
    """
    rules = current_rules()
    if rules is None:
        return P()
    mesh = mesh if mesh is not None else current_mesh()
    return P(*[_resolve_entry(rules.get(ax), mesh) if ax is not None else None
               for ax in axes])


def enforce_divisible(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Replicate every spec dimension whose mesh-axis product does not divide
    the array dimension evenly (constraints are hints, not requirements)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        size = _mesh_axis_size(mesh, names)
        out.append(entry if size <= 1 or dim % size == 0 else None)
    return P(*out)


def shard(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Constrain `x`'s sharding by logical axis names; no-op outside a mesh.

    `axes` is one logical name (or None) per array dimension. Under an active
    ``axis_rules(rules, mesh)`` context this lowers to
    ``with_sharding_constraint``; anywhere else (unit tests, single-device
    examples) it returns `x` unchanged.
    """
    mesh = current_mesh()
    if mesh is None or current_rules() is None:
        return x
    spec = enforce_divisible(logical_spec(axes, mesh), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


@jax.custom_vjp
def barrier(x: jax.Array) -> jax.Array:
    """Differentiable `lax.optimization_barrier`: pins value order against XLA
    hoisting (e.g. keeping a bf16 cast on the producer side of a dispatch
    all-gather) and, unlike the raw primitive, has a VJP — the cotangent is
    barriered the same way."""
    return jax.lax.optimization_barrier(x)


def _barrier_fwd(x):
    return barrier(x), None


def _barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


barrier.defvjp(_barrier_fwd, _barrier_bwd)


# ---------------------------------------------------------------------------
# path-based parameter sharding
# ---------------------------------------------------------------------------

# weights contracted on their LAST dim at apply time: output dim on "model"
# (column-parallel), input dim on the fsdp axis.
_COL_PARALLEL = frozenset({
    "wq", "wk", "wv", "w_gate", "w_up",
    "in_proj_z", "in_proj_x", "in_proj_B", "in_proj_C", "in_proj_dt",
    "w_in", "w_gate_branch", "wa", "wx",
    "lm_head", "patch_proj",
})
# weights whose FIRST dim is the model-sharded activation dim (row-parallel):
# input dim on "model", output dim on the fsdp axis.
_ROW_PARALLEL = frozenset({"wo", "w_down", "out_proj", "w_out"})


def param_spec(path: str, ndim: int, stacked: bool = False) -> P:
    """PartitionSpec for a parameter (or optimizer-moment) pytree leaf.

    `path` is the "/"-joined pytree path; optimizer prefixes ("m/...",
    "v/...") pass through because every rule matches on path suffixes.
    `stacked` marks scan-stacked group params (leading layer dim, never
    sharded); the remaining dims follow the unstacked rule.
    """
    lead: Tuple[Optional[str], ...] = (None,) if stacked else ()
    body = ndim - len(lead)
    leaf = path.rsplit("/", 1)[-1]
    if body <= 1:
        return P()          # scalars, biases, norm scales: replicated
    if "experts/" in path:
        # MoE expert stacks [E, d, f]: expert dim on "model" (EP), matching
        # the manual shard_map path's in_specs (models/moe.py).
        return P(*lead, "model", *([None] * (body - 1)))
    if "conv/" in path:
        # depthwise conv [W, C]: channel dim follows the activation layout
        return P(*lead, *([None] * (body - 1)), "model")
    if "embed/tokens" in path:
        # token embedding [V, d]: vocab on "model" (sharded-vocab CE), d fsdp
        return P(*lead, "model", *([None] * (body - 2)), "data")
    if leaf in _ROW_PARALLEL:
        return P(*lead, "model", *([None] * (body - 2)), "data")
    if leaf in _COL_PARALLEL:
        return P(*lead, "data", *([None] * (body - 2)), "model")
    return P()              # unknown (router gates, ...): replicated


def _key_str(entry: Any) -> str:
    """One pytree KeyEntry -> path segment (DictKey/GetAttrKey/SequenceKey)."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def param_shardings(tree: Any, mesh: Mesh) -> Any:
    """NamedShardings for a param/opt-state pytree (of arrays or
    ShapeDtypeStructs) via :func:`param_spec` on each leaf's path."""

    def visit(path, leaf):
        pathstr = "/".join(_key_str(p) for p in path)
        stacked = "groups/" in pathstr
        ndim = len(getattr(leaf, "shape", ()) or ())
        spec = param_spec(pathstr, ndim, stacked=stacked)
        spec = P(*[_resolve_entry(e, mesh) for e in spec])
        spec = enforce_divisible(spec, leaf.shape, mesh) if ndim else spec
        entries = list(spec)
        while entries and entries[-1] is None:   # P(None, None) == P()
            entries.pop()
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(visit, tree)
