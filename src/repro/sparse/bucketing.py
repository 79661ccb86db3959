"""Size-bucketing planner: ragged subjects -> a few static-shape buckets.

XLA needs static shapes. Subjects vary in row count I_k, nonzero-column
count c_k, and nonzero count nnz_k; we group them into buckets whose padded
geometry is chosen to bound padding waste while keeping the number of
distinct compiled shapes small. Pad targets are rounded up to multiples of
``row_align`` / ``col_align`` (8 / 128 by default — TPU sublane/lane quanta;
the 128 lane default is what the Pallas MTTKRP kernels' alignment assumption
and the ``auto`` backend's kernel-friendly check expect). Pass a smaller
``col_align`` explicitly for CPU-only runs where padding waste matters more
than lane alignment.

Two padding currencies, one per device format (repro.core.irregular):

* **area** — the CC format densifies each slice over its kept columns, so a
  bucket costs ``Kb * I_pad * C_pad`` cells regardless of the true nonzero
  count. ``padding_waste`` measures this.
* **nnz** — the SCOO format stores flat per-subject triplets padded to the
  bucket's ``N_pad`` (``nnz_pads``), so a bucket costs ``Kb * N_pad``
  entries. ``nnz_waste`` measures this; pass ``nnz_counts`` to plan it.

``plan_buckets`` sorts the subjects by padded row count and nonzeros and
cuts that order where :func:`slot_cost`, the estimated padded work of
both formats, sums least; ``plan_layout`` routes that plan and, where a
bucket goes to CC, bounds each bucket's footprint (:func:`slot_rows`).

``route_formats`` turns the per-bucket *density* — true nonzeros over the
densified CC cell count, the quantity that decides which format is cheaper —
into a per-bucket "cc"/"scoo" decision (the ``bucketize(format="auto")``
router).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

__all__ = ["BucketPlan", "fixed_plan", "plan_buckets", "plan_layout",
           "route_formats", "slot_cost", "SCOO_DENSITY_THRESHOLD"]


def _shard_capacities(n_members: int, n_shards: int) -> List[int]:
    """Real-subject slots per shard under ``bucketize``'s layout: the bucket
    pads Kb up to a multiple of `n_shards` with padding slots at the TAIL,
    and shard s then owns the contiguous slots [s*cs, (s+1)*cs). Shards
    0..n-2 therefore hold exactly ``cs`` real subjects; the LAST shard
    absorbs all the padding."""
    cs = -(-n_members // n_shards)            # ceil -> padded Kb / n_shards
    return [max(0, min(cs, n_members - s * cs)) for s in range(n_shards)]

# Density below which the SCOO format wins over CC for a bucket: one SCOO
# nonzero costs ~3 staged entries (val + row + col) and ~2 gathers per
# contraction vs CC's 1 dense cell, so the crossover is well above 10%;
# 0.25 keeps CC for near-dense buckets (where the MXU-shaped dense matmul
# is unbeatable) and routes genuinely sparse buckets to the O(nnz) path.
SCOO_DENSITY_THRESHOLD = 0.25


def _round_up(x: np.ndarray, align: int) -> np.ndarray:
    """Every entry rounded up to a multiple of ``align``, at least ``align``."""
    return np.maximum(align, -(-x // align) * align)


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Assignment of subject indices to padded-shape buckets."""

    # per bucket: (I_pad, C_pad) and the member subject indices
    shapes: List[tuple]          # [(I_pad, C_pad)]
    members: List[np.ndarray]    # [int32 arrays of subject ids]
    # per bucket: padded nonzero count N_pad (SCOO layout); None when the
    # plan was built without nnz_counts
    nnz_pads: Optional[List[int]] = None

    @property
    def n_buckets(self) -> int:
        return len(self.shapes)

    def padding_waste(self, row_counts: Sequence[int], col_counts: Sequence[int]) -> float:
        """Fraction of padded cells that are padding (area metric — the CC
        format's currency)."""
        used = 0
        total = 0
        for (ip, cp), mem in zip(self.shapes, self.members):
            for k in mem:
                used += int(row_counts[k]) * int(col_counts[k])
                total += ip * cp
        return 1.0 - used / max(total, 1)

    # -- nnz metrics (the SCOO format's currency + the format router's signal)
    def bucket_nnz(self, nnz_counts: Sequence[int]) -> List[int]:
        """True nonzero count per bucket."""
        nz = np.asarray(nnz_counts, dtype=np.int64)
        return [int(nz[mem].sum()) for mem in self.members]

    def bucket_densities(self, nnz_counts: Sequence[int]) -> List[float]:
        """Per-bucket density: true nonzeros over the densified CC cell count
        ``n_members * I_pad * C_pad`` — the CC-vs-SCOO routing signal."""
        return [
            nnz / max(len(mem) * ip * cp, 1)
            for (ip, cp), mem, nnz in zip(
                self.shapes, self.members, self.bucket_nnz(nnz_counts))
        ]

    def nnz_waste(self, nnz_counts: Sequence[int]) -> float:
        """Fraction of padded SCOO entries that are padding (needs a plan
        built with ``nnz_counts`` so ``nnz_pads`` is populated)."""
        if self.nnz_pads is None:
            raise ValueError("plan has no nnz_pads; pass nnz_counts to "
                             "plan_buckets to plan the SCOO layout")
        used = sum(self.bucket_nnz(nnz_counts))
        total = sum(npad * len(mem)
                    for npad, mem in zip(self.nnz_pads, self.members))
        return 1.0 - used / max(total, 1)

    # -- nnz-balanced sharding (the mesh engine's straggler planner) --------
    def balance_for_shards(self, nnz_counts: Sequence[int],
                           n_shards: int) -> "BucketPlan":
        """Reorder every bucket's members so the `n_shards` contiguous
        subject shards carry (near-)equal NONZERO counts, not equal subject
        counts.

        Under ``engine="mesh"`` each bucket's leading axis splits into
        `n_shards` contiguous chunks (``bucketize(subject_align=n_shards)``
        pads at the tail — see :func:`_shard_capacities`); the planner hands
        the members over sorted by size, so naive order puts all
        the heavy subjects on the last shards and the per-chunk SCOO work
        (O(bucket nnz / n_shards) only if balanced) stragglers. This is
        capacity-constrained greedy LPT: walk subjects by nnz descending,
        assign each to the least-loaded shard with a free slot (ties -> the
        lowest shard index, so the result is deterministic). The short
        final shard (the one holding the padding) gets the fewest slots.

        Shapes and pad targets are untouched — only the order WITHIN each
        bucket changes, so the padded geometry (and therefore the compiled
        program) is identical; only the subject->slot assignment moves.
        """
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        nz = np.asarray(nnz_counts, dtype=np.int64)
        if n_shards == 1:
            return self
        new_members = []
        for mem in self.members:
            caps = _shard_capacities(len(mem), n_shards)
            loads = [0] * n_shards
            bins: List[list] = [[] for _ in range(n_shards)]
            # stable sort on -nnz: equal-nnz subjects keep member order
            order = mem[np.argsort(-nz[mem], kind="stable")]
            for k in order:
                s = min((s for s in range(n_shards) if len(bins[s]) < caps[s]),
                        key=lambda s: (loads[s], s))
                bins[s].append(k)
                loads[s] += int(nz[k])
            new_members.append(
                np.concatenate([np.asarray(b, dtype=np.int32) for b in bins
                                if b]) if len(mem) else mem)
        return dataclasses.replace(self, members=new_members)

    def shard_nnz(self, nnz_counts: Sequence[int],
                  n_shards: int) -> List[List[int]]:
        """Per-bucket per-shard true nonzero counts under the contiguous
        chunk layout (tail padding) — the balance the planner above
        optimizes, surfaced so drivers can report it."""
        nz = np.asarray(nnz_counts, dtype=np.int64)
        out = []
        for mem in self.members:
            caps = _shard_capacities(len(mem), n_shards)
            loads, lo = [], 0
            for c in caps:
                loads.append(int(nz[mem[lo:lo + c]].sum()))
                lo += c
            out.append(loads)
        return out

    def shard_imbalance(self, nnz_counts: Sequence[int],
                        n_shards: int) -> float:
        """max/mean per-shard nnz over all buckets combined (1.0 = perfectly
        balanced; the straggler factor an unbalanced plan pays)."""
        per_bucket = self.shard_nnz(nnz_counts, n_shards)
        totals = [sum(b[s] for b in per_bucket) for s in range(n_shards)]
        mean = sum(totals) / max(len(totals), 1)
        return max(totals) / mean if mean > 0 else 1.0

    def stats(self, row_counts: Sequence[int], col_counts: Sequence[int],
              nnz_counts: Sequence[int],
              formats: Optional[Sequence[str]] = None) -> List[dict]:
        """Per-bucket records (shape, members, nnz, density, chosen format) —
        what ``decompose.py --json`` surfaces.

        With ``formats`` given, each record also has ``pad_share``: the
        share of the bucket's padded slots that hold no data, in its routed
        format's currency. SCOO: 1 - nnz / (Kb * N_pad); CC: 1 - sum_k I_k
        * c_k / (Kb * I_pad * C_pad), Kb being the member count."""
        out = []
        nnzs = self.bucket_nnz(nnz_counts)
        dens = self.bucket_densities(nnz_counts)
        area = (np.asarray(row_counts, dtype=np.int64)
                * np.asarray(col_counts, dtype=np.int64))
        for i, ((ip, cp), mem) in enumerate(zip(self.shapes, self.members)):
            rec = {
                "i_pad": ip, "c_pad": cp, "n_subjects": len(mem),
                "nnz": nnzs[i], "density": dens[i],
            }
            if self.nnz_pads is not None:
                rec["nnz_pad"] = self.nnz_pads[i]
            if formats is not None:
                rec["format"] = formats[i]
                if formats[i] == "cc":
                    used, slots = int(area[mem].sum()), len(mem) * ip * cp
                elif self.nnz_pads is not None:
                    used, slots = nnzs[i], len(mem) * self.nnz_pads[i]
                else:       # bucketize picks this SCOO bucket's N_pad itself
                    slots = 0
                if slots:
                    rec["pad_share"] = 1.0 - used / slots
            out.append(rec)
        return out


def slot_cost(i_pad, c_pad, n_pad):
    """Estimated device work of one subject slot of padded shape
    ``(I_pad, C_pad, N_pad)``, in units of one gathered SCOO triplet: the
    SCOO gathers (``N_pad``), the CC slab passes (``I_pad * C_pad / 400``),
    the mode-2 scatter's compact rows (``C_pad / 4``) and the polar stage,
    whose eigendecomposition grows with the bucket's rows (``I_pad**2 / 8``).
    It reads only the padded counts, so it prices every format alike:
    ``route_formats`` chooses the format after the plan is made. Takes
    scalars or NumPy arrays (``n_pad`` 0 where no nonzero counts are
    planned)."""
    return n_pad + i_pad * c_pad / 400 + c_pad / 4 + i_pad * i_pad / 8


def slot_rows(i_pad, c_pad):
    """Rows of width R that one subject slot materialises on the device in
    every format: its row space (``I_pad``: X_k V, B, Q) and its compact
    mode-2 rows (``C_pad``). A bucket's temporaries grow with Kb times
    this, and the buckets of a step reuse one another's memory, so the
    largest bucket sets the step's peak."""
    return i_pad + c_pad


# Cut positions the split planner weighs at most: its dynamic programme costs
# max_buckets * m**2 in their number m.
_MAX_CUTS = 512


def _even_cuts(n: int, n_buckets: int) -> np.ndarray:
    """Inner boundaries of ``n_buckets`` equal-count groups of ``n``."""
    sizes = [len(g) for g in np.array_split(np.arange(n), n_buckets)]
    return np.cumsum(sizes)[:-1]


def _cut_candidates(ip, cp, npad, n_buckets: int) -> np.ndarray:
    """Positions along the subject order where a bucket may end: every one
    below ``_MAX_CUTS`` subjects; else where the padded shape changes (a
    cut inside a run of one padded shape only helps to meet the footprint
    cap), thinned past ``_MAX_CUTS`` to the positions nearest equal steps
    of subjects and of the subjects' own padded cost (which resolves the
    few tall subjects at the end of the order), plus the equal-count
    boundaries, so that a split within the cap is always a candidate."""
    n = ip.size
    if n <= _MAX_CUTS:
        return np.arange(1, n)
    cuts = np.flatnonzero((ip[1:] != ip[:-1]) | (cp[1:] != cp[:-1])
                          | (npad[1:] != npad[:-1])) + 1
    if cuts.size > _MAX_CUTS:
        own = np.cumsum(slot_cost(ip, cp, npad))
        q = np.linspace(0.0, 1.0, _MAX_CUTS // 2 + 1)[1:]
        at = np.concatenate([q * n, np.searchsorted(own, q * own[-1])])
        cuts = cuts[np.unique(np.minimum(np.searchsorted(cuts, at),
                                         cuts.size - 1))]
    return np.union1d(cuts, _even_cuts(n, n_buckets))


def _best_split(ip, cp, npad, n_buckets: int, cap: int) -> np.ndarray:
    """Bucket boundaries ``[0, ..., n]`` along the order that minimise
    sum_b Kb * slot_cost(bucket b's padded shape) over at most
    ``n_buckets`` contiguous buckets ending at the cut candidates, no
    bucket's footprint Kb * :func:`slot_rows` above ``cap``. Ties go to
    fewer buckets, then to earlier cuts."""
    n = ip.size
    pos = np.concatenate([[0], _cut_candidates(ip, cp, npad, n_buckets), [n]])
    m = pos.size - 1                                  # blocks between cuts
    upper = np.triu(np.ones((m, m), dtype=bool))      # [a, b]: b >= a

    def range_max(v):
        # [a, b] -> max over blocks a..b (0 below the diagonal; counts >= 0)
        blk = np.maximum.reduceat(v, pos[:-1])
        return np.maximum.accumulate(np.where(upper, blk[None, :], 0), axis=1)

    size = pos[1:][None, :] - pos[:-1][:, None]       # subjects in a..b
    rows_i, rows_c = range_max(ip), range_max(cp)
    fits = upper & (size * slot_rows(rows_i, rows_c) <= cap)
    cost = np.where(fits, size * slot_cost(rows_i, rows_c, range_max(npad)),
                    np.inf)
    best = cost[0]                                    # one bucket: 0..b
    totals, starts = [best[-1]], []
    for _ in range(1, min(n_buckets, m)):
        # the last bucket spans blocks a..b, the ones before end at a - 1
        via = best[:-1, None] + cost[1:]
        start = np.argmin(via, axis=0)
        best = np.concatenate([[np.inf], via[start, np.arange(m)][1:]])
        starts.append(start + 1)
        totals.append(best[-1])
    k = int(np.argmin(totals))                        # buckets - 1
    ends, b = [m], m - 1
    for start in reversed(starts[:k]):
        b = int(start[b]) - 1
        ends.append(b + 1)
    return pos[np.asarray([0] + ends[::-1])]


def plan_buckets(
    row_counts: Sequence[int],
    col_counts: Sequence[int],
    *,
    max_buckets: int = 4,
    row_align: int = 8,
    col_align: int = 128,
    nnz_counts: Optional[Sequence[int]] = None,
    nnz_align: int = 8,
    sort_by: Optional[str] = None,
) -> BucketPlan:
    """Ordered split of the subjects into at most ``max_buckets`` buckets,
    cut where padding costs least.

    Subjects are sorted stably by their padded row count
    ``round_up(I_k, row_align)``, then by ``sort_by``: ``"nnz"`` (nnz_k, the
    SCOO format's padded-triplet currency; needs ``nnz_counts``) or
    ``"area"`` (I_k * c_k, the CC format's padded-cell currency). The
    default is ``"nnz"`` where ``nnz_counts`` is given and ``"area"``
    elsewhere. Remaining ties go by I_k, c_k and the padded nnz_k, so a run
    of equal keys holds one padded shape and the plan depends only on the
    multiset of shapes, never on which subject has which.

    The cuts along that order are chosen by dynamic programming to minimise
    the estimated padded work sum_b Kb * :func:`slot_cost` (I_pad, C_pad,
    N_pad), each bucket padded to its members' maxima rounded up to the
    alignments. The search is exact over contiguous splits of the order up
    to ``_MAX_CUTS`` subjects, and past that weighs the positions where the
    padded shape changes, thinned to about ``_MAX_CUTS``. Buckets of one
    padded shape merge. With ``nnz_counts`` given, every bucket also gets
    its SCOO pad target ``N_pad = round_up(max member nnz, nnz_align)`` in
    ``plan.nnz_pads``. :func:`plan_layout` bounds the buckets' footprint
    where the layout holds CC buckets.
    """
    return _plan(row_counts, col_counts, max_buckets=max_buckets,
                 row_align=row_align, col_align=col_align,
                 nnz_counts=nnz_counts, nnz_align=nnz_align, sort_by=sort_by,
                 bounded=False)


def _plan(row_counts, col_counts, *, max_buckets, row_align, col_align,
          nnz_counts, nnz_align, sort_by, bounded: bool) -> BucketPlan:
    """:func:`plan_buckets`; with ``bounded``, no bucket's footprint Kb *
    :func:`slot_rows` may exceed that of an equal-count bucket of the
    widest shape, and buckets of one shape merge only within it."""
    rc = np.asarray(row_counts, dtype=np.int64)
    cc = np.asarray(col_counts, dtype=np.int64)
    if rc.shape != cc.shape or rc.ndim != 1 or rc.size == 0:
        raise ValueError("row_counts/col_counts must be equal-length 1-D, non-empty")
    if max_buckets < 1:
        raise ValueError(f"max_buckets must be >= 1, got {max_buckets}")
    nz = None
    if nnz_counts is not None:
        nz = np.asarray(nnz_counts, dtype=np.int64)
        if nz.shape != rc.shape:
            raise ValueError("nnz_counts must match row_counts in length")
    if sort_by is None:
        sort_by = "area" if nz is None else "nnz"
    if sort_by == "area":
        key = rc * cc
    elif sort_by == "nnz":
        if nz is None:
            raise ValueError("sort_by='nnz' needs nnz_counts")
        key = nz
    else:
        raise ValueError(f"unknown sort_by {sort_by!r}; choose 'area' or 'nnz'")
    ip = _round_up(rc, row_align)
    cp = _round_up(cc, col_align)
    npad = np.zeros_like(rc) if nz is None else _round_up(nz, nnz_align)
    order = np.lexsort((npad, cc, rc, key, ip))
    ip, cp, npad = ip[order], cp[order], npad[order]
    n_buckets = int(min(max_buckets, rc.size))
    # the footprint of an equal-count bucket of the widest shape: the peak
    # any equal-count split may reach, and a bound that split meets
    cap = (-(-rc.size // n_buckets) * slot_rows(ip.max(), cp.max())
           if bounded else np.inf)
    bounds = _best_split(ip, cp, npad, n_buckets, cap)
    # merge buckets of one padded shape (compile-shape dedupe)
    shapes: List[tuple] = []
    members: List[np.ndarray] = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        shape = (int(ip[lo:hi].max()), int(cp[lo:hi].max()),
                 int(npad[lo:hi].max()))
        grp = order[lo:hi].astype(np.int32)
        i = shapes.index(shape) if shape in shapes else -1
        if i >= 0 and (len(members[i]) + grp.size) * slot_rows(*shape[:2]) <= cap:
            members[i] = np.concatenate([members[i], grp])
        else:
            shapes.append(shape)
            members.append(grp)
    return BucketPlan(shapes=[s[:2] for s in shapes], members=members,
                      nnz_pads=None if nz is None else [s[2] for s in shapes])


def plan_layout(
    row_counts: Sequence[int],
    col_counts: Sequence[int],
    nnz_counts: Sequence[int],
    *,
    format: str = "auto",
    max_buckets: int = 4,
    row_align: int = 8,
    col_align: int = 128,
    nnz_align: int = 8,
    density_threshold: float = SCOO_DENSITY_THRESHOLD,
) -> tuple:
    """The device layout's plan and per-bucket formats, ``(BucketPlan,
    [format per bucket])``: :func:`plan_buckets`, routed by
    :func:`route_formats`.

    Where any bucket routes to CC, the plan is made again with each
    bucket's footprint, Kb * :func:`slot_rows` (I_pad, C_pad), bounded by
    that of an equal-count bucket of the widest shape, ceil(K / buckets) *
    slot_rows(max I_pad, max C_pad), and routed again. The buckets of a step
    reuse one another's memory, so the largest sets the peak; a CC bucket's
    temporaries grow with its footprint, and the unbounded split would pile
    the many short subjects into one bucket whose temporaries outgrow what
    equal counts needed (CHOA at one chip's share: 5.1 against 4.4 GiB on a
    TPU v5e). A SCOO bucket's temporaries grow with its padded triplets,
    which the split's cost already weighs.
    """
    kw = dict(max_buckets=max_buckets, row_align=row_align,
              col_align=col_align, nnz_counts=nnz_counts, nnz_align=nnz_align,
              sort_by=None)
    route = dict(format=format, density_threshold=density_threshold)
    plan = _plan(row_counts, col_counts, bounded=False, **kw)
    formats = route_formats(plan, nnz_counts, **route)
    if "cc" in formats:
        plan = _plan(row_counts, col_counts, bounded=True, **kw)
        formats = route_formats(plan, nnz_counts, **route)
    return plan, formats


def fixed_plan(
    n_subjects: int,
    i_pad: int,
    c_pad: int,
    *,
    nnz_pad: Optional[int] = None,
) -> BucketPlan:
    """A single-bucket plan with an EXPLICIT padded geometry.

    The split planner above picks shapes from the data, so two batches
    with different member geometry compile two different programs. The
    streaming service (``launch/stream.py``) instead pins one
    ``(I_pad, C_pad[, N_pad])`` rectangle chosen up front and pads every
    request batch into it — each flush then re-dispatches the SAME compiled
    update regardless of which subjects arrived. Members are simply
    ``0..n_subjects-1``: the caller stages exactly the batch's subjects.

    Raises ``ValueError`` downstream (in ``bucketize``) if a subject exceeds
    the pinned nnz budget; row/col overflow must be checked by the caller
    (the service grows its sticky geometry and recompiles).
    """
    if n_subjects < 1 or i_pad < 1 or c_pad < 1:
        raise ValueError("fixed_plan needs n_subjects, i_pad, c_pad >= 1")
    return BucketPlan(
        shapes=[(int(i_pad), int(c_pad))],
        members=[np.arange(n_subjects, dtype=np.int32)],
        nnz_pads=None if nnz_pad is None else [int(nnz_pad)],
    )


def route_formats(
    plan: BucketPlan,
    nnz_counts: Sequence[int],
    *,
    format: str = "auto",
    density_threshold: float = SCOO_DENSITY_THRESHOLD,
) -> List[str]:
    """Per-bucket device-format decision for ``bucketize``.

    ``format="cc"``/``"scoo"`` force every bucket; ``"auto"`` routes each
    bucket by its measured density (true nonzeros over the densified CC cell
    count): below ``density_threshold`` the O(nnz) SCOO path wins, at or
    above it the dense-over-kept-columns CC matmuls do.
    """
    if format in ("cc", "scoo"):
        return [format] * plan.n_buckets
    if format != "auto":
        raise ValueError(
            f"unknown format {format!r}; choose from 'cc', 'scoo', 'auto'")
    return ["scoo" if d < density_threshold else "cc"
            for d in plan.bucket_densities(nnz_counts)]


def route_compress(shapes, sketch_dim: int) -> List[bool]:
    """Per-bucket pass-through decision for the rsvd preprocessing stage
    (:mod:`repro.core.compress`): compress a bucket only when its padded row
    space exceeds the sketch width — otherwise the "core" would be as large
    as the data and the QB pass pure overhead.

    ``shapes`` is a list of ``(i_pad, c_pad)`` pairs (``BucketPlan.shapes``
    or the realized buckets' padded shapes); returns one bool per bucket.
    """
    if isinstance(shapes, BucketPlan):
        shapes = shapes.shapes
    if sketch_dim < 1:
        raise ValueError(f"sketch_dim must be >= 1, got {sketch_dim}")
    return [int(ip) > int(sketch_dim) for ip, _ in shapes]
