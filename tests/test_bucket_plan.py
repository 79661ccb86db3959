"""The bucket planner's ordered split: cut points chosen by dynamic
programming over the padded cost, checked against brute force and against
the equal-count split it replaced; the footprint bound of CC layouts; and
the per-bucket ``pad_share``."""
import itertools

import numpy as np
import pytest

from repro.data.ehr import choa_like, movielens_like
from repro.sparse import plan_buckets, plan_layout
from repro.sparse import bucketing
from repro.sparse.bucketing import BucketPlan, slot_cost


def _up(x, align):
    return np.maximum(align, -(-np.asarray(x, np.int64) // align) * align)


def _plan_cost(plan, nz):
    """sum_b Kb * slot_cost of a plan built with nnz counts."""
    return sum(len(m) * slot_cost(ip, cp, npad) for (ip, cp), m, npad
               in zip(plan.shapes, plan.members, plan.nnz_pads))


def _split_cost(order, rc, cc, nz, bounds, ra=8, ca=128, na=8):
    total = 0.0
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        g = order[lo:hi]
        total += len(g) * slot_cost(int(_up(rc[g].max(), ra)),
                                    int(_up(cc[g].max(), ca)),
                                    int(_up(nz[g].max(), na)))
    return total


def _footprints(order, rc, cc, bounds):
    """Kb * (I_pad + C_pad) of each bucket of a split of ``order``."""
    return [(hi - lo) * int(_up(rc[order[lo:hi]].max(), 8)
                            + _up(cc[order[lo:hi]].max(), 128))
            for lo, hi in zip(bounds[:-1], bounds[1:])]


def _footprint_cap(rc, cc, k):
    """An equal-count bucket of the widest padded shape."""
    return -(-len(rc) // k) * int(_up(rc.max(), 8) + _up(cc.max(), 128))


def _order(rc, cc, nz):
    """The documented order: padded rows, nnz, then I_k, c_k, padded nnz."""
    return np.lexsort((_up(nz, 8), cc, rc, nz, _up(rc, 8)))


def _equal_count(rc, cc, nz, max_buckets=4):
    """The former rule: equal-count groups of the nnz order, each padded to
    its members' maxima. Returns (cost, nnz_waste)."""
    order = np.argsort(nz, kind="stable")
    groups = [g for g in np.array_split(order, max_buckets) if g.size]
    bounds = np.cumsum([0] + [g.size for g in groups])
    cost = _split_cost(order, rc, cc, nz, bounds)
    slots = sum(g.size * int(_up(nz[g].max(), 8)) for g in groups)
    return cost, 1.0 - nz.sum() / slots


def _counts(data):
    return (np.asarray(data.row_counts(), np.int64),
            np.asarray(data.col_counts(), np.int64),
            np.asarray(data.nnz_counts(), np.int64))


def _bounded(rc, cc, nz, max_buckets=4):
    return bucketing._plan(rc, cc, max_buckets=max_buckets, row_align=8,
                           col_align=128, nnz_counts=nz, nnz_align=8,
                           sort_by=None, bounded=True)


def _tall_tail(n, seed):
    """Many short subjects and a long tail of tall ones, as in EHR data."""
    rng = np.random.default_rng(seed)
    rc = np.minimum(np.ceil(np.exp(rng.normal(1.4, 1.0, n))), 166)
    cc = rng.integers(3, 30, n)
    nz = np.minimum(rng.integers(3, 120, n), rc * cc).astype(int)
    return rc.astype(int), cc, nz


@pytest.mark.parametrize("bounded", [False, True])
@pytest.mark.parametrize("seed", range(6))
def test_split_cost_equals_brute_force_minimum(seed, bounded):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 13))
    max_buckets = int(rng.integers(1, 5))
    rc = rng.integers(1, 60, n)
    cc = rng.integers(1, 400, n)
    nz = np.minimum(rng.integers(1, 900, n), rc * cc)
    plan = (_bounded(rc, cc, nz, max_buckets) if bounded else
            plan_buckets(rc, cc, nnz_counts=nz, max_buckets=max_buckets))
    order = _order(rc, cc, nz)
    cap = _footprint_cap(rc, cc, min(max_buckets, n)) if bounded else np.inf
    best = min(
        _split_cost(order, rc, cc, nz, [0, *cuts, n])
        for k in range(max_buckets)
        for cuts in itertools.combinations(range(1, n), k)
        if max(_footprints(order, rc, cc, [0, *cuts, n])) <= cap)
    assert _plan_cost(plan, nz) == pytest.approx(best, rel=1e-12)
    assert plan.n_buckets <= max_buckets


@pytest.mark.parametrize("draw", ["choa", "movielens"])
def test_split_beats_equal_count_on_cohort_shapes(draw):
    data = (choa_like(scale=0.004, seed=1) if draw == "choa"
            else movielens_like(scale=0.04, seed=1))
    rc, cc, nz = _counts(data)
    plan = plan_buckets(rc, cc, nnz_counts=nz, max_buckets=4)
    old_cost, old_waste = _equal_count(rc, cc, nz)
    assert _plan_cost(plan, nz) <= old_cost
    assert plan.nnz_waste(nz) <= old_waste
    assert plan.n_buckets <= 4
    members = np.sort(np.concatenate(plan.members))
    np.testing.assert_array_equal(members, np.arange(rc.size))
    for (ip, cp), mem, npad in zip(plan.shapes, plan.members, plan.nnz_pads):
        assert rc[mem].max() <= ip and cc[mem].max() <= cp
        assert nz[mem].max() <= npad


def test_cc_layout_bounds_the_bucket_footprint():
    """Routed to CC, no bucket holds more rows than an equal-count bucket
    of the widest shape could; routed to SCOO, the split is unbounded."""
    rc, cc, nz = _tall_tail(4000, 2)
    free = plan_buckets(rc, cc, nnz_counts=nz)
    cap = _footprint_cap(rc, cc, 4)
    assert max(len(m) * (ip + cp) for (ip, cp), m in
               zip(free.shapes, free.members)) > cap
    plan, fmts = plan_layout(rc, cc, nz, format="cc")
    assert fmts == ["cc"] * plan.n_buckets
    assert max(len(m) * (ip + cp) for (ip, cp), m in
               zip(plan.shapes, plan.members)) <= cap
    assert _plan_cost(plan, nz) >= _plan_cost(free, nz)
    for fmt in ("scoo", "auto"):
        plan, fmts = plan_layout(rc, cc, nz, format=fmt)
        assert set(fmts) == {"scoo"}     # density far below the threshold
        assert plan.shapes == free.shapes and plan.nnz_pads == free.nnz_pads


def test_permuting_equal_shapes_keeps_the_plan():
    """Subjects of one shape trade places: shapes and pads stay, and each
    bucket keeps the same multiset of member shapes."""
    rc, cc, nz = _counts(choa_like(scale=0.002, seed=3))
    rng = np.random.default_rng(0)
    perm = rng.permutation(rc.size)
    base = plan_buckets(rc, cc, nnz_counts=nz)
    moved = plan_buckets(rc[perm], cc[perm], nnz_counts=nz[perm])
    assert moved.shapes == base.shapes
    assert moved.nnz_pads == base.nnz_pads

    def shapes_of(plan, r, c, z):
        return [sorted(zip(r[m], c[m], z[m])) for m in plan.members]

    assert shapes_of(moved, rc[perm], cc[perm], nz[perm]) == \
        shapes_of(base, rc, cc, nz)


def test_capped_candidates_stay_near_the_exact_split(monkeypatch):
    """Past ``_MAX_CUTS`` subjects the planner weighs about that many cut
    positions: the plan stays no worse than equal counts and within 2% of
    the split over every position."""
    rng = np.random.default_rng(7)
    n = 1_500
    rc = np.minimum(np.ceil(np.exp(rng.normal(2.0, 1.0, n))), 166).astype(int)
    cc = rng.integers(3, 600, n)
    nz = np.minimum(rng.integers(5, 3000, n), rc * cc)
    order = _order(rc, cc, nz)
    ip, cp, npad = _up(rc, 8)[order], _up(cc, 128)[order], _up(nz, 8)[order]
    n_cuts = int(np.count_nonzero((ip[1:] != ip[:-1]) | (cp[1:] != cp[:-1])
                                  | (npad[1:] != npad[:-1])))
    assert n_cuts > bucketing._MAX_CUTS
    capped = _plan_cost(plan_buckets(rc, cc, nnz_counts=nz), nz)
    assert capped <= _equal_count(rc, cc, nz)[0]
    monkeypatch.setattr(bucketing, "_MAX_CUTS", n)
    exact = _plan_cost(plan_buckets(rc, cc, nnz_counts=nz), nz)
    assert exact <= capped <= 1.02 * exact


def test_area_order_without_nnz_counts():
    """Without nnz counts the order's second key is I_k * c_k and the cost
    has no triplet term; ``sort_by`` still checks its value."""
    rc = np.array([3, 5, 9, 2, 14, 7, 7, 40, 41])
    cc = np.array([4, 4, 8, 2, 16, 8, 4, 30, 2])
    plan = plan_buckets(rc, cc, max_buckets=3, row_align=4, col_align=4)
    assert plan.nnz_pads is None
    assert plan.n_buckets <= 3
    np.testing.assert_array_equal(np.sort(np.concatenate(plan.members)),
                                  np.arange(rc.size))
    with pytest.raises(ValueError, match="unknown sort_by"):
        plan_buckets(rc, cc, sort_by="rows")
    with pytest.raises(ValueError, match="max_buckets"):
        plan_buckets(rc, cc, max_buckets=0)


def test_pad_share_matches_hand_count():
    """Seven subjects in two buckets, one routed to each format."""
    rc = [2, 3, 4, 1, 6, 5, 8]
    cc = [3, 2, 4, 1, 5, 6, 8]
    nz = [4, 5, 9, 1, 20, 12, 30]
    plan = BucketPlan(shapes=[(4, 4), (8, 8)],
                      members=[np.array([0, 1, 2, 3], np.int32),
                               np.array([4, 5, 6], np.int32)],
                      nnz_pads=[16, 32])
    cc_stats, scoo_stats = plan.stats(rc, cc, nz, formats=["cc", "scoo"])
    # CC: areas 6 + 6 + 16 + 1 = 29 of 4 subjects x 4 x 4 = 64 cells
    assert cc_stats["pad_share"] == pytest.approx(1 - 29 / 64)
    # SCOO: nonzeros 20 + 12 + 30 = 62 of 3 subjects x 32 = 96 triplets
    assert scoo_stats["pad_share"] == pytest.approx(1 - 62 / 96)
    flipped = plan.stats(rc, cc, nz, formats=["scoo", "cc"])
    assert flipped[0]["pad_share"] == pytest.approx(1 - 19 / 64)
    assert flipped[1]["pad_share"] == pytest.approx(1 - (30 + 30 + 64) / 192)
    assert all("pad_share" not in r for r in plan.stats(rc, cc, nz))


@pytest.mark.parametrize("bounded", [False, True])
def test_shard_balance_on_unequal_buckets(bounded):
    """The split gives buckets of very different sizes; the nnz balancer
    still permutes within each bucket only and never balances worse."""
    rc, cc, nz = _tall_tail(4000, 2)
    plan = (_bounded(rc, cc, nz) if bounded
            else plan_buckets(rc, cc, nnz_counts=nz))
    sizes = [len(m) for m in plan.members]
    assert max(sizes) > 2 * min(sizes)
    bal = plan.balance_for_shards(nz, 4)
    assert bal.shapes == plan.shapes and bal.nnz_pads == plan.nnz_pads
    for a, b in zip(plan.members, bal.members):
        np.testing.assert_array_equal(np.sort(a), np.sort(b))
    assert bal.shard_imbalance(nz, 4) <= plan.shard_imbalance(nz, 4)
