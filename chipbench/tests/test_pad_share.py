"""``pad_share`` over hand-made bucket records, and nothing where the
records lack the per-bucket share (a program whose plan does not report
it)."""
import pytest

from chipbench.cell import Context
from chipbench.metrics import pad_share


def _ctx(buckets):
    return Context(trace=None, spans={}, compile_s=0.0, iterations_traced=0,
                   buckets=buckets, rank=40, device_kind="TPU v5 lite",
                   work={})


SCOO = {"i_pad": 8, "c_pad": 128, "n_subjects": 10, "nnz": 120,
        "nnz_pad": 32, "format": "scoo"}
CC = {"i_pad": 16, "c_pad": 128, "n_subjects": 2, "nnz": 300,
      "nnz_pad": 200, "format": "cc"}


def test_none_without_the_field():
    assert pad_share.read(_ctx([dict(SCOO), dict(CC)])) is None
    assert pad_share.read(_ctx([dict(SCOO, pad_share=0.625), dict(CC)])) is None
    assert pad_share.read(_ctx([])) is None


def test_empty_slots_over_all_slots():
    # SCOO: 120 of 10 x 32 = 320 triplets hold data, 200 are empty;
    # CC: 2 x 16 x 128 = 4096 cells, 1024 of them empty
    recs = [dict(SCOO, pad_share=1 - 120 / 320), dict(CC, pad_share=0.25)]
    assert pad_share.slots(recs[0]) == 320
    assert pad_share.slots(recs[1]) == 4096
    assert pad_share.read(_ctx(recs)) == pytest.approx(
        100.0 * (200 + 1024) / (320 + 4096))
