"""The control (the reference one precision below the configuration's, in
the program's place) comes out not correct under each cell's limits, and
so do the faults planted in the reference; at a share of each cohort a
test run can hold. ``chipbench/control.py`` takes the same readings at the
cells' own sizes."""
import dataclasses

import pytest

from chipbench import cell as cell_mod, control, gen, reference

CELLS = [w["name"] for w in cell_mod.load_benchmark()["workloads"]]
SMALL = {"choa-r40": 3000, "movielens-r40": 300}


@pytest.fixture(scope="module", params=CELLS)
def readings(request):
    c = cell_mod.load_cell(request.param)
    c = dataclasses.replace(
        c, cfg=dict(c.cfg, n_subjects=SMALL[c.entry["config"]]))
    seed = 2**31 + 17
    cohort = gen.generate(c.cfg, seed)
    v0 = cell_mod.initial_v(c.cfg)
    steps = cell_mod.STEPS
    refs = reference.run(cohort, v0, steps)
    snaps = {
        "control": control.as_snaps(reference.run(cohort, v0, steps, "high")),
        "frozen": control.frozen(cohort, v0, steps),
        "half": control.half(cohort, v0, steps),
        "altered": control.altered(refs),
    }
    return c, {k: cell_mod.compare(s, refs) for k, s in snaps.items()}


@pytest.mark.parametrize("kind", ["control", "frozen", "half", "altered"])
def test_not_correct(readings, kind):
    c, nums = readings
    correct, rows = cell_mod.judge(nums[kind], c.limits)
    assert not correct, rows


def test_reference_against_itself_is_correct(readings):
    c, _ = readings
    cohort = gen.generate(dict(c.cfg, n_subjects=200), 5)
    v0 = cell_mod.initial_v(c.cfg)
    refs = reference.run(cohort, v0, cell_mod.STEPS)
    nums = cell_mod.compare(control.as_snaps(refs), refs)
    assert nums["fit_gap"] == 0 and nums["h_gap"] == 0
    assert cell_mod.judge(nums, c.limits)[0]
