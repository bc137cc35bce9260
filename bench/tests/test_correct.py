"""What decides ``correct``: sound runs pass, the control and each fault
that a cell can have fail.

The harness runs here without its look for a chip, at sizes a CPU holds,
through the same set-up, window and checks as on the chip, with the
cell's own limits. The control is the plain reference one precision down
(bfloat16 rows and per-row arithmetic, f32 sums) in the program's place,
read at the states the program produced. The faults are planted under the
timed path (``harness.Fault``): a step that returns its state unchanged,
half of the rows left out with the sum taken as twice the rest's, each
drawn θ altered where it is produced, and (FlyMC) a step that skips its
z-update. One chip has no exchange between chips to leave out.
"""

import pytest

from bench import harness

# Each cell at a size the CPU holds (Pallas kernels interpreted).
TINY = {
    "logistic-rwmh.map.c4": (
        {"n": 2000, "map_steps": 300, "reference_draws": 1000},
        {"warmup": 200, "chunk": 100}),
    "robust-slice.map.c2": (
        {"n": 20000, "map_steps": 300},
        {"warmup": 60, "chunk": 20, "capacity": 512, "cand_capacity": 512}),
    "robust-slice.regular.c2": (
        {"n": 200000, "map_steps": 300},
        {"warmup": 60, "chunk": 20}),
}
# Long enough that a shift of 5/√N (about 4.5 posterior sd) stands out of
# the window's standard error.
SECONDS = {"logistic-rwmh.map.c4": 15.0, "robust-slice.regular.c2": 20.0}
# Sizes the control is read at, where TINY is too small for it: below the
# cell's own N the control's error in a full-data log density stays under
# the limit set at N = 1.8M.
CONTROL_CFG = {"robust-slice.regular.c2": {"n": 1800000}}
# The faults each cell can have: FlyMC also has a z-update to skip.
FAULTS = [(cell, fault) for cell in sorted(TINY)
          for fault in ("frozen", "half", "shift", "z_frozen")
          if fault != "z_frozen" or ".map." in cell]


def _run(cell, seed, cfg_over=None, **kw):
    cfg, traffic = TINY[cell]
    return harness.run(cell, seed, SECONDS.get(cell, 6.0),
                       cfg_over={**cfg, **(cfg_over or {})},
                       traffic_over=traffic, **kw)


def _over(res, numbers):
    return {k: v for k, v in numbers.items()
            if not v <= res["checks"][k]["limit"]}


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell):
    res = _run(cell, 21)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_is_not_correct(cell):
    res = _run(cell, 23, cfg_over=CONTROL_CFG.get(cell), control=True)
    assert _over(res, res["control"]), (res["control"], res["checks"])


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_not_correct(cell, fault):
    res = _run(cell, 22, fault=harness.Fault(fault))
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1
