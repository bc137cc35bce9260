"""What decides ``correct`` in the softmax cell: a sound run passes, the
control and each fault fail.

``softmax-mala.map.c64`` runs here without its look for a chip, shrunk to
what a CPU holds (Pallas kernels interpreted), through the same set-up,
window and checks as on the chip, with the cell's own limits. The rows
keep the cell's K but lose most of D: at D = 256 the binary features'
common direction makes XᵀX's largest eigenvalue ≈ D + 1 times the rest,
MALA's step is held by it, and the contrasts relax over thousands of
iterations, which a CPU cannot run in a test. The initial step is the
cell's step scaled to this N and D, so that the warm-up reaches a tuned
step. The control, faults and readings are as in ``test_correct.py``.

The cell's limits are set for 64 chains over 18,000 rows on the chip.
Two of its numbers read sampling noise, which is far larger at this size,
so they take limits of their own, set as the cell's were: between the
largest sound reading and the smallest fault reading, here over sound
seeds 21 and 24-26 and faults on seed 22 at this size. ``bright_gap``
compares a count of a few bright rows per chain-iteration, which the
z-chain holds over many iterations: sound runs read 0.006-0.17, and
``z_frozen`` 0.83, ``frozen`` 4.2. ``mean_z`` is taken over 16 contrasts
with a window ESS of 27-44, where the chip has 768 and hundreds: sound
runs read 2.2-3.8, ``shift`` 8.6 and ``z_frozen`` 14.4. The other numbers
keep the cell's limits.
"""

import pytest

from bench import harness

CELL = "softmax-mala.map.c64"
CFG = {"n": 2000, "d": 8, "map_steps": 300, "reference_draws": 1000,
       "step_size": 0.03}
TRAFFIC = {"chains": 4, "warmup": 200, "chunk": 50, "capacity": 512,
           "cand_capacity": 512}
SECONDS = 12.0
LIMITS = {"bright_gap": 0.4, "mean_z": 6.0}


@pytest.fixture(autouse=True)
def _limits_of_this_size(monkeypatch):
    find_cell = harness.find_cell

    def shrunk(name, spec=None):
        entry, cfg, traffic, limits = find_cell(name, spec)
        return entry, cfg, traffic, {**limits, **LIMITS}

    monkeypatch.setattr(harness, "find_cell", shrunk)


def _run(seed, **kw):
    return harness.run(CELL, seed, SECONDS, cfg_over=CFG,
                       traffic_over=TRAFFIC, **kw)


def test_sound_run_is_correct():
    res = _run(21)
    assert res["correct"], res["checks"]
    assert res["window"]["compilations"] == 0


def test_control_is_not_correct():
    res = _run(23, control=True)
    over = {k: v for k, v in res["control"].items()
            if not v <= res["checks"][k]["limit"]}
    assert set(over) & {"lp_gap", "delta_gap"}, (res["control"],
                                                 res["checks"])


@pytest.mark.parametrize("fault", ["frozen", "half", "shift", "z_frozen"])
def test_fault_is_not_correct(fault):
    res = _run(22, fault=harness.Fault(fault))
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1
