"""The comparison that decides ``correct`` has been shown to fail.

1. The plain reference computed with 8-bit running totals, put in the
   program's place behind a door, comes out as not correct; the exact
   reference in the same place comes out correct.
2. The rest of a run with the timed path broken underneath
   (``control.OverAdmit``: an answer altered where it is produced, the
   control the chip runs use) ends with ``correct`` false.
"""

import os

import pytest

from cellbench import probe, run
from cellbench.families import (flow as control, flow as deploy,
                                flow_reference as reference)

from fake_door import FakeDoor, reference_decider

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "manifest.json")


def probe_against(lower: bool, seed: int = 5, traffic: str = "tiny-open"):
    # the control needs a count past 256, where 8 bits run out: the real
    # deployment's file, whose door here is the reference itself
    dep = deploy.load_deployment("mesh-100k")
    tr = deploy.load_json(os.path.join(HERE, "extra", "traffic",
                                       traffic + ".json"))
    ref = reference.for_deployment(dep, lower_precision=lower)
    door = FakeDoor(reference_decider(ref))
    try:
        return probe.Probe(door.port, dep, tr, seed=seed,
                           say=lambda m: None).run()
    finally:
        door.close()


def test_exact_reference_in_the_programs_place_is_correct():
    out = probe_against(lower=False)
    assert out["ok"], out


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_lower_precision_in_the_programs_place_is_not_correct(seed):
    out = probe_against(lower=True, seed=seed)
    assert not out["ok"]
    bad = {c["check"]: c["mismatches"] for c in out["checks"]}
    assert bad["big"] > 0 and bad["guard"] > 0  # counts 5000 and 30000
    assert bad["tight"] == 0  # counts under 256 survive 8 bits


def test_a_run_with_altered_answers_is_not_correct():
    lines = []
    result = run.run_cell(MANIFEST, "tiny.tiny-open", seed=2_147_483_900,
                          seconds=1.5, trace=0, require_chip=False,
                          wrap_service=control.OverAdmit, out=lines.append)
    assert result["correct"] is False
    assert any("probe tight" in ln and " 0 mismatches" not in ln
               for ln in lines)


def test_the_same_run_unbroken_is_correct():
    result = run.run_cell(MANIFEST, "tiny.tiny-open", seed=2_147_483_901,
                          seconds=1.5, trace=0, require_chip=False,
                          out=lambda m: None)
    assert result["correct"] is True and result["failed"] == 0
