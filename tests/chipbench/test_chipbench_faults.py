"""A run with the timed path broken underneath comes out not correct:
a decode that returns its cache unchanged, half of the batch decoded from
the other half's logits, and a token altered where it is produced.  (One
chip: there is no exchange between chips to leave out.)"""

from __future__ import annotations

from chipbench import faults
import pytest


@pytest.mark.parametrize("cell", ["td", "ts"])
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_timed_path_is_not_correct(run_tiny, cell, fault):
    r = run_tiny(cell, make_program=faults.broken(fault))
    assert r["correct"] is False
    assert r["check"]["logit_gap"]["value"] > \
        r["check"]["logit_gap"]["limit"]
