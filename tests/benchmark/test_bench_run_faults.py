"""A tiny run with the timed path broken underneath reads not correct,
and so does the control: the reference drawing with 13-round Threefry."""

import json

import pytest

from _bench_drive import drive
from _bench_paths import BENCH

import reference  # noqa: E402


@pytest.mark.parametrize("fault", ["flip_flag", "half_batch", "no_rounds"])
def test_broken_timed_path_is_not_correct(fault):
    res, _ = drive("gossip_10k.flood", fault)
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert sum(c["value"] for k, c in res["checks"].items()
               if k.endswith("_mismatch")) >= 1


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_control_differs_from_the_reference(seed):
    import gossip_10k

    traffic = json.loads((BENCH / "traffic" / "flood.json").read_text())
    doc = gossip_10k.doc(seed, traffic, gossip_10k.TINY)
    ref = reference.simulate(doc)
    again = reference.simulate(doc)
    ctl = reference.simulate(doc, rounds=13)
    assert not any(reference.mismatches([again], ref).values())
    miss = reference.mismatches([ctl], ref)
    assert miss["units_dropped"] == 1 and miss["events"] == 1
