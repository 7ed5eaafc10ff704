"""One experiment through the harness's ``run_experiment`` at the gossip
config's TINY size: its identity is the one recorded before the
harness took its model readings from the config module, and its record
carries the summary's spans and device counters."""

import json

import pytest

from _bench_paths import BENCH

import gossip_10k  # noqa: E402
import run  # noqa: E402

TRAFFIC = json.loads((BENCH / "traffic" / "flood.json").read_text())

#: the identity of a tiny run of each seed, read with the harness at
#: commit 94ee5d1 (``node_results`` in run.py, ``reference`` by name)
COUNTERS = ("dgrams_received", "dgrams_sent", "events", "processes_spawned",
            "units_delivered", "units_emitted")
EXPECTED = {
    5: {"events": 53610, "units_sent": 53200, "units_dropped": 179,
        "bytes_sent": 9122624, "process_errors": [],
        "counters": dict(zip(COUNTERS, (53200, 53379, 53610, 400, 53200,
                                        53379))),
        "nodes_digest": "d7c99ee3bdce526910a7221d52eca2b1"
                        "dd51ad4c5ced56112011aa8c15d33706"},
    2147483659: {"events": 53589, "units_sent": 53179, "units_dropped": 191,
                 "bytes_sent": 9130856, "process_errors": [],
                 "counters": dict(zip(COUNTERS, (53179, 53370, 53589, 400,
                                                 53179, 53370))),
                 "nodes_digest": "be836047e7112457d5804f4644e41094"
                                 "1fd07fd18f28178ea2adaabcf04a0c31"},
}


@pytest.fixture(scope="module", params=sorted(EXPECTED))
def record(request, tmp_path_factory):
    import jax

    doc = gossip_10k.doc(request.param, TRAFFIC, gossip_10k.TINY)
    reference = run.load_reference(gossip_10k)
    rec = run.run_experiment(jax, doc, tmp_path_factory.mktemp("exp"),
                             run.SpanClock(), gossip_10k, reference)
    return request.param, rec


def test_identity_is_the_one_recorded(record):
    seed, rec = record
    assert rec["identity"] == EXPECTED[seed]
    assert set(rec["identity"]) == set(run.load_reference(gossip_10k).KEYS)


def test_record_carries_spans_and_device_counters(record):
    _, rec = record
    assert rec["c_engine"]
    assert rec["phase_detail"]["events.py_callback"] > 0
    assert rec["phase_calls"]["events"] > 0
    dev = rec["device"]
    assert sum(dev["routes"].values()) == rec["units_sent"]
    for key in ("handoff_units", "resident_units", "resident_peak",
                "spec_rows"):
        assert isinstance(dev[key], int), key
    assert dev["platform"] == "cpu"
