"""The benchmark's config copy generates what the repo's generator does,
and a run's seeds come from --seed alone."""

import json
import sys

import pytest

from _bench_paths import BENCH, ROOT

sys.path.insert(0, str(ROOT / "tools"))

import gossip_10k  # noqa: E402
import run  # noqa: E402


def traffic(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_gossip_10k_is_the_example_byte_for_byte_at_seed_4():
    text = gossip_10k.text(4, traffic("flood"))
    assert text == (ROOT / "examples" / "gossip_10k.yaml").read_text()


def test_seed_reaches_general_seed_only():
    big = 2**31 + 12345
    g = gossip_10k.doc(big, traffic("flood"))
    assert g["general"]["seed"] == big
    assert g["hosts"] == gossip_10k.doc(4, traffic("flood"))["hosts"]
    assert g["network"] == gossip_10k.doc(4, traffic("flood"))["network"]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**33 + 5])
def test_derived_seeds_are_fixed_distinct_31_bit(seed):
    k = traffic("flood")["seeds_per_run"]
    a = run.derive_seeds(seed, k)
    assert a == run.derive_seeds(seed, k)
    assert len(set(a)) == k and all(0 <= s < 2**31 for s in a)
    assert a != run.derive_seeds(seed + 1, k)


def test_assumed_names_each_departure_from_the_source():
    for key in ("relay_direction", "transport", "trickle", "tx_rate",
                "graph", "results"):
        assert key in gossip_10k.ASSUMED, key
