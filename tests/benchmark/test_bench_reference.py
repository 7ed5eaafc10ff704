"""The plain reference: its Threefry against the published answers and
the program's, its routes against the program's tables, and its result
against the program's per-unit plane at a tiny size."""

import copy
import json

import numpy as np
import pytest

from _bench_paths import BENCH, ROOT  # noqa: F401

import gossip_10k  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

TRAFFIC = json.loads((BENCH / "traffic" / "flood.json").read_text())

#: Random123's known answers for Threefry-2x32-20: (key, counter, out)
KAT = [
    ((0, 0), (0, 0), (0x6B200159, 0x99BA4EFE)),
    ((0xFFFFFFFF, 0xFFFFFFFF), (0xFFFFFFFF, 0xFFFFFFFF),
     (0x1CB996FC, 0xBB002BE7)),
    ((0x13198A2E, 0x03707344), (0x243F6A88, 0x85A308D3),
     (0xC4923A9C, 0x483DF7A0)),
]


@pytest.mark.parametrize("key,ctr,out", KAT)
def test_threefry_known_answers(key, ctr, out):
    x0, x1 = reference.threefry2x32(*key, [ctr[0]], [ctr[1]])
    assert (int(x0[0]), int(x1[0])) == out


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 9, 2**40 + 3])
def test_threefry_matches_the_program(seed):
    from shadow_tpu.ops.prng import threefry2x32

    rng = np.random.default_rng(seed % 1000)
    c0 = rng.integers(0, 2**32, 4096, dtype=np.uint32)
    c1 = rng.integers(0, 2**32, 4096, dtype=np.uint32)
    k0, k1 = seed & 0xFFFFFFFF, seed >> 32
    mine = reference.threefry2x32(k0, k1, c0, c1)
    prog = threefry2x32(np.uint32(k0), np.uint32(k1), c0, c1)
    assert all((a == b).all() for a, b in zip(mine, prog))


def test_thirteen_rounds_differ():
    a = reference.threefry2x32(1, 2, np.arange(64), np.arange(64))
    b = reference.threefry2x32(1, 2, np.arange(64), np.arange(64), 13)
    assert (a[0] != b[0]).all()


def test_routes_match_the_program_tables():
    from shadow_tpu.network.graph import load_graph
    from shadow_tpu.ops.prng import quantize_loss

    doc = gossip_10k.doc(4, TRAFFIC)
    g = load_graph(doc["network"]["graph"])
    net = reference.load(doc)
    assert net["lat"] == g.latency_ns.tolist()
    assert (np.array(net["thresh"]) == quantize_loss(g.reliability)).all()
    assert net["round_ns"] == g.min_latency_ns == 2_000_000


@pytest.mark.parametrize("text,ns", [("30s", 30 * 10**9), (30, 30 * 10**9),
                                     ("1 ms", 10**6), ("2 ms", 2 * 10**6)])
def test_parse_time(text, ns):
    assert reference.parse_time(text) == ns


@pytest.mark.parametrize("text,bps", [("50 Mbit", 6_250_000),
                                      ("100 Mbit", 12_500_000),
                                      ("1 Gbit", 125_000_000)])
def test_parse_bandwidth(text, bps):
    assert reference.parse_bandwidth(text) == bps


@pytest.mark.parametrize("seed", run.derive_seeds(12, 2))
def test_equals_the_per_unit_plane(seed, tmp_path):
    """A second witness: the program's thread_per_core plane (Python units,
    numpy draws, no C engine or device) gives the reference's result."""
    from shadow_tpu.config.schema import parse_config
    from shadow_tpu.core.controller import Controller

    doc = gossip_10k.doc(seed, TRAFFIC, gossip_10k.TINY)
    cfg = parse_config(copy.deepcopy(doc), {
        "general.data_directory": str(tmp_path),
        "experimental.scheduler_policy": "thread_per_core"})
    ctl = Controller(cfg, mirror_log=False)
    summary = ctl.run()
    prog = {k: summary[k] for k in reference.IDENTITY_KEYS}
    prog.update(gossip_10k.results(ctl, tmp_path))
    ref = reference.simulate(doc)
    assert not any(reference.mismatches([prog], ref).values())
    assert ref["bucket_waits"] == 0
