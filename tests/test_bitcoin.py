"""The Bitcoin relay model (models/bitcoin.py and its C half) at the
benchmark config's tiny size: equal on every compared key to the plain
reference (benchmark/reference_bitcoin.py), its timer, block-relay-only
and reconstruction rules read off the C nodes, and the same work in every
experiment whatever the seed."""

import copy
import json
import sys
from pathlib import Path

import pytest

from shadow_tpu.config import parse_config
from shadow_tpu.core.controller import Controller
from shadow_tpu.models.bitcoin import MESSAGES

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
for _p in (BENCH, BENCH / "configs"):
    if str(_p) not in sys.path:
        sys.path.append(str(_p))

import bitcoin_10k  # noqa: E402
import reference_bitcoin  # noqa: E402

TRAFFIC = json.loads((BENCH / "traffic" / "mainnet_block.json").read_text())
SEEDS = [3, 41, 977, 2147483659, 11, 12, 13, 14]
#: the seeds also compared with the reference
COMPARED = SEEDS[:4]


def doc(seed):
    return bitcoin_10k.doc(seed, TRAFFIC, bitcoin_10k.TINY)


def run_program(seed, tmp):
    cfg = parse_config(copy.deepcopy(doc(seed)), {
        "general.data_directory": str(tmp / f"btc{seed}"),
        "experimental.scheduler_policy": "tpu_batch"})
    ctl = Controller(cfg, mirror_log=False)
    summary = ctl.run()
    ident = {k: summary[k] for k in reference_bitcoin.IDENTITY_KEYS}
    ident.update(bitcoin_10k.results(ctl, None))
    nodes = [h.processes[0].app._c for h in ctl.hosts]
    return {"ident": ident, "summary": summary,
            "stats": [n.stats() for n in nodes],
            "peers": [n.peer_info() for n in nodes],
            "txs": ctl.hosts[0].processes[0].app.txs}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("btc")
    return {s: run_program(s, tmp) for s in SEEDS}


@pytest.mark.parametrize("seed", COMPARED)
def test_program_equals_the_reference(runs, seed):
    ref = reference_bitcoin.simulate(doc(seed))
    got = runs[seed]["ident"]
    assert not any(reference_bitcoin.mismatches([got], ref).values()), {
        k: (got[k], ref[k]) for k in reference_bitcoin.KEYS
        if got[k] != ref[k]}
    # the block reached every node, and most had to ask for some of it
    assert ref["block_reached"] == ref["nodes"] == 300
    assert ref["getblocktxn"] > 100


@pytest.mark.parametrize("seed", COMPARED)
def test_outbound_peers_time_their_own_inv_inbound_peers_share_one(runs,
                                                                    seed):
    r = runs[seed]
    fired = r["summary"]["phase_calls"]["btc.trickles"]
    total = 0
    for stats, peers in zip(r["stats"], r["peers"]):
        inbound_fires = stats[4]
        total += inbound_fires
        for _host, outbound, full, _hb, invs, _txs, _blk, fires in peers:
            total += fires
            if outbound:
                # each INV to an outbound peer is one firing of its timer
                assert invs <= fires
            else:
                # no inbound peer has a timer of its own: the node's one
                # sends to all of them
                assert fires == 0
                assert invs <= inbound_fires
    assert total == fired > 0
    # the inbound timer serves several peers at a firing: more INVs went to
    # inbound peers than it fired
    inv_in = sum(p[4] for ps in r["peers"] for p in ps if not p[1])
    assert inv_in > sum(s[4] for s in r["stats"])


@pytest.mark.parametrize("seed", COMPARED)
def test_block_relay_only_links_carry_no_transaction(runs, seed):
    r = runs[seed]
    block_only = [p for ps in r["peers"] for p in ps if not p[2]]
    # 2 outbound block-relay-only links per node, seen from both ends
    assert len(block_only) == 2 * 2 * 300
    assert all(p[4] == 0 and p[5] == 0 for p in block_only)
    assert sum(p[6] for p in block_only) > 0  # the block did go over them
    full = [p for ps in r["peers"] for p in ps if p[2]]
    assert sum(p[4] for p in full) > 0 and sum(p[5] for p in full) > 0


@pytest.mark.parametrize("seed", COMPARED)
def test_a_node_missing_k_block_txs_asks_once_for_those_k(runs, seed):
    r = runs[seed]
    txs = r["txs"]
    block = sum(1 << i for i, (t, _) in enumerate(txs)
                if t < TRAFFIC["block_s"] * 10**9)
    asked = 0
    for known, block_ns, gbt, gbt_mask, _, sent in r["stats"]:
        assert block_ns >= 0 and known & block == block
        assert gbt in (0, 1) and sent[MESSAGES.index("getblocktxn")] == gbt
        if gbt:
            asked += 1
            assert gbt_mask and gbt_mask & ~block == 0
        else:
            assert gbt_mask == 0
    assert asked > 100
    ref = reference_bitcoin.BtcSim(doc(seed), 20)
    ref.run()
    want = [(n.gbt_sent, n.gbt_mask) for n in
            (ref.btc[h] for h in sorted(ref.btc))]
    assert [(s[2], s[3]) for s in r["stats"]] == want


@pytest.mark.parametrize("key", ["units_sent", "events"])
def test_every_experiment_does_the_same_work(runs, key):
    """The traffic fixes each experiment's work; the seed only places it."""
    vals = [runs[s]["ident"][key] for s in SEEDS]
    mean = sum(vals) / len(vals)
    assert max(abs(v - mean) for v in vals) <= 0.05 * mean, vals


def test_work_is_fixed_and_placed_by_the_seed():
    a, (ta, ma) = bitcoin_10k.work(5, TRAFFIC)
    b, (tb, mb) = bitcoin_10k.work(6, TRAFFIC)
    assert len(a) == len(b) == round(6 * 9) == 54
    assert ta == tb == 4 * 10**9
    assert a != b
    assert all(10**9 <= t < 10 * 10**9 for t, _ in a)
    assert [t for t, _ in a] == sorted(t for t, _ in a)
    assert bitcoin_10k.work(5, TRAFFIC) == (a, (ta, ma))
