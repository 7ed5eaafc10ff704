"""bitcoin_10k: Bitcoin Core's transaction and block relay on 10,000
reachable nodes.

The deployment is the public network's reachable nodes, one Bitcoin Core
node per host with its defaults (``src/net.h``: 8 outbound full-relay and
2 block-relay-only connections, 125 connections at most;
``net_processing.cpp``: INV trickled every 2 s on average to each
outbound peer and every 5 s to the inbound ones; BIP 152 compact blocks
with 3 high-bandwidth peers), on one chip: nothing is sharded. Shadow
runs this network in Miller and Jansen, *Shadow-Bitcoin* (CSET 2015).
The model is ``shadow_tpu/models/bitcoin.py`` over the C stream
transport; the nodes sit on ``gossip_10k``'s seeded 64-node graph, spread
evenly over its nodes.

Each experiment's work is fixed by the traffic and only placed by
``general.seed``: exactly ``round(tx_rate * (stop_s - tx_start_s))``
transactions, at times uniform in [tx_start_s, stop_s) (a Poisson process
conditioned on its count) from origins uniform over the nodes, and one
block at exactly ``block_s`` from a miner drawn the same way. The seed
also keys each node's peers, start time and trickle draws (its host's
Philox stream) and the loss draws.

What the harness reads of a configuration module: ``doc``, ``REFERENCE``,
``results`` and ``TINY``.
"""

from __future__ import annotations

import numpy as np

from graphgen import random_gml
from reference_bitcoin import msg_digest, node_digest

REFERENCE = "reference_bitcoin.py"

SOURCE = ("Bitcoin Core defaults: src/net.h (8 full-relay + 2 "
          "block-relay-only outbound, 125 peers), net_processing.cpp "
          "(2 s/5 s INV trickle); BIP 152 compact blocks; Miller & "
          "Jansen, Shadow-Bitcoin, CSET 2015")

#: cut from the source (each is a key of the config's ``reduced`` list)
REDUCED = {
    "nodes": "10,000 listening nodes of the 15,000-17,000 reachable ones, "
             "and none of the non-listening nodes behind them",
    "duration": "one block in an experiment of 10 sim-s around it (6 tx/s "
                "from 1 s, the block at 4 s) in place of hours of blocks "
                "every 10 minutes",
    "mempool": "a block's older transactions are counted in its size and "
               "held by every mempool from the start, not relayed",
}

#: where the model departs from the source, each with what it stands on
ASSUMED = {
    "inbound_cap": "inbound connections are capped at 125 - 10 = 115; the "
                   "cap never binds here: the mean inbound count is 10",
    "trickle": "a trickle timer is armed when its queue turns non-empty "
               "and fires at its exact time; Core's timers run freely and "
               "are read in its message loop. Poisson is memoryless, so the "
               "first INV after a queue fills comes after the same law",
    "trickle_draws": "delays are int(-log(u) * mean), u from a splitmix64 "
                     "stream seeded from the host RNG, not Core's "
                     "GetExponentialRand over its own RNG",
    "known_filter": "a peer's known set is exact (every tx it announced, "
                    "sent or was sent), where Core's is a rolling bloom "
                    "filter; INV entries are sent in txid order, not Core's "
                    "mempool order, and never exceed Core's per-INV cap",
    "getdata": "entries are requested at once from the first announcer, "
               "inbound or outbound: no 2 s wait for inbound announcers "
               "and no retry to another announcer (streams are reliable "
               "and never time out here)",
    "handshake": "version, wtxidrelay, verack, then sendheaders and one "
                 "sendcmpct each way; no sendaddrv2, ping, getheaders, "
                 "feefilter or addr traffic",
    "high_bandwidth": "a node asks its first 3 outbound peers to finish the "
                      "handshake for high-bandwidth compact blocks and "
                      "keeps them; Core picks the last 3 peers that gave it "
                      "a new block first",
    "block": "the block holds every transaction of the experiment made "
             "before 4 s, as if its miner had seen each one as it was made "
             "(most have not reached a tenth of the nodes by then); zero "
             "validation delay; a second cmpctblock while a getblocktxn is "
             "open is ignored, not reconstructed in parallel",
    "sizes": "version 102 B, sendcmpct 9, inv and getdata a CompactSize "
             "count and 36 B an entry, tx 400 B (models/gossip.py), headers "
             "82, getdata(CMPCT_BLOCK) 37, cmpctblock 88 + 6 * 3,000 + 250, "
             "getblocktxn 32 + count + 3 B an index, blocktxn 32 + count + "
             "400 B a tx; each with a 24-byte header",
    "writes": "the messages a node queues to a peer in one handler leave "
              "as one write, as Core's MSG_MORE sends coalesce",
    "graph": "gossip_10k's seeded 64-node graph (ring + 64 chords, 10-120 "
             "ms links, loss up to 0.2%, 50 or 100 Mbit per node) stands in "
             "for the internet's; socket buffers are the program's defaults",
    "placement": "the nodes are spread evenly over the graph's nodes (157 "
                 "or 156 each), in blocks of consecutive host ids",
    "routing": "experimental.tpu_device_floor is 2**30, so the C barrier "
               "draws every loss inline and hands no batch to device "
               "windows; speculative windows still run on the device. Under "
               "the default break-even a third of an experiment's units went "
               "through windows or the numpy twin, which made it 1.2-1.8 s "
               "slower than the same seed drawn inline and flipped between "
               "experiments of one run (TPU v5 lite; the Tor cell's default "
               "routing already keeps every draw inline)",
}

#: the device floor that keeps every batch's loss draws inline in C
DEVICE_FLOOR = 1 << 30

SIZES = {"n_nodes": 10_000, "graph_nodes": 64, "graph_seed": 20260704,
         "port": 8333}
#: the CPU tests' size (its own stop)
TINY = {**SIZES, "n_nodes": 300, "graph_nodes": 8, "stop_s": 6}

NS = 1_000_000_000


def work(seed: int, traffic: dict, sizes: dict = SIZES) -> tuple:
    """The experiment's transactions, each (ns, origin) in txid order, and
    its block's (ns, miner): the count and the block's time fixed, the
    rest drawn from ``seed``."""
    n = sizes["n_nodes"]
    stop = sizes.get("stop_s", traffic["stop_s"])
    t0 = traffic["tx_start_s"]
    k = round(traffic["tx_rate"] * (stop - t0))
    rng = np.random.default_rng(seed)
    times = rng.integers(int(t0 * NS), int(stop * NS), size=k)
    origins = rng.integers(0, n, size=k)
    miner = int(rng.integers(0, n))
    order = np.argsort(times, kind="stable")
    txs = [(int(times[i]), int(origins[i])) for i in order]
    return txs, (int(traffic["block_s"] * NS), miner)


def doc(seed: int, traffic: dict, sizes: dict = SIZES) -> dict:
    """The config document of one experiment."""
    grng = np.random.default_rng(sizes["graph_seed"])
    g = sizes["graph_nodes"]
    gml = random_gml(grng, g, min_lat_ms=10, max_lat_ms=120, max_loss=0.002,
                     bw_choices=("50 Mbit", "100 Mbit"))
    n, port = sizes["n_nodes"], sizes["port"]
    txs, (block_ns, miner) = work(seed, traffic, sizes)
    args = [str(port), str(n), ",".join(f"{t}:{o}" for t, o in txs),
            f"{block_ns}:{miner}"]
    hosts = {}
    per = n // g
    for gi in range(g):
        q = per + (1 if gi < n - per * g else 0)
        if q:
            hosts[f"n{gi}_"] = {
                "network_node_id": gi, "quantity": q,
                "processes": [{
                    "path": "pyapp:shadow_tpu.models.bitcoin:BitcoinNode",
                    "args": args}]}
    stop = sizes.get("stop_s", traffic["stop_s"])
    return {"general": {"stop_time": f"{stop}s", "seed": seed,
                        "log_level": "warning"},
            "network": {"graph": {"type": "gml", "inline": gml}},
            "experimental": {"tpu_device_floor": DEVICE_FLOOR},
            "hosts": hosts}


def results(ctl, data_dir) -> dict:
    """The compared readings the summary lacks: digests of every node's
    (known transactions, block arrival, getblocktxn sent) and of its sent
    count per message type, read from the nodes after the run."""
    nodes, msgs = [], []
    for h in ctl.hosts:
        known, block_ns, gbt, _, _, sent = h.processes[0].app._c.stats()
        nodes.append((known, block_ns, gbt))
        msgs.append(tuple(sent))
    return {"node_digest": node_digest(nodes), "msg_digest": msg_digest(msgs)}
