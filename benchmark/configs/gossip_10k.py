"""gossip_10k: a Bitcoin-style inv/getdata/tx flood over 10,000 nodes.

A copy of ``tools/gen_benchmarks.py::gen_gossip_10k`` at commit 9028760:
with the ``flood`` traffic and seed 4, ``text`` is
``examples/gossip_10k.yaml`` byte for byte. The traffic mix supplies the
originators, transactions per originator, their interval and the stop
time; ``general.seed`` is the run's ``--seed``.

What the harness reads of a configuration module: ``doc``, ``REFERENCE``
(its plain reference, a file under ``benchmark/``), ``results`` (the
compared readings the summary lacks, taken before the data directory is
removed) and ``TINY`` (the sizes of the CPU tests).
"""

from __future__ import annotations

import numpy as np
import yaml

from graphgen import indent, random_gml
from reference import nodes_digest

REFERENCE = "reference.py"

SOURCE = ("Bitcoin Core's documented defaults: 8 outbound full-relay peers, "
          "inv/getdata/tx relay; 10k is the order of reachable Bitcoin nodes")

#: nothing is cut from the source
REDUCED = {}

#: where the model departs from the source, each with what it stands on:
#: the model is the program's ``models/gossip.py`` as it is, which this
#: benchmark may not change (PERF.md section 7 lists them for later PRs)
ASSUMED = {
    "relay_direction": "INV goes only to a node's own 8 outbound picks, so "
                       "relay runs along directed edges; Bitcoin Core relays "
                       "to every connection, inbound too, up to 125 "
                       "(net.h DEFAULT_MAX_PEER_CONNECTIONS)",
    "transport": "messages are lossy datagrams with no retransmission; "
                 "Bitcoin Core runs over TCP, so a lost INV, GETDATA or TX "
                 "is resent there and is lost for good here",
    "trickle": "INV is sent at once; Bitcoin Core trickles it, on average "
               "every 2 s to outbound and 5 s to inbound peers "
               "(net_processing.cpp, *_INVENTORY_BROADCAST_INTERVAL)",
    "tx_rate": "10 transactions in 30 s, from 10 originators, one each; "
               "mainnet carries a few transactions every second",
    "graph": "seeded 64-node random graph (ring + 64 chords), 10-120 ms "
             "links, loss up to 0.2%, node bandwidth 50 or 100 Mbit",
    "tx_size": "400 B transactions, 64 B inv and getdata (models/gossip.py)",
    "graph_seed": "the graph comes from the fixed generator seed 20260704; "
                  "general.seed keys peer choice, announce times and loss",
    "results": "nodes log (received, known) only when they exit, and none "
               "exits before the stop, so the harness reads them from the "
               "nodes after the run",
}

SIZES = {"n_nodes": 10_000, "k_peers": 8, "graph_nodes": 64,
         "graph_seed": 20260704, "port": 7000}
TINY = {**SIZES, "n_nodes": 400}


def text(seed: int, traffic: dict, sizes: dict = SIZES) -> str:
    """The config as YAML text, as the repo's generator writes it."""
    rng = np.random.default_rng(sizes["graph_seed"])
    g = sizes["graph_nodes"]
    gml = random_gml(rng, g, min_lat_ms=10, max_lat_ms=120, max_loss=0.002,
                     bw_choices=("50 Mbit", "100 Mbit"))
    n, k, port = sizes["n_nodes"], sizes["k_peers"], sizes["port"]
    interval = traffic["interval_s"]
    out = [
        "# BASELINE.md config 4: 10k-node gossip (inv/getdata/tx) flood.",
        "# Generated by tools/gen_benchmarks.py (seeded) - do not hand-edit.",
        "general:",
        f"  stop_time: {traffic['stop_s']}s",
        f"  seed: {seed}",
        f"  heartbeat_interval: {traffic['heartbeat_s']}s",
        "network:",
        "  graph:",
        "    type: gml",
        "    inline: |",
        indent(gml, "      "),
        "hosts:",
    ]
    # host ids 0..n-1 in YAML order (GossipNode addresses peers by id):
    # the originators on node 0, then one listener template per graph node
    n_origin = traffic["originators"]
    out += [
        "  origin:",
        "    network_node_id: 0",
        f"    quantity: {n_origin}",
        "    processes:",
        "      - path: pyapp:shadow_tpu.models.gossip:GossipNode",
        f'        args: ["{port}", "{n}", "{k}", '
        f'"{traffic["txs_per_originator"]}", "{interval}"]',
    ]
    rest = n - n_origin
    per = rest // g
    for gi in range(g):
        cnt = per if gi < g - 1 else rest - per * (g - 1)
        out += [
            f"  n{gi}_:",
            f"    network_node_id: {gi}",
            f"    quantity: {cnt}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.gossip:GossipNode",
            f'        args: ["{port}", "{n}", "{k}", "0", "{interval}"]',
        ]
    return "\n".join(out) + "\n"


def doc(seed: int, traffic: dict, sizes: dict = SIZES) -> dict:
    """The config document of one experiment."""
    return yaml.safe_load(text(seed, traffic, sizes))


def node_results(ctl) -> tuple[list, list]:
    """Each gossip node's (received, known), in host order: the program
    keeps them in the node (or its C twin) and logs them only at exit.
    A node that never started has (0, 0)."""
    received, known = [], []
    for h in ctl.hosts:
        app = h.processes[0].app
        if app is None:
            r, k = 0, 0
        elif app._c is not None:
            r, k = app._c.stats()
        else:
            r, k = app.received_tx, len(app.seen)
        received.append(r)
        known.append(k)
    return received, known


def results(ctl, data_dir) -> dict:
    """The compared readings the summary lacks: a digest of every node's
    (received, known)."""
    return {"nodes_digest": nodes_digest(*node_results(ctl))}
