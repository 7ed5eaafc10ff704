"""tor_1_10: Tor at one tenth of the public network's relays, with web and
OnionPerf clients fetching through 3-hop circuits.

The deployment is what tornettools (https://github.com/shadow/tornettools)
``generate --network_scale 0.1`` describes: 650 relays, one host each,
1/10 of a ~6,500-relay consensus, each with its own up and down bandwidth
and a path-selection weight equal to it; Guard and Exit flags; TGen
servers; clients that build circuits over the C stream transport and fetch
from the servers through them. Running Tor networks at 10% scale over many
seeds is the discipline of Jansen, Tracey and Goldberg, *Once is Never
Enough* (USENIX Security 2021). The perf clients fetch OnionPerf's 50 KiB,
1 MiB and 5 MiB. No consensus, atlas graph or tgen model file is in the
repository, so the shapes that come from them are seeded draws, each listed
in ``ASSUMED``.

The traffic mix supplies the client counts, their fetch schedules and the
stop time; ``general.seed`` keys path choice, start times, sizes and loss
(each client's draws come from its host's Philox stream). The relays'
bandwidths and flags, the servers' places and the graph come from the
fixed generator seed.

What the harness reads of a configuration module: ``doc``, ``REFERENCE``,
``results`` and ``TINY``.
"""

from __future__ import annotations

import numpy as np

from reference_tor import fetch_digest, relay_digest

REFERENCE = "reference_tor.py"

SOURCE = ("tornettools generate --network_scale 0.1; Jansen, Tracey and "
          "Goldberg, Once is Never Enough, USENIX Security 2021; OnionPerf "
          "50 KiB, 1 MiB and 5 MiB fetches")

#: cut from the source (each is a key of the config's ``reduced`` list)
REDUCED = {
    "network_scale": "0.1: 650 relays of a ~6,500-relay consensus",
    "clients": "5,000 web + 50 perf clients (the traffic's), half of "
               "10,000 + 100, chosen to fit a run's time limit, not "
               "tornettools' user count: at 10,000 + 100 an experiment "
               "takes 19.4 s of wall on a TPU v5 lite host, at half 9.3 s",
    "stop_s": "40 sim-s against tornettools' usual hour after bootstrap; "
              "the stop cuts most 5 MiB fetches (they end at 129-217 "
              "sim-s), so a client is held to no final state and a fetch "
              "still open is recorded as it stands, with the bytes it got",
    "directory_traffic": "no directory or consensus fetches",
}

#: where the model departs from the source, each with what it stands on
ASSUMED = {
    "cells": "no cryptography, no 514-byte cell framing and no SENDME flow "
             "control: cells are 12-byte headers, DATA bodies are counted "
             "bytes (models/tor.py)",
    "streams": "one stream per circuit, and every fetch on a fresh "
               "circuit; Tor reuses a circuit for about 10 minutes",
    "graph": "a seeded 64-node graph in tornettools' GML schema (ring + 64 "
             "chords, 8-90 ms links, loss up to 0.1%, node bandwidths "
             "20,000-1,000,000 Kibit) stands in for its atlas",
    "relay_bandwidth": "log-normal, median 40 Mbit, sigma 1.0, clipped to "
                       "[2 Mbit, 1 Gbit], from the generator seed; Tor "
                       "Metrics shows a heavy tail of this order",
    "flags": "each relay is Guard-flagged with probability 1/2 and "
             "Exit-flagged with probability 1/5, independently (Tor "
             "Metrics shares, from memory); exits run TorExit",
    "weights": "a relay's path-selection weight is its bandwidth in kbit, "
               "by position with no Wgg/Wee-style position weights",
    "web_sizes": "web fetch sizes log-normal, median 20 KiB, sigma 1.0, "
                 "clipped to [1 KiB, 2 MiB]; think time uniform in "
                 "[1, 4) s; tgen's Markov models are not in the repo",
    "servers": "50 TGenServer hosts on seeded graph nodes, at their "
               "node's bandwidth; a client fetches from one server",
    "socket_buffers": "every socket's buffers fixed at Linux's autotuning "
                      "maxima (tcp_wmem 4 MiB, tcp_rmem 6 MiB); the "
                      "program has no autotuning",
    "log_level": "general.log_level warning: no host writes its info "
                 "lines (a client's line per fetch), so no per-host log "
                 "file is made; at info an experiment of a quarter of these "
                 "clients wrote about 2,600 of them, 2.3-7.1 s of "
                 "file-system calls on a TPU v5 "
                 "lite host, most of an experiment's wall and most of "
                 "its spread",
    "graph_seed": "graph, relays and servers come from the fixed "
                  "generator seed 20261015",
    "results": "clients keep their per-fetch records and relays their "
               "(cells, bytes) in memory; the harness reads them from "
               "the processes after the run",
}

#: socket send and receive buffers (bytes): Linux's tcp_wmem and tcp_rmem
#: maxima, which its buffer autotuning reaches on a bulk path
SOCKET_BUFFERS = (4194304, 6291456)

SIZES = {"relays": 650, "servers": 50, "graph_nodes": 64,
         "graph_seed": 20261015, "relay_port": 9001, "server_port": 80}
#: the CPU tests' size: client counts here override the traffic's
TINY = {**SIZES, "relays": 24, "servers": 4, "graph_nodes": 8,
        "web_clients": 40, "perf_clients": 3}


def tornet_gml(rng, g: int) -> str:
    """A topology in the tornettools output schema: city-labeled nodes
    with country codes and Kibit bandwidths, path-labeled edges with
    microsecond latencies and small packet_loss, a ring plus g chords and
    2 ms self-edges. A copy of ``tools/gen_benchmarks.py::tornet_gml``
    at commit 6fd9f79 that returns the text instead of writing a file."""
    cities = ["3365", "2263", "5067", "1180", "7431", "6660", "4121",
              "8922"]
    ccs = ["US", "DE", "FR", "GB", "NL", "JP", "CA", "AU"]
    lines = ["graph [", "  directed 0"]
    for i in range(g):
        city = cities[int(rng.integers(0, len(cities)))]
        cc = ccs[int(rng.integers(0, len(ccs)))]
        up = int(rng.integers(20_000, 1_000_000))  # Kibit/s, tornet-style
        down = int(rng.integers(20_000, 1_000_000))
        lines.append(
            f'  node [ id {i} label "city_{city}_{i}" country_code "{cc}" '
            f'host_bandwidth_up "{up} Kibit" '
            f'host_bandwidth_down "{down} Kibit" ]')
    edges = set()

    def add_edge(a, b):
        if a == b or (a, b) in edges or (b, a) in edges:
            return
        edges.add((a, b))
        lat_us = int(rng.integers(8_000, 90_000))
        loss = float(rng.random()) * 0.001
        lines.append(
            f'  edge [ source {a} target {b} label "path_{a}_{b}" '
            f'latency "{lat_us} us" packet_loss {loss:.6f} ]')

    for i in range(g):
        add_edge(i, (i + 1) % g)
    for _ in range(g):
        add_edge(int(rng.integers(0, g)), int(rng.integers(0, g)))
    for i in range(g):
        lines.append(f'  edge [ source {i} target {i} '
                     f'label "path_{i}_{i}" latency "2000 us" '
                     f'packet_loss 0.0 ]')
    lines.append("]")
    return "\n".join(lines) + "\n"


def relays(rng, n: int) -> list:
    """(kbit bandwidth, flags) of relay0..relay{n-1}."""
    out = []
    for _ in range(n):
        kbit = int(np.clip(rng.lognormal(np.log(40_000), 1.0), 2_000,
                           1_000_000))
        flags = ("G" if rng.random() < 0.5 else "") + \
                ("E" if rng.random() < 0.2 else "")
        out.append((kbit, flags))
    for i, f in ((0, "G"), (1, "E")):  # at least one of each
        if not any(f in fl for _, fl in out):
            out[i] = (out[i][0], out[i][1] + f)
    return out


def doc(seed: int, traffic: dict, sizes: dict = SIZES) -> dict:
    """The config document of one experiment."""
    rng = np.random.default_rng(sizes["graph_seed"])
    g = sizes["graph_nodes"]
    gml = tornet_gml(rng, g)
    rel = relays(rng, sizes["relays"])
    n = len(rel)
    port, sport = sizes["relay_port"], sizes["server_port"]
    spec = ",".join(f"{k}:{f}" for k, f in rel)
    hosts = {}
    for i, (kbit, flags) in enumerate(rel):
        cls = "TorExit" if "E" in flags else "TorRelay"
        hosts[f"relay{i}"] = {
            "network_node_id": int(rng.integers(0, g)),
            "bandwidth_up": f"{kbit} kbit", "bandwidth_down": f"{kbit} kbit",
            "processes": [{"path": f"pyapp:shadow_tpu.models.tor:{cls}",
                           "args": [str(port)]}]}
    n_srv = sizes["servers"]
    for i in range(n_srv):
        hosts[f"web{i}"] = {
            "network_node_id": int(rng.integers(0, g)),
            "processes": [{"path": "pyapp:shadow_tpu.models.tgen:TGenServer",
                           "args": [str(sport)]}]}
    web, perf = traffic["web"], traffic["perf"]
    lo, hi = web["size_clip"]
    schedules = {
        "web": (web["fetches"],
                f"delay=uniform:{web['start_ms'][0]}:{web['start_ms'][1]};"
                f"size=lognormal:{web['size_median']}:{web['size_sigma']}:"
                f"{lo}:{hi};"
                f"think=uniform:{web['think_ms'][0]}:{web['think_ms'][1]}"),
        "perf": (len(perf["sizes"]),
                 f"delay=uniform:{perf['start_ms'][0]}:{perf['start_ms'][1]};"
                 f"sizes={':'.join(str(s) for s in perf['sizes'])};"
                 f"think=fixed:{perf['pause_ms']}"),
    }
    # one template per (kind, graph node): a client's server is fixed by
    # its template, the servers taken in turn
    k = 0
    for kind in ("web", "perf"):
        count = sizes.get(f"{kind}_clients", traffic[kind]["clients"])
        fetches, sched = schedules[kind]
        per = count // g
        for gi in range(g):
            q = per + (1 if gi < count - per * g else 0)
            if q == 0:
                continue
            hosts[f"{kind}{gi}_"] = {
                "network_node_id": gi, "quantity": q,
                "processes": [{
                    "path": "pyapp:shadow_tpu.models.tor:TorClient",
                    "args": [str(n), str(port), f"web{k % n_srv}",
                             str(sport), "0", str(fetches), "0", spec,
                             sched]}]}
            k += 1
    return {"general": {"stop_time": f"{traffic['stop_s']}s", "seed": seed,
                        "log_level": "warning"},
            "network": {"graph": {"type": "gml", "inline": gml}},
            "experimental": {"socket_send_buffer": SOCKET_BUFFERS[0],
                             "socket_recv_buffer": SOCKET_BUFFERS[1]},
            "hosts": hosts}


def results(ctl, data_dir) -> dict:
    """The compared readings the summary lacks: digests of the clients'
    per-fetch records (a fetch the stop cut as it stands) and of the
    relays' (cells, bytes), read from the processes (or their C twins)
    after the run."""
    records, stats = [], []
    for h in ctl.hosts:
        app = h.processes[0].app
        kind = type(app).__name__
        if kind == "TorClient":
            records.append(app.fetch_records())
        elif kind in ("TorRelay", "TorExit"):
            if app._c is not None:
                stats.append(tuple(app._c.stats()))
            else:
                stats.append((app.cells_relayed, app.bytes_relayed))
    return {"fetch_digest": fetch_digest(records),
            "relay_digest": relay_digest(stats)}
