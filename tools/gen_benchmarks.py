"""Deterministic generator for the committed benchmark configs.

Produces BASELINE.md configs #2 and #4 (the reference's 1k-host random-graph
tgen bulk transfer and the 10k-node gossip flood). Run from the repo root:

    python tools/gen_benchmarks.py

The outputs (examples/tgen_1k.yaml, examples/gossip_10k.yaml) are committed
so benchmarks are reproducible without re-running this script; regenerate
only when deliberately changing the benchmark shape. ``tor_doc`` builds
config #5 (Tor) in memory at any scale, for bench.py and chip_smoke.py.
"""

from __future__ import annotations

import numpy as np


def random_gml(rng, g, min_lat_ms=5, max_lat_ms=80, max_loss=0.004,
               bw_choices=("100 Mbit", "250 Mbit", "1 Gbit")) -> str:
    """Connected random graph: a ring (guarantees connectivity) plus g
    random chords. Latencies, loss, and node bandwidth defaults are drawn
    deterministically from ``rng``."""
    lines = ["graph [", "  directed 0"]
    for i in range(g):
        bw = bw_choices[int(rng.integers(0, len(bw_choices)))]
        lines.append(
            f'  node [ id {i} host_bandwidth_up "{bw}" host_bandwidth_down "{bw}" ]'
        )
    edges = set()

    def add_edge(a, b):
        if a == b or (a, b) in edges or (b, a) in edges:
            return
        edges.add((a, b))
        lat = int(rng.integers(min_lat_ms, max_lat_ms + 1))
        loss = float(rng.random()) * max_loss
        loss_attr = f" packet_loss {loss:.5f}" if loss > 0.0005 else ""
        lines.append(
            f'  edge [ source {a} target {b} latency "{lat} ms"{loss_attr} ]'
        )

    for i in range(g):
        add_edge(i, (i + 1) % g)
    for _ in range(g):
        add_edge(int(rng.integers(0, g)), int(rng.integers(0, g)))
    for i in range(g):  # intra-node latency (hosts on the same node)
        lines.append(f'  edge [ source {i} target {i} latency "2 ms" ]')
    lines.append("]")
    return "\n".join(lines)


def indent(text, pad):
    return "\n".join(pad + ln for ln in text.splitlines())


def tornet_gml(rng, g, path) -> None:
    """A topology file in the tornettools output schema (the Shadow v2
    network-graph GML tornettools generates from its atlas): city-labeled
    nodes carrying country codes and Kibit bandwidths, path-labeled edges
    with microsecond latencies and small float packet_loss. BASELINE.md
    config #3 consumes this as a committed FILE fixture, so the format the
    reference's tooling emits is exercised end to end (parser tolerance
    for unknown attributes, base-1024 bit units, us-latencies, file-based
    graph references)."""
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
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


def tor_doc(n_relays: int, n_clients: int, stop_s: int,
             fetch: str = "20 kB") -> dict:
    """Config #5 generator (BASELINE.md): the uniform smoke shape of
    onion routing — TorRelay/TorExit relays, TGen web servers, TorClients
    building one 3-hop circuit each, hops drawn uniformly (exits from the
    first n_relays // 8 relays), one fetch at start, on a 64-node random
    graph. Deterministic from the fixed seed. ``tor_doc(700, 10_000, 8)``
    is the chip_smoke.py workload: 10,720 hosts. The tornettools-shaped
    deployment (bandwidth-weighted Guard/Exit relays, web and OnionPerf
    fetch schedules) is ``benchmark/configs/tor_1_10.py``."""
    rng = np.random.default_rng(42)
    g = 64
    gml = random_gml(rng, g, min_lat_ms=10, max_lat_ms=120, max_loss=0.002,
                     bw_choices=("50 Mbit", "100 Mbit", "1 Gbit"))
    hosts = {}
    n_exits = max(1, n_relays // 8)  # exits FIRST: clients draw their last hop
    # from relay0..relay{n_exits-1} (TorClient's n_exits arg)
    for i in range(n_relays):
        cls = "TorExit" if i < n_exits else "TorRelay"
        hosts[f"relay{i}"] = {
            "network_node_id": int(rng.integers(0, g)),
            "processes": [{"path": f"pyapp:shadow_tpu.models.tor:{cls}",
                           "args": ["9001"]}]}
    for i in range(20):
        hosts[f"web{i}"] = {
            "network_node_id": int(rng.integers(0, g)),
            "processes": [{"path": "pyapp:shadow_tpu.models.tgen:TGenServer",
                           "args": ["80"]}]}
    per = n_clients // g
    for i in range(g):
        q = per + (n_clients - per * g if i == g - 1 else 0)
        hosts[f"u{i}_"] = {
            "network_node_id": i, "quantity": q,
            "processes": [{"path": "pyapp:shadow_tpu.models.tor:TorClient",
                           "args": [str(n_relays), "9001", f"web{i % 20}",
                                    "80", fetch, "1", str(n_exits)],
                           "start_time": f"{2000 + i * 150} ms"}]}
    return {"general": {"stop_time": f"{stop_s}s", "seed": 6},
            "network": {"graph": {"type": "gml", "inline": gml}},
            "hosts": hosts}


def gen_tgen_1k(path="examples/tgen_1k.yaml"):
    """Config #2: 1000 hosts on a 32-node random graph; 50 servers, 950
    clients each fetching 2 MB from 2 seeded-random servers (bulk transfer,
    stresses the egress token buckets and the batched barrier)."""
    rng = np.random.default_rng(20260730)
    g = 32
    gml = random_gml(rng, g, max_loss=0.001)
    out = [
        "# BASELINE.md config 2: 1k-host random-graph tgen bulk transfer.",
        "# Generated by tools/gen_benchmarks.py (seeded) - do not hand-edit.",
        "general:",
        "  stop_time: 60s",
        "  seed: 2",
        "  heartbeat_interval: 20s",
        "network:",
        "  graph:",
        "    type: gml",
        "    inline: |",
        indent(gml, "      "),
        "hosts:",
    ]
    n_servers = 50
    for i in range(n_servers):
        node = int(rng.integers(0, g))
        out += [
            f"  server{i}:",
            f"    network_node_id: {node}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.tgen:TGenServer",
            '        args: ["8080"]',
        ]
    # clients grouped by (node, servers) template via quantity where shapes
    # repeat is impossible here (peers differ), so emit each host; YAML is
    # ~7k lines but trivially parsed.
    n_clients = 950
    for i in range(n_clients):
        node = int(rng.integers(0, g))
        peers = rng.choice(n_servers, size=2, replace=False)
        start_ms = 1000 + int(rng.integers(0, 5000))
        out += [
            f"  client{i}:",
            f"    network_node_id: {node}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.tgen:TGenClient",
            f'        args: ["1 MB", "1", serial, "8080", '
            f'server{int(peers[0])}, server{int(peers[1])}]',
            f"        start_time: {start_ms} ms",
            "        expected_final_state: {exited: 0}",
        ]
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"wrote {path}: {n_servers} servers + {n_clients} clients on {g} nodes")


def gen_gossip_10k(path="examples/gossip_10k.yaml"):
    """Config #4: 10k-node inv/getdata/tx gossip flood on a 64-node random
    graph; 20 originators, k=8 peers. One tx floods ~90k datagrams across
    the network within a few RTTs — the high-event-fan-out stress."""
    rng = np.random.default_rng(20260704)
    g = 64
    gml = random_gml(rng, g, min_lat_ms=10, max_lat_ms=120, max_loss=0.002,
                    bw_choices=("50 Mbit", "100 Mbit"))
    n = 10_000
    out = [
        "# BASELINE.md config 4: 10k-node gossip (inv/getdata/tx) flood.",
        "# Generated by tools/gen_benchmarks.py (seeded) - do not hand-edit.",
        "general:",
        "  stop_time: 30s",
        "  seed: 4",
        "  heartbeat_interval: 10s",
        "network:",
        "  graph:",
        "    type: gml",
        "    inline: |",
        indent(gml, "      "),
        "hosts:",
    ]
    # host ids 0..n-1 assigned in YAML order (GossipNode addresses peers by
    # id): first a small originator template on node 0, then per-graph-node
    # quantity-expanded listener templates
    n_origin = 10
    out += [
        "  origin:",
        "    network_node_id: 0",
        f"    quantity: {n_origin}",
        "    processes:",
        "      - path: pyapp:shadow_tpu.models.gossip:GossipNode",
        f'        args: ["7000", "{n}", "8", "1", "5.0"]',
    ]
    rest = n - n_origin
    per = rest // g
    made = 0
    for gi in range(g):
        cnt = per if gi < g - 1 else rest - per * (g - 1)
        out += [
            f"  n{gi}_:",
            f"    network_node_id: {gi}",
            f"    quantity: {cnt}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.gossip:GossipNode",
            f'        args: ["7000", "{n}", "8", "0", "5.0"]',
        ]
        made += cnt
    assert made == rest
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"wrote {path}: {n} hosts on {g} nodes")


def gen_gossip_churn(path="examples/gossip_churn.yaml"):
    """Churn variant of the gossip flood (fault-injection smoke config,
    tools/ci.sh): a small gossip network plus one bulk stream crossing a
    partition that heals, with seeded host churn — every fault kind the
    faults: schema offers, at a scale the CI gate can run in seconds.
    Originators re-announce so floods cut off by the partition restart
    after heal; the tgen client rides the transport's RTO recovery and
    ETIMEDOUT-retry path."""
    rng = np.random.default_rng(20260803)
    g = 8
    gml = random_gml(rng, g, min_lat_ms=10, max_lat_ms=60, max_loss=0.002,
                     bw_choices=("50 Mbit", "100 Mbit"))
    n = 120  # gossip population (originators + listeners + churned)
    out = [
        "# Fault-injection smoke config (tools/ci.sh; see README 'Fault",
        "# injection'): gossip flood + one bulk stream under a healing",
        "# bipartite partition, a degrade window, and seeded host churn.",
        "# Generated by tools/gen_benchmarks.py (seeded) - do not hand-edit.",
        "general:",
        "  stop_time: 40s",
        "  seed: 7",
        "network:",
        "  graph:",
        "    type: gml",
        "    inline: |",
        indent(gml, "      "),
        "hosts:",
        "  origin:",
        "    network_node_id: 0",
        "    quantity: 6",
        "    processes:",
        "      - path: pyapp:shadow_tpu.models.gossip:GossipNode",
        f'        args: ["7000", "{n}", "6", "2", "4.0"]',
        '        environment: {GOSSIP_REANNOUNCE_SEC: "4"}',
    ]
    made = 6
    per = (n - 6 - 10) // g  # 10 churned hosts ride their own template
    for gi in range(g):
        cnt = per if gi < g - 1 else (n - 6 - 10) - per * (g - 1)
        out += [
            f"  n{gi}_:",
            f"    network_node_id: {gi}",
            f"    quantity: {cnt}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.gossip:GossipNode",
            f'        args: ["7000", "{n}", "6", "0", "4.0"]',
        ]
        made += cnt
    out += [
        "  churn_:",
        "    network_node_id: 3",
        "    quantity: 10",
        "    processes:",
        "      - path: pyapp:shadow_tpu.models.gossip:GossipNode",
        f'        args: ["7000", "{n}", "6", "0", "4.0"]',
    ]
    made += 10
    assert made == n, made
    out += [
        "  bulk_server:",
        "    network_node_id: 0",
        "    processes:",
        "      - path: pyapp:shadow_tpu.models.tgen:TGenServer",
        '        args: ["8080"]',
        "  bulk_client:",
        "    network_node_id: 4",
        "    processes:",
        "      - path: pyapp:shadow_tpu.models.tgen:TGenClient",
        '        args: ["2 MB", "1", serial, "8080", bulk_server]',
        "        start_time: 3s",
        '        environment: {TGEN_RETRIES: "2", TGEN_IDLE_TIMEOUT_SEC: "20"}',
        "        expected_final_state: {exited: 0}",
        "faults:",
        "  events:",
        "    # bipartite partition mid-stream, healed 5 s later: the bulk",
        "    # transfer stalls on RTO backoff and resumes after the heal",
        "    - {time: 4s, kind: link_down, src_nodes: [0, 1, 2, 3],",
        "       dst_nodes: [4, 5, 6, 7], duration: 5s}",
        "    # degrade window across the same cut: slower, lossier, thinner",
        "    - {time: 10s, kind: link_degrade, src_nodes: [0],",
        "       dst_nodes: [4], latency_factor: 2.0, loss_add: 0.02,",
        "       bandwidth_scale: 0.5, duration: 5s}",
        "  churn:",
        '    - {hosts: ["churn_*"], mean_uptime: 6s, mean_downtime: 2s,',
        "       start_time: 2s}",
    ]
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"wrote {path}: {n} gossip hosts + 1 stream pair on {g} nodes, "
          f"partition/degrade/churn timeline")


def gen_tor_400(path="examples/tor_400relay.yaml"):
    """Config #3: Tor 5%-scale shape — 400 relays (tornettools-style city
    graph), 10 web servers, 600 clients each building circuits and fetching
    through 3 hops. Stresses multi-hop relaying and relay fan-in."""
    rng = np.random.default_rng(20260303)
    g = 30
    # the topology rides a committed tornettools-format FILE fixture
    # (BASELINE #3 names a "tornettools-generated topology")
    tornet_gml(rng, g, "examples/topology_tornet400.gml")
    out = [
        "# BASELINE.md config 3: Tor-5%-scale onion-routing workload on a",
        "# tornettools-format topology fixture (examples/topology_tornet400.gml).",
        "# Generated by tools/gen_benchmarks.py (seeded) - do not hand-edit.",
        "general:",
        "  stop_time: 60s",
        "  seed: 3",
        "  heartbeat_interval: 20s",
        "network:",
        "  graph:",
        "    type: gml",
        "    file: topology_tornet400.gml",
        "hosts:",
    ]
    n_relays, n_web, n_clients = 400, 10, 600
    n_exits = max(1, n_relays // 8)  # exits first (relay0..relayE-1); clients
    # draw their last hop from this population (TorClient n_exits arg)
    for i in range(n_relays):
        cls = "TorExit" if i < n_exits else "TorRelay"
        out += [
            f"  relay{i}:",
            f"    network_node_id: {int(rng.integers(0, g))}",
            "    processes:",
            f"      - path: pyapp:shadow_tpu.models.tor:{cls}",
            '        args: ["9001"]',
        ]
    out += [
        "  web:",
        "    network_node_id: 0",
        f"    quantity: {n_web}",
        "    processes:",
        "      - path: pyapp:shadow_tpu.models.tgen:TGenServer",
        '        args: ["80"]',
    ]
    for i in range(n_clients):
        web = f"web{int(rng.integers(0, n_web))}"
        start_ms = 1000 + int(rng.integers(0, 8000))
        out += [
            f"  user{i}:",
            f"    network_node_id: {int(rng.integers(0, g))}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.tor:TorClient",
            f'        args: ["{n_relays}", "9001", {web}, "80", "500 kB",'
            f' "1", "{n_exits}"]',
            f"        start_time: {start_ms} ms",
            "        expected_final_state: {exited: 0}",
        ]
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"wrote {path}: {n_relays} relays, {n_web} servers, {n_clients} clients")


def gen_web_cdn(path="examples/web_cdn.yaml"):
    """Modern-web smoke/bench config (ROADMAP item 4): an HTTP/CDN tier —
    2 origins behind 8 edge caches (70% hit ratio) serving 300 page-loop
    clients, with a 2-level DNS referral chain (root -> auth) under 4
    caching resolvers — plus a fault timeline (a healing partition that
    cuts half the clients off their edges, and a degrade window that adds
    loss across the origin path, exercising SACK recovery on
    request/response traffic). Clients ride quantity templates: edge
    choice is seeded per-host RNG, so one template yields diverse
    traffic."""
    rng = np.random.default_rng(20260804)
    g = 12
    gml = random_gml(rng, g, min_lat_ms=5, max_lat_ms=60, max_loss=0.002,
                     bw_choices=("50 Mbit", "100 Mbit", "250 Mbit"))
    out = [
        "# Modern-web HTTP/CDN benchmark (ROADMAP item 4; tools/ci.sh",
        "# web smoke gate): clients -> edge caches (70% hit) -> origin,",
        "# DNS referral chain + caching resolvers, fault timeline with a",
        "# partition and a lossy degrade window (SACK recovery on",
        "# request/response traffic).",
        "# Generated by tools/gen_benchmarks.py (seeded) - do not hand-edit.",
        "general:",
        "  stop_time: 40s",
        "  seed: 9",
        "network:",
        "  graph:",
        "    type: gml",
        "    inline: |",
        indent(gml, "      "),
        "telemetry:",
        "  sample_every: 10s",
        "hosts:",
    ]
    n_edges, n_res, n_clients = 8, 4, 300
    edges = [f"edge{i}" for i in range(n_edges)]
    for i in range(2):
        out += [
            f"  origin{i}:",
            f"    network_node_id: {i}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.web:WebOrigin",
            '        args: ["80"]',
        ]
    # 2-level referral chain: one root + one auth, resolvers walk both
    for name, node in (("dnsroot", 0), ("dnsauth", 1)):
        out += [
            f"  {name}:",
            f"    network_node_id: {node}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.dns:DnsAuth",
            '        args: ["53"]',
        ]
    for i in range(n_res):
        out += [
            f"  resolver{i}:",
            f"    network_node_id: {int(rng.integers(0, g))}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.dns:DnsResolver",
            '        args: ["53", dnsroot, dnsauth]',
            '        environment: {DNS_TTL_SEC: "20"}',
        ]
    for i in range(n_edges):
        out += [
            f"  edge{i}:",
            f"    network_node_id: {int(rng.integers(0, g))}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.web:WebEdge",
            f'        args: ["80", origin{i % 2}, "80", "70"]',
        ]
    # client templates: one per graph node, resolvers round-robin by
    # group; page loop = 4 pages x (1 main + 3 subresources)
    per = n_clients // g
    made = 0
    for gi in range(g):
        cnt = per if gi < g - 1 else n_clients - per * (g - 1)
        start_ms = 500 + 250 * gi
        out += [
            f"  c{gi}_:",
            f"    network_node_id: {gi}",
            f"    quantity: {cnt}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.web:WebClient",
            f'        args: ["4", "3", "150 kB", "40 kB", "80", '
            f'resolver{gi % n_res}, {", ".join(edges)}]',
            f"        start_time: {start_ms} ms",
            '        environment: {WEB_RETRIES: "2", WEB_THINK_SEC: "2"}',
        ]
        made += cnt
    assert made == n_clients
    out += [
        "faults:",
        "  events:",
        "    # partition half the client nodes away from the edge/origin",
        "    # side mid-run, healed 4 s later: page fetches stall on RTO",
        "    # backoff, DNS retries kick in, then everything recovers",
        "    - {time: 8s, kind: link_down, src_nodes: [0, 1, 2, 3, 4, 5],",
        "       dst_nodes: [6, 7, 8, 9, 10, 11], duration: 4s}",
        "    # lossy degrade window across the origin path: SACK block",
        "    # recovery carries the request/response traffic through it",
        "    - {time: 16s, kind: link_degrade, src_nodes: [0, 1],",
        "       dst_nodes: [2, 3, 4, 5], latency_factor: 1.5,",
        "       loss_add: 0.03, bandwidth_scale: 0.7, duration: 8s}",
    ]
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"wrote {path}: 2 origins, {n_edges} edges, {n_res} resolvers, "
          f"{n_clients} clients on {g} nodes + fault timeline")


def gen_web_cdn_100k(path="examples/web_cdn_100k.yaml"):
    """The device-transport benchmark config (PR 11; ROADMAP item 2):
    100k page-loop clients against a 48-edge/4-origin CDN tier — the
    regime where per-endpoint ticks (ack application + congestion-
    control arithmetic at the edges, one scalar callback per ack)
    dominate the round loop, so the columnar transport's one-kernel-
    per-round cohort advance is measurable on a committed config.
    Template-streamed like tor_1m: O(templates) YAML, not O(hosts).
    Short stop_time by design — 100k concurrent fetch loops generate
    millions of units in a few sim seconds, and the bench row runs
    device-transport on/off interleaved median-of-3 on it."""
    rng = np.random.default_rng(20260815)
    g = 24
    gml = random_gml(rng, g, min_lat_ms=5, max_lat_ms=50, max_loss=0.0,
                     bw_choices=("50 Mbit", "100 Mbit"))
    n_origins, n_edges, n_res, n_clients = 4, 48, 16, 100_000
    out = [
        "# Device-transport benchmark (PR 11; bench.py web_cdn_100k row,",
        "# tools/ci.sh devtransport smoke): 100k page-loop clients behind",
        "# a 48-edge CDN tier — endpoint ticks dominate, the columnar",
        "# transport's target regime. Template-streamed (quantity +",
        "# network_node_ids), so 100k hosts stay a few KB of YAML.",
        "# Generated by tools/gen_benchmarks.py (seeded) - do not hand-edit.",
        "general:",
        "  stop_time: 6s",
        "  seed: 11",
        "  heartbeat_interval: 2s",
        "network:",
        "  graph:",
        "    type: gml",
        "    inline: |",
        indent(gml, "      "),
        "hosts:",
    ]
    edges = [f"edge{i}" for i in range(n_edges)]
    for i in range(n_origins):
        out += [
            f"  origin{i}:",
            f"    network_node_id: {i}",
            '    bandwidth_up: "10 Gbit"',
            '    bandwidth_down: "1 Gbit"',
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.web:WebOrigin",
            '        args: ["80"]',
        ]
    for name, node in (("dnsroot", 0), ("dnsauth", 1)):
        out += [
            f"  {name}:",
            f"    network_node_id: {node}",
            '    bandwidth_up: "1 Gbit"',
            '    bandwidth_down: "1 Gbit"',
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.dns:DnsAuth",
            '        args: ["53"]',
        ]
    for i in range(n_res):
        out += [
            f"  resolver{i}:",
            f"    network_node_id: {int(rng.integers(0, g))}",
            '    bandwidth_up: "1 Gbit"',
            '    bandwidth_down: "1 Gbit"',
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.dns:DnsResolver",
            '        args: ["53", dnsroot, dnsauth]',
            '        environment: {DNS_TTL_SEC: "30"}',
        ]
    for i in range(n_edges):
        # CDN-edge uplinks: ~2k clients per edge must be served by the
        # edge NIC, not starved by a node-default 100 Mbit
        out += [
            f"  edge{i}:",
            f"    network_node_id: {int(rng.integers(0, g))}",
            '    bandwidth_up: "20 Gbit"',
            '    bandwidth_down: "2 Gbit"',
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.web:WebEdge",
            f'        args: ["80", origin{i % n_origins}, "80", "85"]',
        ]
    # client population: one template per graph node with a node-id
    # cycle, ~n_clients/g copies each; edge choice is seeded per-host
    # RNG over the full edge list, so one template yields diverse load
    per = n_clients // g
    made = 0
    for gi in range(g):
        cnt = per if gi < g - 1 else n_clients - per * (g - 1)
        start_ms = 200 + 50 * gi
        out += [
            f"  c{gi}_:",
            f"    network_node_id: {gi}",
            f"    quantity: {cnt}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.web:WebClient",
            f'        args: ["2", "2", "100 kB", "30 kB", "80", '
            f'resolver{gi % n_res}, {", ".join(edges)}]',
            f"        start_time: {start_ms} ms",
            '        environment: {WEB_RETRIES: "2", WEB_THINK_SEC: "2"}',
        ]
        made += cnt
    assert made == n_clients
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"wrote {path}: {n_origins} origins, {n_edges} edges, "
          f"{n_res} resolvers, {n_clients} clients on {g} nodes")


def gen_abr_1k(path="examples/abr_1k.yaml"):
    """ABR benchmark: 1000 hosts — 10 segment servers, 990 video
    sessions walking a 4-rung bitrate ladder over degraded links (two
    staggered lossy windows force downshifts and rebuffering stalls;
    the telemetry summary prices them as mean selected rate + stall
    records)."""
    rng = np.random.default_rng(20260811)
    g = 16
    gml = random_gml(rng, g, min_lat_ms=10, max_lat_ms=70, max_loss=0.002,
                     bw_choices=("25 Mbit", "50 Mbit", "100 Mbit"))
    n = 1000
    n_servers = 10
    out = [
        "# ABR benchmark (ROADMAP item 4; tools/ci.sh abr smoke gate):",
        "# ~1k video sessions rate-switching against the fluid NIC",
        "# buckets, with lossy degrade windows forcing downshifts and",
        "# rebuffering stalls.",
        "# Generated by tools/gen_benchmarks.py (seeded) - do not hand-edit.",
        "general:",
        "  stop_time: 45s",
        "  seed: 10",
        "  heartbeat_interval: 20s",
        "network:",
        "  graph:",
        "    type: gml",
        "    inline: |",
        indent(gml, "      "),
        "telemetry:",
        "  sample_every: 10s",
        "hosts:",
    ]
    for i in range(n_servers):
        out += [
            f"  video{i}:",
            f"    network_node_id: {int(rng.integers(0, g))}",
            # CDN-edge-class uplink: the ladder walk should be driven by
            # path loss and the degrade windows, not by ~99 sessions
            # starving on a node-default NIC
            '    bandwidth_up: "2 Gbit"',
            '    bandwidth_down: "200 Mbit"',
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.abr:AbrServer",
            '        args: ["80"]',
        ]
    rest = n - n_servers
    per = rest // g
    made = 0
    ladder = '"400000", "1000000", "2500000", "5000000"'
    for gi in range(g):
        cnt = per if gi < g - 1 else rest - per * (g - 1)
        start_ms = 500 + 300 * gi
        out += [
            f"  v{gi}_:",
            f"    network_node_id: {gi}",
            f"    quantity: {cnt}",
            "    processes:",
            "      - path: pyapp:shadow_tpu.models.abr:AbrClient",
            f'        args: [video{gi % n_servers}, "80", "12", "2000", '
            f"{ladder}]",
            f"        start_time: {start_ms} ms",
            '        environment: {ABR_RETRIES: "2"}',
        ]
        made += cnt
    assert made == rest
    out += [
        "faults:",
        "  events:",
        "    # two staggered lossy windows across the server side: the",
        "    # sessions inside them downshift and stall; SACK recovery",
        "    # keeps segment tails from RTO-stalling the whole player",
        "    - {time: 10s, kind: link_degrade, src_nodes: [0, 1, 2, 3],",
        "       dst_nodes: [8, 9, 10, 11], latency_factor: 2.0,",
        "       loss_add: 0.04, bandwidth_scale: 0.5, duration: 8s}",
        "    - {time: 24s, kind: link_degrade, src_nodes: [4, 5, 6, 7],",
        "       dst_nodes: [12, 13, 14, 15], latency_factor: 1.5,",
        "       loss_add: 0.02, bandwidth_scale: 0.6, duration: 8s}",
    ]
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"wrote {path}: {n_servers} servers + {rest} sessions on "
          f"{g} nodes, degrade timeline")


if __name__ == "__main__":
    gen_tgen_1k()
    gen_gossip_10k()
    gen_gossip_churn()
    gen_tor_400()
    gen_web_cdn()
    gen_web_cdn_100k()
    gen_abr_1k()
