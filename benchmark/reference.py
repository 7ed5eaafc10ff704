"""The plain reference for the gossip cells, and the comparison that
decides ``correct``.

A discrete-event simulation of the configuration document, written from
the documented semantics alone; it imports nothing of the program and
takes nothing it made. It reads the document itself:

- Hosts are the ``hosts:`` templates in document order, each ``quantity``
  copies in turn, numbered from 0; each sits on its graph node.
- The GML graph gives node bandwidths and edge latencies and losses. A
  message between two graph nodes takes the lowest-latency path (all-pairs
  min-plus squaring, ties to the lowest intermediate node, the path's
  reliability the float32 product of ``1 - packet_loss`` along that
  decomposition); between two hosts of one node, the node's self-edge.
  The float32 rule decides the loss threshold to the last bit, so the
  reference follows it, and checks its latencies against Dijkstra.
- A datagram of ``n`` bytes is one message of ``n + 40`` wire bytes, in
  one 1500-byte packet (every gossip message fits one). It is lost iff it
  draws below ``rint((1 - reliability) * 2**24)``: the top 24 bits of the
  first word of Threefry-2x32-20 (Salmon et al., SC'11), keyed by
  ``general.seed``, counter (message id low word, high word); a message id
  is ``host << 32 | the host's count of messages sent``.
- Each host has a token bucket each way (its node's bandwidth; depth one
  round, the least link latency, of bytes, at least 16 KiB): a message
  leaves once the
  sender's bucket holds its bytes, and is delivered once the receiver's
  does.
- Per-host random numbers: numpy's Philox keyed ``(seed << 16) ^ host``.
- Events at one host run in time order; at one instant, arrivals before
  timers, arrivals by message id, timers in the order they were set. Only
  events before ``stop_time`` run.
- ``GossipNode [port, n, k, originate, interval]``: at start it draws
  peers ``rng.integers(0, n)`` until it has ``min(k, n - 1)`` others, and
  an originator waits ``(0.25 + 0.5 * rng.random()) * interval``. A new
  transaction (id ``host:count``) is sent as INV (64 B) to every peer. An
  INV for an unknown transaction is answered with GETDATA (64 B); every
  GETDATA with TX (400 B); a TX of an unknown transaction makes it known,
  counts as received, and is announced to every peer but its sender.

The result of a run is what the program reports of the same experiment:
its events, messages sent, lost and their bytes, the per-host counters,
process errors, and each node's (received, known) pair.

A configuration names its reference in ``REFERENCE``; the harness reads
``IDENTITY_KEYS``, ``KEYS``, ``simulate``, ``mismatches`` and
``describe`` from it. ``simulate(doc, rounds=13)`` is the control
(``benchmark/control.py``).
"""

from __future__ import annotations

import hashlib
import heapq
import re

import numpy as np

HEADER = 40
MTU = 1500
MIN_CAP = 16384
NS = 1_000_000_000
INF = 1 << 61
INV, GETDATA, TX = 0, 1, 2
MSG_BYTES = {INV: 64, GETDATA: 64, TX: 400}
GOSSIP = "pyapp:shadow_tpu.models.gossip:GossipNode"

#: the summary keys compared as they are, then the per-node results
IDENTITY_KEYS = ("events", "units_sent", "units_dropped", "bytes_sent",
                 "counters", "process_errors")
KEYS = IDENTITY_KEYS + ("nodes_digest",)

# -- Threefry-2x32 -----------------------------------------------------------

_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = 0x1BD11BDA


def threefry2x32(k0: int, k1: int, c0, c1, rounds: int = 20):
    """Threefry-2x32 with ``rounds`` rounds (20 is the published one; the
    key is injected after every fourth). numpy uint32 arrays in and out."""
    u32 = np.uint32
    ks = (u32(k0), u32(k1), u32(k0 ^ k1 ^ _PARITY))
    x0 = np.asarray(c0, dtype=u32)
    x1 = np.asarray(c1, dtype=u32)
    with np.errstate(over="ignore"):
        x0 = x0 + ks[0]
        x1 = x1 + ks[1]
        for i in range(rounds):
            r = _ROT[i % 8]
            x0 = x0 + x1
            x1 = ((x1 << u32(r)) | (x1 >> u32(32 - r))) ^ x0
            if i % 4 == 3:
                j = i // 4 + 1
                x0 = x0 + ks[j % 3]
                x1 = x1 + ks[(j + 1) % 3] + u32(j)
    return x0, x1


class Draws:
    """The 24-bit loss draw of each message id (packet 0: every gossip
    message fits one packet), computed in blocks of message counts per
    host."""

    BLOCK = 256

    def __init__(self, seed: int, n_hosts: int, rounds: int) -> None:
        self.k0, self.k1 = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
        self.rounds = rounds
        ctr = np.tile(np.arange(self.BLOCK, dtype=np.uint32), n_hosts)
        hid = np.repeat(np.arange(n_hosts, dtype=np.uint32), self.BLOCK)
        x0, _ = threefry2x32(self.k0, self.k1, ctr, hid, rounds)
        flat = (x0 >> np.uint32(8)).tolist()
        b = self.BLOCK
        self.rows = [flat[i * b:(i + 1) * b] for i in range(n_hosts)]

    def lost(self, host: int, ctr: int, thresh: int) -> bool:
        row = self.rows[host]
        while ctr >= len(row):
            n = len(row)
            c = np.arange(n, n + self.BLOCK, dtype=np.uint32)
            x0, _ = threefry2x32(self.k0, self.k1, c,
                                 np.full(self.BLOCK, host, np.uint32),
                                 self.rounds)
            row.extend((x0 >> np.uint32(8)).tolist())
        return row[ctr] < thresh


# -- the document ------------------------------------------------------------

_TIME = {"ns": 1, "us": 1_000, "ms": 1_000_000, "s": NS, "": NS,
         "sec": NS, "min": 60 * NS, "h": 3600 * NS}
_BIT = {"": 1, "k": 10**3, "m": 10**6, "g": 10**9, "ki": 2**10,
        "mi": 2**20, "gi": 2**30}


def parse_time(v) -> int:
    if isinstance(v, int):
        return v * NS
    m = re.fullmatch(r"\s*([0-9.]+)\s*([a-z]*)\s*", str(v).lower())
    return int(round(float(m.group(1)) * _TIME[m.group(2)]))


def parse_bandwidth(v) -> int:
    """Bytes per second of ``"<n> <prefix>bit"``."""
    m = re.fullmatch(r"\s*([0-9.]+)\s*([a-z]*)bits?\s*", str(v).lower())
    return int(float(m.group(1)) * _BIT[m.group(2)] / 8)


def parse_gml(text: str) -> tuple[list, list]:
    """The ``node`` and ``edge`` records of a GML graph, as dicts."""
    toks = re.findall(r'"[^"]*"|\[|\]|[^\s\[\]"]+', text)
    nodes, edges, stack, cur, key = [], [], [], None, None
    for t in toks:
        if t == "[":
            stack.append((cur, key))
            cur = {}
            if key == "node":
                nodes.append(cur)
            elif key == "edge":
                edges.append(cur)
            key = None
        elif t == "]":
            cur, key = stack.pop()
            key = None
        elif key is None:
            key = t
        else:
            if cur is not None:
                cur[key] = t.strip('"')
            key = None
    return nodes, edges


def routes(nodes: list, edges: list) -> tuple[np.ndarray, np.ndarray]:
    """(latency ns, loss threshold) between every two graph nodes."""
    ids = {int(n["id"]): i for i, n in enumerate(nodes)}
    g = len(nodes)
    lat = np.full((g, g), INF, dtype=np.int64)
    rel = np.zeros((g, g), dtype=np.float32)
    self_lat = np.zeros(g, dtype=np.int64)
    self_rel = np.ones(g, dtype=np.float32)
    adj = [[] for _ in range(g)]
    for e in edges:
        a, b = ids[int(e["source"])], ids[int(e["target"])]
        ns = parse_time(e.get("latency", "1 ms"))
        loss = float(e.get("packet_loss", 0.0))
        if a == b:
            self_lat[a], self_rel[a] = ns, 1.0 - loss
            continue
        adj[a].append((b, ns))
        adj[b].append((a, ns))
        for s, t in ((a, b), (b, a)):
            if ns < lat[s, t]:
                lat[s, t], rel[s, t] = ns, 1.0 - loss
    np.fill_diagonal(lat, 0)
    np.fill_diagonal(rel, 1.0)
    idx = np.arange(g)
    for _ in range(max(1, int(np.ceil(np.log2(max(g, 2)))))):
        cand = np.minimum(lat[:, :, None] + lat[None, :, :], INF)
        k = cand.argmin(axis=1)
        lat = cand[idx[:, None], k, idx[None, :]]
        rel = rel[idx[:, None], k] * rel[k, idx[None, :]]
    for s in range(g):  # the latencies, checked against Dijkstra
        dist, heap = {s: 0}, [(0, s)]
        while heap:
            d, u = heapq.heappop(heap)
            if d > dist[u]:
                continue
            for v, ns in adj[u]:
                if d + ns < dist.get(v, INF):
                    dist[v] = d + ns
                    heapq.heappush(heap, (d + ns, v))
        row = [dist.get(t, INF) for t in range(g)]
        if row != lat[s].tolist():
            raise AssertionError(f"min-plus latencies from node {s} are "
                                 f"not the shortest paths")
    for i in range(g):
        if self_lat[i] == 0:  # no self-edge: the node's shortest edge
            self_lat[i] = min(ns for _, ns in adj[i]) if adj[i] else 10**6
        lat[i, i], rel[i, i] = self_lat[i], self_rel[i]
    thresh = np.clip(np.rint((1.0 - rel.astype(np.float64)) * 2**24),
                     0, 2**24).astype(np.int64)
    return lat, thresh


def load(doc: dict) -> dict:
    """Hosts, processes and network of a gossip document."""
    nodes, edges = parse_gml(doc["network"]["graph"]["inline"])
    lat, thresh = routes(nodes, edges)
    ids = {int(n["id"]): i for i, n in enumerate(nodes)}
    bw = [(parse_bandwidth(n["host_bandwidth_up"]),
           parse_bandwidth(n["host_bandwidth_down"])) for n in nodes]
    hosts = []
    for name, h in doc["hosts"].items():
        for _ in range(int(h.get("quantity", 1))):
            (proc,) = h["processes"]
            if proc["path"] != GOSSIP or proc.get("environment"):
                raise ValueError(f"the reference models plain GossipNode "
                                 f"hosts only, not {name}: {proc}")
            args = [str(a) for a in proc["args"]]
            hosts.append({"node": ids[int(h["network_node_id"])],
                          "start": parse_time(proc.get("start_time", 0)),
                          "port": int(args[0]), "n": int(args[1]),
                          "k": int(args[2]), "originate": int(args[3]),
                          "interval": float(args[4])})
    positive = lat[(lat > 0) & (lat < INF)]
    round_ns = int(positive.min()) if positive.size else 10**6
    return {"seed": int(doc["general"]["seed"]),
            "stop": parse_time(doc["general"]["stop_time"]),
            "hosts": hosts, "lat": lat.tolist(), "thresh": thresh.tolist(),
            "bw": bw, "round_ns": round_ns}


# -- the simulation ----------------------------------------------------------

class Bucket:
    """A token bucket kept in byte-nanoseconds, so that refill is exact."""

    __slots__ = ("rate", "cap", "tok", "t")

    def __init__(self, rate: int, cap: int) -> None:
        self.rate, self.cap, self.tok, self.t = rate, cap * NS, cap * NS, 0

    def take(self, size: int, t: int) -> int:
        """When ``size`` bytes offered at ``t`` clear the bucket."""
        if t > self.t:
            self.tok = min(self.cap, self.tok + self.rate * (t - self.t))
            self.t = t
        need = size * NS - self.tok
        if need > 0:
            wait = -(-need // self.rate)
            self.t += wait
            self.tok += self.rate * wait
        self.tok -= size * NS
        return self.t


def simulate(doc: dict, rounds: int = 20) -> dict:
    """Run the document; the result the comparison covers. ``rounds`` is
    for the control only."""
    net = load(doc)
    hosts, lat, thresh = net["hosts"], net["lat"], net["thresh"]
    stop, seed = net["stop"], net["seed"]
    n = len(hosts)
    node = [h["node"] for h in hosts]
    draws = Draws(seed, n, rounds)
    egress, ingress = [], []
    for h in hosts:
        up, down = net["bw"][h["node"]]
        egress.append(Bucket(up, max(up * net["round_ns"] // NS, MIN_CAP)))
        ingress.append(Bucket(down,
                              max(down * net["round_ns"] // NS, MIN_CAP)))
    peers = [None] * n
    seen = [set() for _ in range(n)]
    received = [0] * n
    originated = [0] * n
    ctr = [0] * n
    c = {"events": 0, "sent": 0, "dropped": 0, "bytes": 0, "emitted": 0,
         "delivered": 0, "spawned": 0, "waits": 0}
    heap, order = [], 0
    for i, h in enumerate(hosts):  # process start: band 1
        heap.append((h["start"], i, 1, order, "start", None, None))
        order += 1
    heapq.heapify(heap)

    def send(src: int, dst: int, kind: int, txid: tuple, t: int) -> None:
        size = wire(kind, txid)
        if size > MTU:
            raise ValueError(f"a {size}-byte message spans packets")
        k = ctr[src]
        ctr[src] = k + 1
        c["emitted"] += 1
        dep = egress[src].take(size, t)
        c["waits"] += dep > t
        th = thresh[node[src]][node[dst]]
        if th and draws.lost(src, k, th):
            c["dropped"] += 1
            return
        c["sent"] += 1
        c["bytes"] += size
        heapq.heappush(heap, (dep + lat[node[src]][node[dst]], dst, 0,
                              (src << 32) | k, kind, txid, src))

    def announce(me: int, txid: tuple, t: int, exclude: int = -1) -> None:
        for p in peers[me]:
            if p != exclude:
                send(me, p, INV, txid, t)

    while heap and heap[0][0] < stop:
        t, me, band, _key, what, txid, src = heapq.heappop(heap)
        c["events"] += 1
        if band == 0:  # an arrival
            at = ingress[me].take(wire(what, txid), t)
            c["waits"] += at > t
            c["delivered"] += 1
            if what == INV:
                if txid not in seen[me]:
                    send(me, src, GETDATA, txid, t)
            elif what == GETDATA:
                send(me, src, TX, txid, t)
            elif txid not in seen[me]:
                seen[me].add(txid)
                received[me] += 1
                announce(me, txid, t, exclude=src)
        elif what == "start":
            c["spawned"] += 1
            h = hosts[me]
            rng = np.random.Generator(np.random.Philox(
                key=((seed << 16) ^ me) & 0xFFFFFFFFFFFFFFFF))
            mine: set = set()
            while len(mine) < min(h["k"], h["n"] - 1):
                p = int(rng.integers(0, h["n"]))
                if p != me:
                    mine.add(p)
            peers[me] = sorted(mine)
            if h["originate"] > 0:
                wait = int((0.25 + 0.5 * float(rng.random()))
                           * h["interval"] * NS)
                heapq.heappush(heap, (t + wait, me, 1, order, "tx", None,
                                      None))
                order += 1
        else:  # an originator's timer
            originated[me] += 1
            txid = (me, originated[me])
            seen[me].add(txid)
            announce(me, txid, t)
            if originated[me] < hosts[me]["originate"]:
                heapq.heappush(heap, (t + int(hosts[me]["interval"] * NS),
                                      me, 1, order, "tx", None, None))
                order += 1
    known = [len(s) for s in seen]
    reach: dict = {}
    for s in seen:
        for txid in s:
            reach[txid] = reach.get(txid, 0) + 1
    return {"events": c["events"], "units_sent": c["sent"],
            "units_dropped": c["dropped"], "bytes_sent": c["bytes"],
            "counters": {"dgrams_received": c["delivered"],
                         "dgrams_sent": c["emitted"],
                         "events": c["events"],
                         "processes_spawned": c["spawned"],
                         "units_delivered": c["delivered"],
                         "units_emitted": c["emitted"]},
            "process_errors": [],
            "nodes_digest": nodes_digest(received, known),
            "bucket_waits": c["waits"],
            "tx_reach": sorted(reach.values())}


def wire(kind: int, txid: tuple) -> int:
    """Wire bytes of a message: its size, or its payload (tag and
    transaction id) where that is longer, plus the header."""
    return max(MSG_BYTES[kind], 1 + len(f"{txid[0]}:{txid[1]}")) + HEADER


def nodes_digest(received: list, known: list) -> str:
    """One sha256 over every node's (received, known), in host order."""
    h = hashlib.sha256()
    for r, k in zip(received, known):
        h.update(f"{r},{k}\n".encode())
    return h.hexdigest()


def mismatches(results: list, ref: dict) -> dict:
    """Per compared key, how many of ``results`` differ from ``ref``."""
    return {k: sum(r[k] != ref[k] for r in results) for k in KEYS}


def describe(ref: dict) -> str:
    """The reference's part of the harness's ``reference:`` line."""
    return (f"events {ref['events']}, units_sent {ref['units_sent']}, "
            f"dropped {ref['units_dropped']}, bucket waits "
            f"{ref['bucket_waits']}, nodes reached per tx {ref['tx_reach']}")
