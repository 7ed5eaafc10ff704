"""The plain reference for the Bitcoin cells, and the comparison that
decides ``correct``.

The network, the round loop and the stream transport are
``reference_tor``'s (``Sim``, ``Host``, ``Ep``), taken by import: this
file adds the Bitcoin nodes, written from Bitcoin Core's documented
defaults (``src/net.h``, ``src/net_processing.cpp``) and BIP 152 as the
configuration module ``configs/bitcoin_10k.py`` states them, and imports
nothing of the program.

A node (``BitcoinNode`` in the config): at its spawn it draws from its
host's Philox stream its 10 outbound peers (distinct, uniform over the
other nodes; the first 8 full-relay, the last 2 block-relay-only), its
start delay in [0, 0.5 s) and the 64-bit seed of its trickle draws, then
listens on its port (at most 125 - 10 inbound connections) and at the
delay connects to its peers in order. Its other timers are the
transactions it makes and, for the miner, the block.

Messages are counted stream bytes at their wire size (a 24-byte header
plus the payload); what a message says rides a queue per link, appended
when the message is queued and popped when its last byte is delivered.
Every message a handler queues to a peer leaves as one write when the
handler ends, to the peers in the order it first queued to them; what the
send buffer refuses goes out on drain.

- Handshake: the connecting side sends ``version`` when connected (relay
  off on a block-relay-only link). On ``version`` an inbound peer answers
  ``version``, and either side queues ``wtxidrelay`` and ``verack``. On
  ``verack`` the peer is ready: ``sendheaders`` and ``sendcmpct``, the
  latter with announce set to the first 3 outbound peers to get there.
- Transactions are bits of a 64-bit mask (txid i is bit i). A node that
  accepts one queues it for every ready full-relay peer not known to have
  it; the queue turning non-empty arms the peer's timer: its own for an
  outbound peer (mean 2 s), the node's one for inbound peers (mean 5 s).
  A delay is ``int(-log(u) * mean)``, u = ((x >> 11) + 1) / 2**53 for the
  next output x of a splitmix64 stream. A timer sends each of its peers
  an ``inv`` of its queue less what left the mempool and what the peer is
  known to have. New entries of an ``inv`` go back in one ``getdata``;
  ``getdata`` is answered with one ``tx`` per entry.
- The block: the experiment's transactions made before its time, found
  by the miner at that time. To a ready peer not known
  to have it: ``cmpctblock`` if the peer asked for high-bandwidth relay,
  else ``headers``, answered by ``getdata(CMPCT_BLOCK)`` unless the node
  has the block or has asked for it. A ``cmpctblock`` whose transactions
  are all known completes the block; otherwise one ``getblocktxn`` for
  the missing ones, answered by ``blocktxn`` (a second ``cmpctblock``
  meanwhile is ignored). A completed block leaves the mempool and is
  relayed at once.

The result is what the program reports: events, units sent and lost and
their bytes, the counters, process errors; ``node_digest`` (each node's
known transactions, the block's arrival in ns or -1, and its
``getblocktxn`` count) and ``msg_digest`` (each node's sent count per
message type). Every key is compared exactly.
``simulate(doc, rounds=13)`` is the control.
"""

from __future__ import annotations

import hashlib
import math
from collections import deque

import numpy as np

from reference_tor import IDENTITY_KEYS, MS, NS, Ep, Sim

APP = "pyapp:shadow_tpu.models.bitcoin:BitcoinNode"
KEYS = IDENTITY_KEYS + ("node_digest", "msg_digest")

OUTBOUND, FULL_RELAY, MAX_CONNECTIONS = 10, 8, 125
TRICKLE_OUT, TRICKLE_IN = 2 * NS, 5 * NS
START_SPREAD = 500 * MS
HB_PEERS = 3
M64 = (1 << 64) - 1
(VERSION, VERACK, WTXIDRELAY, SENDHEADERS, SENDCMPCT, INV, GETDATA, TX,
 HEADERS, CMPCTBLOCK, GETBLOCKTXN, BLOCKTXN) = range(12)
N_MSG = 12
HDR = 24
VERSION_LEN, SENDCMPCT_LEN, INV_ENTRY, TX_LEN = 102, 9, 36, 400
HEADERS_LEN, GETDATA_BLOCK_LEN = 82, 37
BLOCK_TXS = 3000
CMPCT_LEN = 88 + 6 * BLOCK_TXS + 250
GBT_INDEX = 3


def varint(n: int) -> int:
    return 1 if n < 253 else 3 if n <= 0xFFFF else 5 if n <= 0xFFFFFFFF \
        else 9


def popcount(m: int) -> int:
    return bin(m).count("1")


class Peer:
    __slots__ = ("ep", "remote", "outbound", "full", "ready", "hb_to",
                 "has_block", "armed", "dirty", "known", "q", "cork", "pend",
                 "rx_got", "mq")

    def __init__(self, ep, outbound, full) -> None:
        self.ep, self.outbound, self.full = ep, outbound, full
        self.remote = None
        self.ready = self.hb_to = self.has_block = self.armed = False
        self.dirty = False
        self.known = self.q = 0
        self.cork = self.pend = self.rx_got = 0
        self.mq = deque()  # (type, flag, wire size, mask)


class Node:
    def __init__(self, host, args) -> None:
        self.host = host
        self.port, self.n = int(args[0]), int(args[1])
        self.txs = [tuple(int(v) for v in e.split(":"))
                    for e in args[2].split(",") if e]
        self.block_at = tuple(int(v) for v in args[3].split(":"))
        self.peers = []
        self.dirty = []
        self.ninbound = 0
        self.known = self.mempool = self.requested = 0
        self.block_mask = self.gbt_mask = 0
        self.have_block = False
        self.block_state = 0
        self.n_hb = 0
        self.inb_armed = False
        self.block_t = -1
        self.gbt_sent = 0
        self.sent = [0] * N_MSG
        self.rng = 0

    def start(self) -> None:
        h = self.host
        rng = h.rng
        outs = []
        while len(outs) < min(OUTBOUND, self.n - 1):
            p = int(rng.integers(0, self.n))
            if p != h.id and p not in outs:
                outs.append(p)
        self.outs = outs
        delay = int(rng.integers(0, START_SPREAD))
        self.rng = int(rng.integers(0, 2**64, dtype=np.uint64))
        h.sim.btc[h.id] = self
        h.listeners[self.port] = self.accept
        h.after(delay, self.boot)
        for txid, (t, origin) in enumerate(self.txs):
            if origin == h.id:
                h.after(t - h.now, lambda i=txid: self.originate(i))
        t, miner = self.block_at
        if miner == h.id:
            mask = sum(1 << i for i, (tt, _) in enumerate(self.txs) if tt < t)
            h.after(t - h.now, lambda: self.mine(mask))

    # -- draws
    def exp_delay(self, mean: int) -> int:
        self.rng = (self.rng + 0x9E3779B97F4A7C15) & M64
        z = self.rng
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & M64
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & M64
        z ^= z >> 31
        return int(-math.log(((z >> 11) + 1) * 2.0 ** -53) * mean)

    # -- connections
    def add_peer(self, ep, outbound, full) -> Peer:
        p = Peer(ep, outbound, full)
        self.peers.append(p)
        ep.on_connected = lambda: self.connected(p)
        ep.on_data = lambda n, _payload: self.feed(p, n)
        ep.on_drain = lambda: self.pump(p)
        return p

    def boot(self) -> None:
        h = self.host
        for i, rhost in enumerate(self.outs):
            ep = Ep(h, h.ephemeral, rhost, self.port)
            h.ephemeral += 1
            h.conns[(ep.lport, rhost, self.port)] = ep
            self.add_peer(ep, True, i < FULL_RELAY)
            ep.connect()

    def accept(self, ep) -> None:
        if self.ninbound >= MAX_CONNECTIONS - OUTBOUND:
            ep.close()
            return
        p = self.add_peer(ep, False, False)
        self.ninbound += 1
        a = self.host.sim.btc.get(ep.rhost)
        for q in a.peers if a is not None else ():
            if (q.outbound and q.ep.lport == ep.rport
                    and q.ep.rhost == self.host.id and q.remote is None):
                q.remote, p.remote = p, q
                break

    def connected(self, p) -> None:
        self.queue(p, VERSION, p.full, 0, VERSION_LEN)
        self.flush()

    # -- writes
    def queue(self, p, kind, flag, mask, length) -> None:
        p.mq.append((kind, flag, HDR + length, mask))
        p.cork += HDR + length
        if not p.dirty:
            p.dirty = True
            self.dirty.append(p)
        self.sent[kind] += 1

    def pump(self, p) -> None:
        if p.pend > 0:
            p.pend -= p.ep.send(p.pend)

    def flush(self) -> None:
        dirty, self.dirty = self.dirty, []
        for p in dirty:
            p.dirty = False
            p.pend += p.cork
            p.cork = 0
            self.pump(p)

    # -- transactions
    def relay_tx(self, bit) -> None:
        for i, p in enumerate(self.peers):
            if not p.ready or not p.full or p.known & bit:
                continue
            was = p.q != 0
            p.q |= bit
            if was:
                continue
            if p.outbound:
                if not p.armed:
                    p.armed = True
                    d = self.exp_delay(TRICKLE_OUT)
                    self.host.after(d, lambda i=i: self.fire(i))
            elif not self.inb_armed:
                self.inb_armed = True
                d = self.exp_delay(TRICKLE_IN)
                self.host.after(d, lambda: self.fire(-1))

    def send_inv(self, p) -> None:
        e = p.q & self.mempool & ~p.known
        p.q = 0
        p.armed = False
        if e:
            p.known |= e
            k = popcount(e)
            self.queue(p, INV, 0, e, varint(k) + INV_ENTRY * k)

    def fire(self, idx) -> None:
        if idx >= 0:
            self.send_inv(self.peers[idx])
        else:
            self.inb_armed = False
            for p in self.peers:
                if not p.outbound and p.q:
                    self.send_inv(p)
        self.flush()

    def originate(self, txid) -> None:
        bit = 1 << txid
        if not self.known & bit:
            self.known |= bit
            self.mempool |= bit
            self.relay_tx(bit)
            self.flush()

    # -- the block
    def mine(self, mask) -> None:
        if not self.have_block:
            self.block_mask = mask
            self.accept_block()
            self.flush()

    def accept_block(self) -> None:
        self.have_block = True
        self.block_t = self.host.now
        self.mempool &= ~self.block_mask
        self.known |= self.block_mask
        for p in self.peers:
            if not p.ready or p.has_block:
                continue
            p.has_block = True
            if p.hb_to:
                self.queue(p, CMPCTBLOCK, 0, self.block_mask, CMPCT_LEN)
            else:
                self.queue(p, HEADERS, 0, 0, HEADERS_LEN)

    # -- reads
    def feed(self, p, n) -> None:
        r = p.remote
        while n > 0:
            if r is None or not r.mq:
                raise ValueError("bitcoin: stream bytes with no message")
            need = r.mq[0][2] - p.rx_got
            if n < need:
                p.rx_got += n
                break
            n -= need
            p.rx_got = 0
            self.handle(p, *r.mq.popleft())
        self.flush()

    def handle(self, p, kind, flag, _size, mask) -> None:
        if kind == VERSION:
            if not p.outbound:
                p.full = bool(flag)
                self.queue(p, VERSION, 1, 0, VERSION_LEN)
            self.queue(p, WTXIDRELAY, 0, 0, 0)
            self.queue(p, VERACK, 0, 0, 0)
        elif kind == VERACK:
            p.ready = True
            hb = p.outbound and self.n_hb < HB_PEERS
            self.n_hb += hb
            self.queue(p, SENDHEADERS, 0, 0, 0)
            self.queue(p, SENDCMPCT, int(hb), 0, SENDCMPCT_LEN)
        elif kind == SENDCMPCT:
            if flag:
                p.hb_to = True
        elif kind == INV:
            p.known |= mask
            fresh = mask & ~self.known & ~self.requested
            if fresh:
                self.requested |= fresh
                k = popcount(fresh)
                self.queue(p, GETDATA, 0, fresh, varint(k) + INV_ENTRY * k)
        elif kind == GETDATA:
            if flag:
                p.has_block = True
                if self.have_block:
                    self.queue(p, CMPCTBLOCK, 0, self.block_mask, CMPCT_LEN)
                return
            p.known |= mask
            while mask:
                bit = mask & -mask
                mask ^= bit
                self.queue(p, TX, 0, bit, TX_LEN)
        elif kind == TX:
            p.known |= mask
            self.requested &= ~mask
            if not self.known & mask:
                self.known |= mask
                self.mempool |= mask
                self.relay_tx(mask)
        elif kind == HEADERS:
            p.has_block = True
            if not self.have_block and not self.block_state:
                self.block_state = 1
                self.queue(p, GETDATA, 1, 0, GETDATA_BLOCK_LEN)
        elif kind == CMPCTBLOCK:
            p.has_block = True
            if self.have_block or self.block_state == 2:
                return
            self.block_mask = mask
            missing = mask & ~self.known
            if not missing:
                self.accept_block()
                return
            self.block_state = 2
            self.gbt_sent += 1
            self.gbt_mask = missing
            k = popcount(missing)
            self.queue(p, GETBLOCKTXN, 0, missing,
                       32 + varint(k) + GBT_INDEX * k)
        elif kind == GETBLOCKTXN:
            k = popcount(mask)
            self.queue(p, BLOCKTXN, 0, mask, 32 + varint(k) + TX_LEN * k)
        elif kind == BLOCKTXN:
            p.has_block = True
            if not self.have_block:
                self.known |= mask
                self.accept_block()


class BtcSim(Sim):
    """``reference_tor``'s network with Bitcoin nodes on its hosts."""

    def __init__(self, doc: dict, rounds: int) -> None:
        self.btc = {}  # host id -> Node
        super().__init__(doc, rounds)

    def spawn(self, h) -> None:
        proc = h.proc
        if proc["path"] != APP:
            raise ValueError(f"the reference models Bitcoin nodes only, not "
                             f"{h.name}: {proc['path']}")
        h.app = Node(h, [str(a) for a in proc["args"]])
        h.count("processes_spawned", 1)
        h.app.start()


def simulate(doc: dict, rounds: int = 20) -> dict:
    """Run the document; the result the comparison covers. ``rounds`` is
    for the control only."""
    sim = BtcSim(doc, rounds)
    sim.run()
    counters: dict = {}
    nodes, msgs = [], []
    for h in sim.hosts:
        for k, v in h.counters.items():
            counters[k] = counters.get(k, 0) + v
        a = h.app
        nodes.append((a.known, a.block_t, a.gbt_sent))
        msgs.append(tuple(a.sent))
    counters["events"] = sim.c["events"]
    arrivals = sorted(t for _, t, _ in nodes if t >= 0)
    apps = [h.app for h in sim.hosts]
    return {"events": sim.c["events"], "units_sent": sim.c["sent"],
            "units_dropped": sim.c["dropped"], "bytes_sent": sim.c["bytes"],
            "counters": dict(sorted((k, v) for k, v in counters.items()
                                    if v)),
            "process_errors": [],
            "node_digest": node_digest(nodes),
            "msg_digest": msg_digest(msgs),
            "nodes": len(nodes), "txs": len(apps[0].txs) if apps else 0,
            "block_txs": popcount(next(
                (a.block_mask for a in apps if a.have_block), 0)),
            "block_reached": len(arrivals),
            "block_ms": (arrivals[len(arrivals) // 2] // MS,
                         arrivals[-1] // MS) if arrivals else None,
            "getblocktxn": sum(g for _, _, g in nodes),
            "txs_known": sum(popcount(k) for k, _, _ in nodes),
            "sent": [sum(m[i] for m in msgs) for i in range(N_MSG)]}


def node_digest(nodes: list) -> str:
    """One sha256 over every node's (known mask, block ns or -1,
    getblocktxn sent), in host order."""
    h = hashlib.sha256()
    for known, block_ns, gbt in nodes:
        h.update(f"{known},{block_ns},{gbt}\n".encode())
    return h.hexdigest()


def msg_digest(msgs: list) -> str:
    """One sha256 over every node's sent count per message type, in host
    order."""
    h = hashlib.sha256()
    for sent in msgs:
        h.update((",".join(str(v) for v in sent) + "\n").encode())
    return h.hexdigest()


def mismatches(results: list, ref: dict) -> dict:
    """Per compared key, how many of ``results`` differ from ``ref``."""
    return {k: sum(r[k] != ref[k] for r in results) for k in KEYS}


def describe(ref: dict) -> str:
    """The reference's part of the harness's ``reference:`` line."""
    return (f"events {ref['events']}, units_sent {ref['units_sent']}, "
            f"dropped {ref['units_dropped']}, {ref['nodes']} nodes, "
            f"{ref['txs']} txs, {ref['txs_known']} known in all, block of "
            f"{ref['block_txs']} of them reached {ref['block_reached']} "
            f"nodes (p50/last ms {ref['block_ms']}), getblocktxn "
            f"{ref['getblocktxn']}, sent per type {ref['sent']}")
