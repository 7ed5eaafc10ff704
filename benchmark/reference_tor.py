"""The plain reference for the Tor cells, and the comparison that decides
``correct``.

A discrete-event simulation of the configuration document, written from
the documented semantics of the transport (``network/transport.py``'s
docstring and constants), of Tor's framing and forwarding
(``models/tor.py``'s docstring) and of the network (``network/fluid.py``,
``network/engine.py``); it imports nothing of the program and takes
nothing it made. It may take the gossip reference's helpers: GML parsing,
min-plus routes, Threefry-2x32 and the parsers of times and bandwidths.

The network, per round (the conservative round loop):

- Rounds are ``W`` wide, ``W`` the smallest positive latency of the route
  table (self-edges included). A round [s, e) ends at ``min(s + W,
  stop)``; the next starts at e, or, when the round ran no event and no
  arrival waits for ingress tokens, at the next pending event (ending the
  run when there is none). Events at a host run in (time, band, key)
  order: arrivals (band 0, key = the unit id) before timers (band 1, key
  = the host's count of timers set). Only events before ``stop`` run.
- A host's units leave in emission order. At the round's barrier each
  gets an id ``host << 32 | the host's count of units``; a departure time
  from the host's up bucket, a closed form evaluated at the round's start
  (tokens accrue at the rate, floored once over each interval since the
  bucket's base; a bucket found above its capacity there rebases to full
  at the round's start; a unit leaves at ``max(emitted, the instant the
  cumulative bytes fit)``); an arrival time ``max(departure + latency,
  round end)``; and a loss draw per 1500-byte packet (Threefry-2x32-20 of
  counter (id low word, id high word | packet << 26), keyed by
  ``general.seed``; the unit is lost iff a packet's top 24 bits fall below
  ``rint((1 - reliability) * 2**24)``). Up capacity: ``max(rate * W,
  16 KiB)`` bytes.
- Down buckets refill at each round's start by ``min(rate * dt, cap)``,
  clamped at their capacity. An arrival takes its bytes when it runs, or
  waits; the waiting arrivals of each host (in host order, then in the
  order they came) are retried at the start of each later round, and
  delivered at that round's start if they fit. Acknowledgements owed
  from a round are sent at its barrier, one per connection (in the order
  the connections first owed one), at the host's clock.

The stream transport (every stream ends in a ``HEADER`` of 40 bytes):
SYN / SYNACK carrying the advertised window, segmentation of each write
into units of at most ``unit_chunk`` bytes (never merging two writes),
the silly-window rule, NewReno in integer bytes, duplicate acks carrying
up to 4 SACK blocks, recovery on the third, the RTO at twice the path's
round trip (200 ms to 60 s, backoff to 64x), send and receive buffers
with ``on_drain``, FIN after all data is acked, half-close and TIME_WAIT.

Tor (``models/tor.py``): control cells of a 12-byte header and a payload;
DATA headers followed by counted bytes; relays that answer CREATE,
telescope EXTEND at the circuit's head, forward everything else along the
circuit table and queue writes behind full send buffers; exits that
answer BEGIN by fetching from the TGen server and reframing what comes
back; teardown that closes each spliced connection once its writes are
out. Clients draw their start delay, then per fetch its size and its
path (exit, guard, middle by weight among the relays not yet chosen),
and after a fetch the think time, from the host's Philox stream keyed
``(seed << 16) ^ host``. A fetch still open at the stop is recorded as it
stands: the DATA bytes counted so far, and done -1. A client is held to
its process's ``expected_final_state`` (none, or ``{"exited": 0}``).

The result is what the program reports of the same experiment: events,
units sent, lost and their bytes, the counters, process errors, and
digests of every client's per-fetch records and every relay's (cells,
bytes). Every key is compared exactly; none is left out.
``simulate(doc, rounds=13)`` is the control (``benchmark/control.py``).
"""

from __future__ import annotations

import hashlib
import heapq
import math
from bisect import bisect_right
from itertools import accumulate

import numpy as np

from reference import (parse_bandwidth, parse_gml, parse_time, routes,
                       threefry2x32)

NS = 1_000_000_000
MS = 1_000_000
HEADER, MTU, MIN_CAP, PKT_SHIFT, MAX_PKTS = 40, 1500, 16384, 26, 64
MSS = 1460
INIT_CWND, MIN_CWND = 10 * MSS, 2 * MSS
RTO_MIN, RTO_MAX = 200 * MS, 60_000 * MS
SYN_RETRIES = FIN_RETRIES = 5
DATA_RETRIES = 8
SACK_BLOCKS = 4
INF = 1 << 62
SYN, SYNACK, DATA, ACK, FIN, FINACK = range(6)
CLOSED, SYN_SENT, ESTABLISHED, CLOSING, FIN_SENT, TIME_WAIT = range(6)
CREATE, CREATED, EXTEND, EXTENDED, BEGIN, CONNECTED, CELL_DATA, END = \
    range(8)
CELL = 12
APP = "pyapp:shadow_tpu.models."

IDENTITY_KEYS = ("events", "units_sent", "units_dropped", "bytes_sent",
                 "counters", "process_errors")
KEYS = IDENTITY_KEYS + ("fetch_digest", "relay_digest")


def cell(ctype: int, circ: int, payload: bytes = b"") -> bytes:
    return (bytes([ctype]) + circ.to_bytes(2, "big")
            + len(payload).to_bytes(2, "big") + b"\0" * 7 + payload)


def data_header(circ: int, n: int) -> bytes:
    return (bytes([CELL_DATA]) + circ.to_bytes(2, "big")
            + n.to_bytes(2, "big") + b"\0" * 7)


def over(rate: int, dt: int) -> int:
    """Bytes a rate accrues over ``dt`` ns, floored once."""
    q, r = divmod(dt, NS)
    return rate * q + rate * r // NS


# -- the stream transport ----------------------------------------------------

class Ep:
    """One end of a stream connection."""

    __slots__ = (
        "host", "lport", "rhost", "rport", "state", "rto", "syn_tries",
        "fin_tries", "ctl", "peer_fin", "on_connected", "on_data",
        "on_drain", "on_close", "on_error",
        # sender
        "cwnd", "ssthresh", "sbuf_cap", "nxt", "una", "adv", "sendbuf",
        "buffered", "rtx", "rto_timer", "backoff", "retries", "dup",
        "sacked", "sack_high", "rtx_done", "in_rec", "recover",
        # receiver
        "rbuf_cap", "rcv_nxt", "ooo", "ooo_bytes", "last_wnd")

    def __init__(self, host, lport, rhost, rport) -> None:
        sim = host.sim
        self.host, self.lport, self.rhost, self.rport = \
            host, lport, rhost, rport
        self.state = CLOSED
        a, b = host.node, sim.hosts[rhost].node
        self.rto = min(max(2 * (sim.lat[a][b] + sim.lat[b][a]), RTO_MIN),
                       RTO_MAX)
        self.syn_tries = self.fin_tries = 0
        self.ctl = None
        self.peer_fin = False
        self.on_connected = self.on_data = self.on_drain = None
        self.on_close = self.on_error = None
        self.cwnd, self.ssthresh = INIT_CWND, 1 << 62
        self.sbuf_cap = sim.sbuf
        self.nxt = self.una = 0
        self.adv = INIT_CWND
        self.sendbuf = []  # [nbytes, payload | None], oldest first
        self.buffered = 0
        self.rtx = []  # (seq, n, payload), seq ascending
        self.rto_timer = None
        self.backoff = 1
        self.retries = self.dup = 0
        self.sacked, self.rtx_done = set(), set()
        self.sack_high = 0
        self.in_rec = False
        self.recover = 0
        self.rbuf_cap = sim.rbuf
        self.rcv_nxt = 0
        self.ooo = {}
        self.ooo_bytes = 0
        self.last_wnd = sim.rbuf

    # -- emission
    def emit(self, kind, nbytes=0, payload=None, seq=0, acked=0, wnd=0):
        h = self.host
        if not h.egress:
            h.sim.emitting.append(h)
        if kind == DATA:
            h.egress.append((DATA, self.rhost, nbytes + HEADER, h.now,
                             self.lport, self.rport, nbytes, seq, payload))
        else:
            h.egress.append((kind, self.rhost, HEADER, h.now, self.lport,
                             self.rport, acked, wnd, payload))

    def window(self) -> int:
        return max(self.rbuf_cap - self.ooo_bytes, 0)

    # -- application side
    def send(self, nbytes=0, payload=None) -> int:
        if payload is not None:
            nbytes = len(payload)
        if nbytes <= 0 or self.state in (CLOSING, FIN_SENT, TIME_WAIT):
            return 0
        accept = max(min(nbytes, self.sbuf_cap - self.buffered), 0)
        if accept:
            self.sendbuf.append([accept, payload[:accept]
                                 if payload is not None else None])
            self.buffered += accept
            self.pump()
        self.host.count("stream_bytes_queued", accept)
        return accept

    def close(self) -> None:
        if self.state in (CLOSED, CLOSING, FIN_SENT, TIME_WAIT):
            return
        self.state = CLOSING
        self.pump()

    def connect(self) -> None:
        self.state = SYN_SENT
        self.send_syn()

    # -- sender
    def pump(self) -> None:
        if self.state not in (ESTABLISHED, CLOSING):
            return
        chunk = self.host.sim.chunk
        window = min(self.cwnd, max(self.adv, MSS))
        while self.buffered > 0 and self.nxt - self.una < window:
            inflight = self.nxt - self.una
            usable = window - inflight
            if usable < chunk and usable < self.buffered and inflight > 0:
                break
            budget = min(usable, chunk)
            head = self.sendbuf[0]
            n, p = head
            if n <= budget:
                self.sendbuf.pop(0)
            else:
                head[0] = n - budget
                if p is not None:
                    head[1] = p[budget:]
                    p = p[:budget]
                n = budget
            self.buffered -= n
            seq = self.nxt
            self.nxt += n
            self.rtx.append((seq, n, p))
            self.emit(DATA, nbytes=n, payload=p, seq=seq)
        if self.nxt - self.una > 0:
            self.arm_rto()
        elif self.buffered == 0:
            self.sender_drained()

    def arm_rto(self, reset=False) -> None:
        h = self.host
        if reset and self.rto_timer is not None:
            h.cancel(self.rto_timer)
            self.rto_timer = None
        if self.rto_timer is None:
            self.rto_timer = h.after(self.rto * self.backoff, self.on_rto)

    def cancel_rto(self) -> None:
        if self.rto_timer is not None:
            self.host.cancel(self.rto_timer)
            self.rto_timer = None

    def on_rto(self) -> None:
        self.rto_timer = None
        if self.nxt - self.una == 0 or self.state in (CLOSED, TIME_WAIT):
            return
        if self.adv > 0:
            self.retries += 1
        if self.retries > DATA_RETRIES:
            self.reset("connection timed out (ETIMEDOUT): data "
                       "retransmission retries exhausted")
            return
        self.sacked.clear()
        self.rtx_done.clear()
        self.sack_high = 0
        self.in_rec = False
        self.ssthresh = max((self.nxt - self.una) // 2, MIN_CWND)
        self.cwnd = MIN_CWND
        self.backoff = min(self.backoff * 2, 64)
        seq, n, p = self.rtx[0]
        self.emit(DATA, nbytes=n, payload=p, seq=seq)
        self.arm_rto()

    def holes(self, force_head=False) -> int:
        """Resend every segment below the highest SACKed byte that is
        neither SACKed nor resent in this recovery (and the oldest one
        when ``force_head``)."""
        sent = 0
        for i, (seq, n, p) in enumerate(self.rtx):
            if seq >= self.sack_high and not (force_head and i == 0):
                break
            if seq in self.sacked or seq in self.rtx_done:
                continue
            self.rtx_done.add(seq)
            self.emit(DATA, nbytes=n, payload=p, seq=seq)
            sent += 1
        return sent

    def on_ack(self, cum, wnd, sack) -> None:
        prev = self.adv
        self.adv = wnd
        if sack is not None:
            for off in range(0, len(sack) - 15, 16):
                a = int.from_bytes(sack[off:off + 8], "big")
                b = int.from_bytes(sack[off + 8:off + 16], "big")
                if b > self.sack_high:
                    self.sack_high = b
                for seq, n, _p in self.rtx:
                    if seq >= b:
                        break
                    if seq >= a and seq + n <= b:
                        self.sacked.add(seq)
        if cum > self.una:
            self.dup = 0
            newly = cum - self.una
            self.una = cum
            k = 0
            while k < len(self.rtx) and self.rtx[k][0] + self.rtx[k][1] <= cum:
                k += 1
            if k:
                del self.rtx[:k]
            if self.sacked:
                self.sacked = {s for s in self.sacked if s >= cum}
            if self.rtx_done:
                self.rtx_done = {s for s in self.rtx_done if s >= cum}
            self.backoff = 1
            self.retries = 0
            self.cancel_rto()
            if self.nxt - self.una > 0:
                self.arm_rto()
            if self.in_rec:
                if self.una >= self.recover:
                    self.in_rec = False
                    self.rtx_done.clear()
                else:
                    self.holes(force_head=True)
            if self.cwnd < self.ssthresh:  # NewReno: slow start, AIMD
                self.cwnd += min(newly, self.cwnd)
            else:
                self.cwnd += max(1, MSS * newly // self.cwnd)
            if self.on_drain is not None and self.buffered < self.sbuf_cap:
                self.on_drain()
        elif (cum == self.una and wnd == prev and self.nxt - self.una > 0
              and self.rtx):
            self.dup += 1
            if self.dup == 3 and not self.in_rec:
                self.in_rec = True
                self.recover = self.nxt
                self.rtx_done.clear()
                inflight = self.nxt - self.una
                self.ssthresh = max(inflight // 2, MIN_CWND)
                self.cwnd = max(self.cwnd // 2, MIN_CWND)
                self.holes(force_head=True)
                self.arm_rto(reset=True)
            elif self.in_rec and sack is not None:
                self.holes()
        else:
            self.dup = 0
        self.pump()

    # -- receiver
    def on_segment(self, seq, n, payload) -> None:
        if seq + n <= self.rcv_nxt:
            self.dup_ack()
            return
        if seq > self.rcv_nxt:
            if seq not in self.ooo and n <= self.window():
                self.ooo[seq] = (n, payload)
                self.ooo_bytes += n
            self.dup_ack()
            return
        if n > self.window():
            self.host.owe_ack(self)
            return
        self.deliver(n, payload)
        while self.rcv_nxt in self.ooo:
            n2, p2 = self.ooo.pop(self.rcv_nxt)
            self.ooo_bytes -= n2
            self.deliver(n2, p2)
        self.host.owe_ack(self)

    def deliver(self, n, payload) -> None:
        self.rcv_nxt += n
        if self.on_data is not None:
            self.on_data(n, payload)

    def sack(self):
        if not self.ooo:
            return None
        out = bytearray()
        blocks = 0
        cs = ce = -1
        for s in sorted(self.ooo):
            n = self.ooo[s][0]
            if cs < 0:
                cs, ce = s, s + n
            elif s == ce:
                ce = s + n
            else:
                out += cs.to_bytes(8, "big") + ce.to_bytes(8, "big")
                blocks += 1
                if blocks == SACK_BLOCKS:
                    return bytes(out)
                cs, ce = s, s + n
        out += cs.to_bytes(8, "big") + ce.to_bytes(8, "big")
        return bytes(out)

    def dup_ack(self) -> None:
        if self.state in (CLOSED, TIME_WAIT):
            return
        self.host.acks.pop(self, None)
        self.emit(ACK, payload=self.sack(), acked=self.rcv_nxt,
                  wnd=self.last_wnd)

    def flush_ack(self) -> None:
        self.last_wnd = self.window()
        self.emit(ACK, payload=self.sack(), acked=self.rcv_nxt,
                  wnd=self.last_wnd)

    # -- connection
    def send_syn(self) -> None:
        self.syn_tries += 1
        if self.syn_tries > SYN_RETRIES:
            self.reset("connection timed out (ETIMEDOUT): SYN retries "
                       "exhausted")
            return
        self.emit(SYN, wnd=self.window())
        self.ctl = self.host.after(
            self.rto * min(1 << (self.syn_tries - 1), 64), self.syn_timeout)

    def syn_timeout(self) -> None:
        if self.state == SYN_SENT:
            self.send_syn()

    def sender_drained(self) -> None:
        if self.peer_fin and self.state in (ESTABLISHED, CLOSING):
            self.emit(FINACK)
            self.time_wait()
        elif self.state == CLOSING:
            self.state = FIN_SENT
            self.send_fin()

    def send_fin(self) -> None:
        self.fin_tries += 1
        if self.fin_tries > FIN_RETRIES:
            self.drop()
            return
        self.emit(FIN)
        self.ctl = self.host.after(
            self.rto * min(1 << (self.fin_tries - 1), 64), self.fin_timeout)

    def fin_timeout(self) -> None:
        if self.state == FIN_SENT:
            self.send_fin()

    def cancel_ctl(self) -> None:
        if self.ctl is not None:
            self.host.cancel(self.ctl)
            self.ctl = None

    def reset(self, reason) -> None:
        self.host.count("stream_resets", 1)
        err = self.on_error
        self.drop()
        if err is not None:
            err(reason)

    def drop(self) -> None:
        self.cancel_ctl()
        self.cancel_rto()
        self.state = CLOSED
        self.host.conns.pop((self.lport, self.rhost, self.rport), None)

    def time_wait(self) -> None:
        if self.state == TIME_WAIT:
            return
        was_open = self.state in (ESTABLISHED, CLOSING, FIN_SENT)
        self.state = TIME_WAIT
        self.cancel_ctl()
        self.cancel_rto()
        self.host.after(2 * self.rto, self.drop)
        if was_open and self.on_close is not None:
            self.on_close()

    def handle(self, kind, nbytes, payload, seq) -> None:
        """An arrival: for DATA ``nbytes`` and ``seq`` are the segment's;
        for the others the cumulative ack and the advertised window."""
        st = self.state
        if kind == SYN:
            if st == ESTABLISHED:
                self.adv = seq
                self.emit(SYNACK, wnd=self.window())
        elif kind == SYNACK:
            if st == SYN_SENT:
                self.state = ESTABLISHED
                self.adv = seq
                self.cancel_ctl()
                if self.on_connected is not None:
                    self.on_connected()
                self.pump()
        elif kind == DATA:
            if st not in (CLOSED, TIME_WAIT):
                self.host.count("stream_bytes_received", nbytes)
                self.on_segment(seq, nbytes, payload)
        elif kind == ACK:
            if st not in (CLOSED, TIME_WAIT):
                self.on_ack(nbytes, seq, payload)
        elif kind == FIN:
            if st == SYN_SENT:
                self.emit(FINACK)
                self.reset("connection closed by peer")
            elif (st in (ESTABLISHED, CLOSING)
                  and (self.buffered > 0 or self.nxt - self.una > 0)):
                self.peer_fin = True
            else:
                self.emit(FINACK)
                if self.state != CLOSED:
                    self.time_wait()
        elif kind == FINACK:
            if st == FIN_SENT:
                self.cancel_ctl()
                self.drop()
                if self.on_close is not None:
                    self.on_close()


# -- Tor framing over a stream ----------------------------------------------

class Conn:
    """A framed connection: writes queue behind a full send buffer (a
    partly written frame would desync the peer), reads reassemble cells
    and count DATA bodies."""

    __slots__ = ("ep", "pending", "buf", "body_left", "body_circ",
                 "on_cell", "on_body", "on_hdr", "close_drained")

    def __init__(self, ep, on_cell, on_body, on_hdr=None) -> None:
        self.ep = ep
        self.pending = []  # [payload, offset] | [None, count]
        self.buf = b""
        self.body_left = self.body_circ = 0
        self.on_cell, self.on_body, self.on_hdr = on_cell, on_body, on_hdr
        self.close_drained = False
        ep.on_data = self.feed
        ep.on_drain = self.drain

    def write(self, payload: bytes) -> None:
        self.pending.append([payload, 0])
        self.pump()

    def write_counted(self, n: int) -> None:
        self.pending.append([None, n])
        self.pump()

    def pump(self) -> None:
        pend, ep = self.pending, self.ep
        while pend:
            head = pend[0]
            if head[0] is not None:
                sent = ep.send(payload=head[0][head[1]:])
                head[1] += sent
                done = head[1] >= len(head[0])
            else:
                sent = ep.send(nbytes=head[1])
                head[1] -= sent
                done = head[1] <= 0
            if done:
                pend.pop(0)
            if sent == 0 and not done:
                return

    def drain(self) -> None:
        self.pump()
        if self.close_drained and not self.pending:
            self.ep.close()

    def close_when_drained(self) -> None:
        if not self.pending:
            self.ep.close()
        else:
            self.close_drained = True

    def feed(self, n: int, payload) -> None:
        if self.body_left > 0 and payload is None:
            take = min(n, self.body_left)
            self.body_left -= take
            self.on_body(self.body_circ, take)
            if n > take:
                raise ValueError("framing error: stray counted bytes")
            return
        if payload is None:
            raise ValueError("framing error: counted bytes outside a body")
        self.buf += payload
        while len(self.buf) >= CELL:
            b = self.buf
            ctype, circ = b[0], int.from_bytes(b[1:3], "big")
            ln = int.from_bytes(b[3:5], "big")
            if ctype == CELL_DATA:
                self.buf = b[CELL:]
                self.body_left, self.body_circ = ln, circ
                if self.on_hdr is not None:
                    self.on_hdr(circ, ln)
                return
            if len(b) < CELL + ln:
                return
            self.buf = b[CELL + ln:]
            self.on_cell(ctype, circ, b[CELL:CELL + ln])


# -- the applications ---------------------------------------------------------

class Relay:
    """TorRelay (and TorExit, ``exit_mode``)."""

    def __init__(self, host, args, exit_mode) -> None:
        self.host, self.exit_mode = host, exit_mode
        self.port = int(args[0]) if args else 9001
        self.table = {}  # (conn, circ) -> (conn, circ), both ways
        self.conns = {}
        self.next_conn, self.next_circ = 0, 1
        self.cells = self.bytes = 0

    def start(self) -> None:
        self.host.listeners[self.port] = lambda ep: self.new_conn(ep)

    def new_conn(self, ep):
        cid = self.next_conn
        self.next_conn += 1
        conn = Conn(ep, lambda t, c, p: self.on_cell(cid, t, c, p),
                    lambda c, n: self.on_body(cid, c, n),
                    lambda c, ln: self.on_hdr(cid, c, ln))
        self.conns[cid] = conn
        ep.on_close = lambda: self.on_conn_close(cid)
        return cid, conn

    def on_conn_close(self, cid) -> None:
        self.conns.pop(cid, None)
        peers = [v for k, v in self.table.items() if k[0] == cid]
        self.table = {k: v for k, v in self.table.items()
                      if k[0] != cid and v[0] != cid}
        for ncid, _ in peers:
            pc = self.conns.get(ncid)
            if pc is not None:
                pc.close_when_drained()

    def on_cell(self, cid, ctype, circ, payload) -> None:
        key = (cid, circ)
        if ctype == CREATE:
            self.conns[cid].write(cell(CREATED, circ))
            return
        if ctype == EXTEND and key not in self.table:
            target, port = payload.decode().rsplit(":", 1)
            ep = self.host.connect(target, int(port))
            ncid, nconn = self.new_conn(ep)
            ncirc = self.next_circ
            self.next_circ += 1
            self.table[key] = (ncid, ncirc)
            self.table[(ncid, ncirc)] = key
            ep.on_connected = lambda: nconn.write(cell(CREATE, ncirc))
            ep.connect()
            return
        if ctype == BEGIN and self.exit_mode and key not in self.table:
            self.begin(cid, circ, payload)
            return
        if ctype == CREATED:
            back = self.table.get(key)
            if back is not None:
                self.conns[back[0]].write(cell(EXTENDED, back[1]))
            return
        nxt = self.table.get(key)
        if nxt is None:
            return
        self.cells += 1
        self.conns[nxt[0]].write(cell(ctype, nxt[1], payload))

    def on_hdr(self, cid, circ, ln) -> None:
        nxt = self.table.get((cid, circ))
        if nxt is not None:
            self.conns[nxt[0]].write(data_header(nxt[1], ln))

    def on_body(self, cid, circ, n) -> None:
        nxt = self.table.get((cid, circ))
        if nxt is not None:
            self.bytes += n
            self.conns[nxt[0]].write_counted(n)

    def begin(self, cid, circ, payload) -> None:
        """Exit termination: fetch from the server, reframe the bytes as
        DATA toward the client, END at the fetch's size."""
        dest, port, want = payload.decode().split(":")
        want = int(want)
        ep = self.host.connect(dest, int(port))
        got = [0, False]

        def on_connected():
            ep.send(payload=str(want).encode().rjust(8))
            self.conns[cid].write(cell(CONNECTED, circ))

        def on_data(n, _payload):
            out = self.conns.get(cid)
            if out is not None:
                out.write(data_header(circ, n))
                out = self.conns.get(cid)
                if out is not None:
                    out.write_counted(n)
            got[0] += n
            if got[0] >= want and not got[1]:
                got[1] = True
                ep.close()
                out = self.conns.get(cid)
                if out is not None:
                    out.write(cell(END, circ))

        ep.on_connected = on_connected
        ep.on_data = on_data
        ep.connect()


class Server:
    """TGenServer: an 8-byte decimal request, answered with that many
    counted bytes."""

    def __init__(self, host, args) -> None:
        self.host = host
        self.port = int(args[0]) if args else 8080

    def start(self) -> None:
        self.host.listeners[self.port] = self.accept

    def accept(self, ep) -> None:
        left = [0]

        def push():
            if left[0] > 0:
                left[0] -= ep.send(left[0])

        def on_data(n, payload):
            if payload is not None:
                try:
                    want = int(payload.decode().strip())
                except ValueError:
                    want = 0
                if want > 0:
                    left[0] += want
                    push()

        ep.on_data = on_data
        ep.on_drain = push


class Client:
    """TorClient with a relay table and a fetch schedule."""

    def __init__(self, host, args) -> None:
        self.host = host
        self.port = int(args[1])
        self.server, self.sport = args[2], int(args[3])
        self.count = int(args[5])
        pairs = [e.split(":") for e in args[7].split(",")]
        self.weight = [int(w) for w, _ in pairs]
        self.flags = [f for _, f in pairs]
        self.sched = {}
        for item in args[8].split(";"):
            key, val = item.split("=", 1)
            self.sched[key] = val.split(":")
        if "sizes" in self.sched:
            self.sizes = [int(s) for s in self.sched["sizes"]]
            self.count = len(self.sizes)
        else:
            self.sizes = None
        self.completed = self.failed = self.attempted = 0
        self.fetches = []
        self.open = {}  # attempt -> its open fetch's record as it stands
        self.exit_code = None
        self.exit_at = None

    def start(self) -> None:
        _, lo, hi = self.sched["delay"]
        lo, hi = int(lo) * MS, int(hi) * MS
        if hi > lo:
            self.host.after(int(self.host.rng.integers(lo, hi)), self.fetch)
        else:
            self.fetch()

    def pick(self, flag: str, chosen: list) -> int:
        """A relay carrying ``flag`` and not in ``chosen``, drawn with
        probability proportional to its weight."""
        ids, cum, elig = self.host.sim.tables(flag, self.weight, self.flags)
        w = self.weight
        skip = sorted(elig[c] for c in chosen if c in elig)
        r = int(self.host.rng.integers(
            0, cum[-1] - sum(w[ids[j]] for j in skip)))
        for j in skip:  # the chosen relays' spans are not on the line
            if r >= cum[j] - w[ids[j]]:
                r += w[ids[j]]
        return ids[bisect_right(cum, r)]

    def fetch(self) -> None:
        rng = self.host.rng
        if self.sizes is not None:
            size = self.sizes[self.attempted]
        else:
            _, median, sigma, lo, hi = self.sched["size"]
            size = int(rng.lognormal(math.log(int(median)), float(sigma)))
            size = min(max(size, int(lo)), int(hi))
        ex = self.pick("E", [])
        guard = self.pick("G", [ex])
        middle = self.pick("", [ex, guard])
        self.build([guard, middle, ex], size)

    def build(self, hops, size) -> None:
        host = self.host
        names = [f"relay{i}" for i in hops]
        self.attempted += 1
        attempt = self.attempted
        t0 = host.now
        st = {"stage": 0, "bd": None, "got": 0}
        ep = host.connect(names[0], self.port)

        def record(got, elapsed):
            bd = st["bd"]
            self.open.pop(attempt, None)
            self.fetches.append((*hops, size, got,
                                 -1 if bd is None else bd - t0, elapsed))

        self.open[attempt] = lambda: (
            *hops, size, st["got"], -1 if st["bd"] is None else st["bd"] - t0,
            -1)

        def on_cell(ctype, _circ, _payload):
            if ctype in (CREATED, EXTENDED):
                st["stage"] += 1
                if st["stage"] == 3:
                    st["bd"] = host.now
                if st["stage"] < 3:
                    conn.write(cell(EXTEND, 1, f"{names[st['stage']]}:"
                                    f"{self.port}".encode()))
                else:
                    conn.write(cell(BEGIN, 1, f"{self.server}:{self.sport}:"
                                    f"{size}".encode()))
            elif ctype == END:
                record(st["got"], host.now - t0)
                if st["got"] >= size:
                    self.completed += 1
                else:
                    self.failed += 1
                ep.close()
                self.finish()

        def on_body(_circ, n):
            st["got"] += n

        def on_error(_msg):
            record(0, host.now - t0)
            self.failed += 1
            self.finish()

        conn = Conn(ep, on_cell, on_body)
        ep.on_connected = lambda: conn.write(cell(CREATE, 1))
        ep.on_error = on_error
        ep.connect()

    def finish(self) -> None:
        if self.completed + self.failed >= self.count:
            if self.exit_code is None:
                self.exit_code = 0 if self.failed == 0 else 1
                self.exit_at = self.host.now
                self.host.count("processes_exited", 1)
        else:
            kind, *vals = self.sched["think"]
            if kind == "fixed":
                wait = int(vals[0]) * MS
            else:
                wait = int(self.host.rng.integers(int(vals[0]) * MS,
                                                  int(vals[1]) * MS))
            self.host.after(wait, self.fetch)


# -- hosts and the network ----------------------------------------------------

class Host:
    __slots__ = ("sim", "id", "name", "node", "now", "seq", "cancelled",
                 "ctr", "egress", "up", "down", "cap_up", "cap_down", "base",
                 "tok_up", "debt", "tok", "refilled", "waiting", "conns",
                 "listeners", "ephemeral", "acks", "rng", "counters", "app",
                 "proc", "start")

    def __init__(self, sim, hid, spec) -> None:
        self.sim, self.id = sim, hid
        self.name, self.node = spec["name"], spec["node"]
        self.now = 0
        self.seq = 0
        self.cancelled = set()
        self.ctr = 0
        self.egress = []
        self.up, self.down = spec["up"], spec["down"]
        floor = max(MIN_CAP, sim.unit_mtus * MTU + HEADER)
        self.cap_up = min(max(self.up * sim.w // NS, floor), (1 << 31) - 2)
        self.cap_down = min(max(self.down * sim.w // NS, floor),
                            (1 << 31) - 2)
        self.base, self.tok_up, self.debt = 0, self.cap_up, 0
        self.tok, self.refilled = self.cap_down, 0
        self.waiting = []
        self.conns = {}
        self.listeners = {}
        self.ephemeral = 49152
        self.acks = {}
        self.rng = np.random.Generator(np.random.Philox(
            key=((sim.seed << 16) ^ hid) & 0xFFFFFFFFFFFFFFFF))
        self.counters = {}
        self.app = None
        self.proc, self.start = spec["path"], spec["start"]

    def count(self, key, n) -> None:
        self.counters[key] = self.counters.get(key, 0) + n

    def after(self, delay, fn):
        return self.at(self.now + delay, fn)

    def at(self, t, fn):
        seq = self.seq
        self.seq += 1
        heapq.heappush(self.sim.heap, (t, 1, seq, self.id, fn))
        return seq

    def cancel(self, seq) -> None:
        self.cancelled.add(seq)

    def owe_ack(self, ep) -> None:
        if not self.acks:
            self.sim.ack_hosts.append(self)
        self.acks[ep] = None

    def connect(self, name, port):
        rhost = self.sim.by_name[name]
        ep = Ep(self, self.ephemeral, rhost, port)
        self.ephemeral += 1
        self.conns[(ep.lport, rhost, port)] = ep
        return ep

    def refill(self) -> None:
        """The down bucket's refills at every round start up to now."""
        starts, k = self.sim.starts, len(self.sim.starts) - 1
        i = self.refilled
        tok, cap, rate = self.tok, self.cap_down, self.down
        while i < k and tok < cap:
            i += 1
            dt = starts[i] - starts[i - 1]
            if dt > 0:
                tok += min(min(over(rate, dt), cap), cap - tok)
        self.tok, self.refilled = tok, k

    def deliver(self, row) -> None:
        kind, src, sport, dport, nbytes, seq, payload = row
        self.count("units_delivered", 1)
        ep = self.conns.get((dport, src, sport))
        if ep is None:
            accept = self.listeners.get(dport) if kind == SYN else None
            if accept is None:
                self.count("units_unroutable", 1)
                return
            ep = Ep(self, dport, src, sport)
            ep.state = ESTABLISHED
            ep.adv = seq
            self.conns[(dport, src, sport)] = ep
            ep.emit(SYNACK, wnd=ep.window())
            accept(ep)
            return
        ep.handle(kind, nbytes, payload, seq)


class Sim:
    def __init__(self, doc: dict, rounds: int) -> None:
        nodes, edges = parse_gml(doc["network"]["graph"]["inline"])
        lat, thresh = routes(nodes, edges)
        self.lat, self.thresh = lat.tolist(), thresh.tolist()
        positive = lat[(lat > 0) & (lat < (1 << 61))]
        self.w = int(positive.min()) if positive.size else MS
        ids = {int(n["id"]): i for i, n in enumerate(nodes)}
        exp = doc.get("experimental", {}) or {}
        self.unit_mtus = int(exp.get("unit_mtus", 10))
        self.chunk = self.unit_mtus * MTU - HEADER
        self.sbuf = int(exp.get("socket_send_buffer", 131072))
        self.rbuf = int(exp.get("socket_recv_buffer", 174760))
        self.seed = int(doc["general"]["seed"])
        self.stop = parse_time(doc["general"]["stop_time"])
        self.rounds = rounds
        specs = []
        for name, h in doc["hosts"].items():
            qty = int(h.get("quantity", 1))
            node = ids[int(h["network_node_id"])]
            nd = nodes[node]
            up = parse_bandwidth(h.get("bandwidth_up")
                                 or nd["host_bandwidth_up"])
            down = parse_bandwidth(h.get("bandwidth_down")
                                   or nd["host_bandwidth_down"])
            (proc,) = h["processes"]
            for i in range(qty):
                specs.append({
                    "name": name if qty == 1 else f"{name}{i}",
                    "node": node, "up": up, "down": down,
                    "path": proc, "start": parse_time(
                        proc.get("start_time", 0))})
        self.heap = []
        self.hosts = [Host(self, i, s) for i, s in enumerate(specs)]
        self.by_name = {h.name: h.id for h in self.hosts}
        self.ack_hosts = []
        self.emitting = []
        self.waiting = set()
        self.starts = [0]
        self.pending = []  # units awaiting their loss draws
        self.pending_due = INF
        self.c = {"events": 0, "sent": 0, "dropped": 0, "bytes": 0}
        self._tables = {}
        for h in self.hosts:
            h.at(h.start, lambda h=h: self.spawn(h))

    def tables(self, flag, weight, flags):
        """The relays carrying ``flag`` (every relay for ""), their
        cumulative weights and each one's place among them."""
        t = self._tables.get(flag)
        if t is None:
            ids = [i for i, f in enumerate(flags) if flag in f]
            t = (ids, list(accumulate(weight[i] for i in ids)),
                 {r: j for j, r in enumerate(ids)})
            self._tables[flag] = t
        return t

    def spawn(self, h) -> None:
        proc = h.proc
        path, args = proc["path"], [str(a) for a in proc["args"]]
        if path == APP + "tor:TorRelay":
            h.app = Relay(h, args, False)
        elif path == APP + "tor:TorExit":
            h.app = Relay(h, args, True)
        elif path == APP + "tgen:TGenServer":
            h.app = Server(h, args)
        elif path == APP + "tor:TorClient":
            h.app = Client(h, args)
        else:
            raise ValueError(f"the reference models Tor hosts only, not "
                             f"{h.name}: {path}")
        h.count("processes_spawned", 1)
        h.app.start()

    # -- the round loop
    def run(self) -> None:
        now, stop, heap = 0, self.stop, self.heap
        hosts = self.hosts
        while now < stop:
            end = min(now + self.w, stop)
            if now > self.starts[-1]:
                self.starts.append(now)
            if self.pending and self.pending_due < end:
                self.draw()
            if self.waiting:
                self.retry(now)
            executed = 0
            while heap and heap[0][0] < end:
                t, band, key, hid, what = heapq.heappop(heap)
                h = hosts[hid]
                if band == 1:
                    if key in h.cancelled:
                        h.cancelled.discard(key)
                        continue
                    h.now = t
                    what()
                else:
                    if t > h.now:
                        h.now = t
                    h.refill()
                    size = what[0]
                    if h.tok >= size:
                        h.tok -= size
                        h.deliver(what[1:])
                    else:
                        h.waiting.append(what)
                        self.waiting.add(hid)
                executed += 1
            self.c["events"] += executed
            self.barrier(now, end)
            if executed == 0 and not self.waiting:
                nt = self.next_time()
                while self.pending and self.pending_due < nt:
                    self.draw()
                    nt = self.next_time()
                if nt >= INF:
                    break
                now = max(end, nt)
            else:
                now = end
        if self.pending:
            self.draw()

    def next_time(self) -> int:
        heap, hosts = self.heap, self.hosts
        while heap:
            t, band, key, hid, _ = heap[0]
            if band == 1 and key in hosts[hid].cancelled:
                heapq.heappop(heap)
                hosts[hid].cancelled.discard(key)
                continue
            return t
        return INF

    def retry(self, start) -> None:
        """Arrivals waiting for down tokens, retried at a round's start."""
        hosts = self.hosts
        for hid in sorted(self.waiting):
            h = hosts[hid]
            h.refill()
            rows, h.waiting = h.waiting, []
            for row in rows:
                if h.tok >= row[0]:
                    h.tok -= row[0]
                    if start > h.now:
                        h.now = start
                    h.deliver(row[1:])
                else:
                    h.waiting.append(row)
        self.waiting = {hid for hid in self.waiting if hosts[hid].waiting}

    def barrier(self, start, end) -> None:
        acks, self.ack_hosts = self.ack_hosts, []
        for h in sorted(acks, key=lambda h: h.id):
            eps = list(h.acks)
            h.acks.clear()
            for ep in eps:
                if ep.state != CLOSED:
                    ep.flush_ack()
        lat, thresh, heap = self.lat, self.thresh, self.heap
        emitting, self.emitting = self.emitting, []
        for h in sorted(emitting, key=lambda h: h.id):
            rows, h.egress = h.egress, []
            h.count("units_emitted", len(rows))
            # the up bucket, rebased at the round's start when above cap
            rate = h.up
            if h.tok_up + over(rate, start - h.base) - h.debt > h.cap_up:
                h.base, h.tok_up, h.debt = start, h.cap_up, 0
            q = 0
            sn = h.node
            for r in rows:
                kind, dst, size, temit = r[0], r[1], r[2], r[3]
                q += size
                need = h.debt + q - h.tok_up
                if need > 0:
                    a, b = divmod(need, rate)
                    ready = h.base + a * NS + (b * NS + rate - 1) // rate
                    dep = temit if temit > ready else ready
                else:
                    dep = temit
                uid = (h.id << 32) | h.ctr
                h.ctr += 1
                dn = self.hosts[dst].node
                t = max(dep + lat[sn][dn], end)
                ev = (t, 0, uid, dst, (size, kind, h.id, r[4], r[5], r[6],
                                       r[7], r[8]))
                th = thresh[sn][dn]
                if th:
                    self.pending.append((ev, th, -(-size // MTU)))
                    if t < self.pending_due:
                        self.pending_due = t
                else:
                    self.c["sent"] += 1
                    self.c["bytes"] += size
                    heapq.heappush(heap, ev)
            h.debt += q

    def draw(self) -> None:
        """Resolve every pending unit's loss draws at once."""
        pend, self.pending = self.pending, []
        self.pending_due = INF
        uid = np.array([p[0][2] for p in pend], dtype=np.uint64)
        npk = np.array([min(max(p[2], 1), MAX_PKTS) for p in pend])
        th = np.array([p[1] for p in pend], dtype=np.uint32)
        k = int(npk.max())
        lo = (uid & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (uid >> np.uint64(32)).astype(np.uint32)
        pkt = np.arange(k, dtype=np.uint32)[None, :]
        c0 = np.broadcast_to(lo[:, None], (len(pend), k))
        c1 = hi[:, None] | (pkt << np.uint32(PKT_SHIFT))
        seed = self.seed
        x0, _ = threefry2x32(seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
                             c0, c1, self.rounds)
        hit = ((x0 >> np.uint32(8)) < th[:, None]) & (pkt < npk[:, None])
        lost = hit.any(axis=1).tolist()
        heap = self.heap
        for p, gone in zip(pend, lost):
            if gone:
                self.c["dropped"] += 1
            else:
                self.c["sent"] += 1
                self.c["bytes"] += p[0][4][0]
                heapq.heappush(heap, p[0])


def simulate(doc: dict, rounds: int = 20) -> dict:
    """Run the document; the result the comparison covers. ``rounds`` is
    for the control only."""
    sim = Sim(doc, rounds)
    sim.run()
    records, stats, errors = [], [], []
    counters: dict = {}
    for h in sim.hosts:
        for k, v in h.counters.items():
            counters[k] = counters.get(k, 0) + v
        app = h.app
        if isinstance(app, Client):
            records.append(app.fetches
                           + [f() for f in app.open.values()])
            want = h.proc.get("expected_final_state")
            if want is None:
                pass
            elif want != {"exited": 0}:
                raise ValueError(f"the reference holds clients to no final "
                                 f"state or exit 0, not {want!r}")
            elif app.exit_code is None:
                errors.append(f"{h.name}/torclient.0: expected exit 0, "
                              f"still running")
            elif app.exit_code != 0:
                errors.append(f"{h.name}/torclient.0: expected exit 0, "
                              f"got {app.exit_code}")
        elif isinstance(app, Relay):
            stats.append((app.cells, app.bytes))
    counters["events"] = sim.c["events"]
    fetches = [f for r in records for f in r]
    sizes = {}
    for f in fetches:
        if f[6] < 0:
            continue
        cls = ("<= 64 KiB" if f[3] <= 65536 else
               "<= 1 MiB" if f[3] <= 1 << 20 else "> 1 MiB")
        sizes.setdefault(cls, []).append(f[6])
    return {"events": sim.c["events"], "units_sent": sim.c["sent"],
            "units_dropped": sim.c["dropped"], "bytes_sent": sim.c["bytes"],
            "counters": dict(sorted((k, v) for k, v in counters.items()
                                    if v)),
            "process_errors": errors,
            "fetch_digest": fetch_digest(records),
            "relay_digest": relay_digest(stats),
            "circuits": len(fetches),
            "fetches_done": sum(f[4] >= f[3] for f in fetches),
            "fetches_open": sum(f[6] < 0 for f in fetches),
            "fetches_wanted": sum(h.app.count for h in sim.hosts
                                  if isinstance(h.app, Client)),
            "fetch_bytes": sum(f[4] for f in fetches),
            "last_exit_ms": max((h.app.exit_at for h in sim.hosts
                                 if isinstance(h.app, Client)
                                 and h.app.exit_at is not None),
                                default=0) // MS,
            "done_ms": {k: (int(np.percentile(v, 50)) // MS,
                            int(np.percentile(v, 99)) // MS)
                        for k, v in sorted(sizes.items())}}


def fetch_digest(records: list) -> str:
    """One sha256 over every client's per-fetch records, in host order:
    ``records`` holds one list of (guard, middle, exit, size, got,
    build_ns, done_ns) per client, open fetches last with done_ns -1."""
    h = hashlib.sha256()
    for i, fetches in enumerate(records):
        for f in fetches:
            h.update((f"{i}:" + ",".join(str(x) for x in f) + "\n")
                     .encode())
    return h.hexdigest()


def relay_digest(stats: list) -> str:
    """One sha256 over every relay's (cells, bytes), in host order."""
    h = hashlib.sha256()
    for cells, nbytes in stats:
        h.update(f"{cells},{nbytes}\n".encode())
    return h.hexdigest()


def mismatches(results: list, ref: dict) -> dict:
    """Per compared key, how many of ``results`` differ from ``ref``."""
    return {k: sum(r[k] != ref[k] for r in results) for k in KEYS}


def describe(ref: dict) -> str:
    """The reference's part of the harness's ``reference:`` line."""
    return (f"events {ref['events']}, units_sent {ref['units_sent']}, "
            f"dropped {ref['units_dropped']}, circuits {ref['circuits']}, "
            f"fetches completed {ref['fetches_done']} of "
            f"{ref['fetches_wanted']}, open at the stop "
            f"{ref['fetches_open']}, last client exit "
            f"{ref['last_exit_ms']} ms, bytes fetched {ref['fetch_bytes']}, "
            f"completion p50/p99 ms by size {ref['done_ms']}")
