"""Bitcoin Core's transaction and block relay over stream connections.

Each node is a listening Bitcoin Core node with its defaults
(``src/net.h``, ``src/net_processing.cpp``, BIP 152):

- It opens ``OUTBOUND_FULL_RELAY`` (8) full-relay connections and
  ``BLOCK_RELAY_ONLY`` (2) block-relay-only ones to distinct peers drawn
  uniformly from the host RNG, and accepts inbound connections up to
  ``MAX_CONNECTIONS`` (125) in all.
- A connection opens with ``version`` (its relay flag off on a
  block-relay-only link), ``wtxidrelay`` and ``verack`` each way, then
  ``sendheaders`` and ``sendcmpct``; a node asks the first
  ``HB_PEERS`` (3) outbound peers whose handshake completes for
  high-bandwidth compact blocks (``sendcmpct`` announce 1).
- A transaction a node accepts is queued for every full-relay peer not
  known to have it. Each outbound peer has its own Poisson trickle timer
  (mean 2 s); the inbound peers share one (mean 5 s). A timer is armed
  when its queue turns non-empty and sends one ``inv`` of wtxid entries
  (36 B each) per peer when it fires. A node requests the new entries of
  an ``inv`` at once with one ``getdata`` to the announcer; ``tx`` is 400
  B.
- The block: every transaction of the experiment made before the
  block's time, with the rest of its 3,000 transactions older ones that
  every mempool holds. It goes
  whole (``cmpctblock``: 88 B, 6 B per short id, a 250-B prefilled
  coinbase) to the peers that asked for high-bandwidth relay and by
  ``headers`` to the rest, which fetch it with ``getdata(CMPCT_BLOCK)``.
  A node missing k of its transactions sends one ``getblocktxn`` for the
  k and takes them from ``blocktxn``; then it relays the block on.

The hot half (message handling, queues, timers, the block) runs in the C
engine (``native/colcore`` ``BtcNode``), which frames every message at
its wire size over the C stream endpoints: a 24-byte header plus the
payload, ``version`` 102 B, ``verack``, ``wtxidrelay`` and
``sendheaders`` 0, ``sendcmpct`` 9, ``inv`` and ``getdata`` a
CompactSize count and 36 B an entry, ``tx`` 400, ``headers`` 82,
``getdata(CMPCT_BLOCK)`` 37, ``cmpctblock`` 88 + 6 * 3,000 + 250,
``getblocktxn`` 32 + count + 3 B an index, ``blocktxn`` 32 + count +
400 B a transaction. The model therefore needs the C engine:
``experimental.scheduler_policy: tpu_batch`` with ``native_colcore`` on.

args: ``[port, n_nodes, txs, block]``: the nodes are hosts ``0 ..
n_nodes - 1``; ``txs`` is ``"<ns>:<origin>,..."``, transaction ``i``
made at ``<ns>`` by host ``<origin>`` (at most 64); ``block`` is
``"<ns>:<miner>"``. Node start times are drawn uniform in
[0, ``START_SPREAD_NS``).
"""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

from shadow_tpu.core.time import NS_PER_MS, NS_PER_SEC

OUTBOUND_FULL_RELAY = 8  # MAX_OUTBOUND_FULL_RELAY_CONNECTIONS
BLOCK_RELAY_ONLY = 2  # MAX_BLOCK_RELAY_ONLY_CONNECTIONS
MAX_CONNECTIONS = 125  # DEFAULT_MAX_PEER_CONNECTIONS
OUTBOUND_TRICKLE_NS = 2 * NS_PER_SEC  # OUTBOUND_INVENTORY_BROADCAST_INTERVAL
INBOUND_TRICKLE_NS = 5 * NS_PER_SEC  # INBOUND_INVENTORY_BROADCAST_INTERVAL
START_SPREAD_NS = 500 * NS_PER_MS
MAX_TXS = 64
#: the message types, in the order the C node's ``stats()`` counts them
MESSAGES = ("version", "verack", "wtxidrelay", "sendheaders", "sendcmpct",
            "inv", "getdata", "tx", "headers", "cmpctblock", "getblocktxn",
            "blocktxn")


@lru_cache(maxsize=4)
def schedule(txs: str, block: str) -> tuple:
    """``(txs, block)``: each transaction's (ns, origin) in txid order,
    and the block's (ns, miner); parsed once per process."""
    out = tuple(tuple(int(v) for v in e.split(":")) for e in txs.split(",")
                if e)
    if len(out) > MAX_TXS:
        raise ValueError(f"at most {MAX_TXS} transactions, not {len(out)}")
    t, miner = (int(v) for v in block.split(":"))
    return out, (t, miner)


class BitcoinNode:
    """args: [port, n_nodes, txs, block] (see the module docstring)."""

    def __init__(self, api, args, env):
        self.api = api
        self.port = int(args[0])
        self.n_nodes = int(args[1])
        self.txs, self.block = schedule(args[2], args[3])
        self._c = None

    def start(self):
        api = self.api
        host = api._host
        core = getattr(getattr(host, "colplane", None), "_c", None)
        if core is None or host.pcap is not None:
            raise RuntimeError(
                "BitcoinNode runs on the C engine only: "
                "experimental.scheduler_policy tpu_batch, native_colcore on, "
                "no pcap")
        rng = api.rng
        me = api.host_id
        want = OUTBOUND_FULL_RELAY + BLOCK_RELAY_ONLY
        peers = []
        while len(peers) < min(want, self.n_nodes - 1):
            p = int(rng.integers(0, self.n_nodes))
            if p != me and p not in peers:
                peers.append(p)
        delay = int(rng.integers(0, START_SPREAD_NS))
        seed = int(rng.integers(0, 2**64, dtype=np.uint64))
        self._c = core.btc_node(
            me, self.port, peers, OUTBOUND_FULL_RELAY, seed,
            MAX_CONNECTIONS - want, OUTBOUND_TRICKLE_NS, INBOUND_TRICKLE_NS)
        api.listen(self.port, self._c.accept)
        api.after(delay, self._c.boot)
        now = api.now
        for txid, (t, origin) in enumerate(self.txs):
            if origin == me:
                api.after(t - now, partial(self._c.originate, txid))
        t, miner = self.block
        if miner == me:
            mask = sum(1 << i for i, (tt, _) in enumerate(self.txs) if tt < t)
            api.after(t - now, partial(self._c.mine, mask))

    def stop(self):
        known, block_ns, gbt, _, _, _ = self._c.stats()
        self.api.log(f"bitcoin done: known={bin(known).count('1')} "
                     f"block_ns={block_ns} getblocktxn={gbt}")
