"""Tor-shaped onion-routing workload tests (BASELINE.md config #3 model)."""

import pytest
import yaml

from shadow_tpu.config import parse_config
from shadow_tpu.core.controller import Controller

TOR_CFG = """
general:
  stop_time: 60s
  seed: 12
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        node [ id 1 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 2 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        edge [ source 0 target 1 latency "20 ms" ]
        edge [ source 0 target 2 latency "40 ms" ]
        edge [ source 1 target 2 latency "30 ms" ]
        edge [ source 0 target 0 latency "5 ms" ]
        edge [ source 1 target 1 latency "5 ms" ]
        edge [ source 2 target 2 latency "5 ms" ]
      ]
hosts:
  relay:
    network_node_id: 1
    quantity: 6
    processes:
      - path: pyapp:shadow_tpu.models.tor:TorExit
        args: ["9001"]
  web:
    network_node_id: 0
    processes:
      - path: pyapp:shadow_tpu.models.tgen:TGenServer
        args: ["80"]
  user:
    network_node_id: 2
    quantity: 4
    processes:
      - path: pyapp:shadow_tpu.models.tor:TorClient
        args: ["6", "9001", web, "80", "200 kB", "2"]
        start_time: 1s
        expected_final_state: {exited: 0}
"""


def run(**over):
    cfg = parse_config(yaml.safe_load(TOR_CFG), {
        "general.data_directory": "/tmp/st-tor", **over})
    c = Controller(cfg, mirror_log=False)
    return c, c.run()


def test_circuits_complete_through_three_hops():
    c, result = run()
    assert result["process_errors"] == [], result["process_errors"]
    clients = [p.app for p in c.processes if p.name.startswith("torclient")]
    assert len(clients) == 4
    for cl in clients:
        assert cl.completed == 2 and cl.failed == 0
        # 3 hops + exit fetch: at least 4 one-way latencies each direction
        # plus telescoping handshakes; must be well over one direct RTT
        for t in cl.completion_times:
            assert t > 100_000_000, t
    # relays actually relayed: total relayed bytes >= 2 hops' worth of the
    # 8 fetches (the exit hop re-frames rather than relays)
    relays = [p.app for p in c.processes if p.name.startswith("torexit")]
    total = sum(r.bytes_relayed for r in relays)
    assert total >= 2 * 8 * 200_000, total
    for h in c.hosts:
        assert h._conns == {}, h.name


def test_tor_deterministic():
    _, r1 = run(**{"general.data_directory": "/tmp/st-tor-d1"})
    _, r2 = run(**{"general.data_directory": "/tmp/st-tor-d2"})
    for k in ("events", "units_sent", "units_dropped", "bytes_sent", "counters"):
        assert r1[k] == r2[k], k


def test_weighted_path_choice_by_position():
    """Bandwidth-weighted choice (TorClient's ``relays`` argument): exits
    always Exit-flagged, guards always Guard-flagged, three distinct hops,
    and each position's frequencies proportional to weight among the
    relays it may take. Over 60,000 circuits a relay's count lies within
    5 standard deviations of its expectation given the earlier hops."""
    import numpy as np

    from shadow_tpu.models.tor import relay_table

    spec = "100:GE,300:G,50:E,250:,400:GE,200:E,120:G,80:"
    tab = relay_table(spec)
    w = [int(e.split(":")[0]) for e in spec.split(",")]
    flags = [e.split(":")[1] for e in spec.split(",")]
    rng = np.random.Generator(np.random.Philox(key=7))
    n = 60_000
    seen = {pos: np.zeros(len(w)) for pos in ("E", "G", "")}
    want = {pos: np.zeros(len(w)) for pos in ("E", "G", "")}
    for _ in range(n):
        path = []
        for pos in ("E", "G", ""):
            elig = [i for i in range(len(w))
                    if pos in flags[i] and i not in path]
            tot = sum(w[i] for i in elig)
            for i in elig:
                want[pos][i] += w[i] / tot
            r = tab.draw(rng, pos, tuple(path))
            assert r in elig
            seen[pos][r] += 1
            path.append(r)
        assert len(set(path)) == 3
    for pos in ("E", "G", ""):
        sd = np.sqrt(want[pos] * (1 - want[pos] / n)) + 1e-9
        assert (np.abs(seen[pos] - want[pos]) <= 5 * sd).all(), pos
    # a relay outside a position is never drawn there
    assert seen["E"][[1, 3, 6, 7]].sum() == 0
    assert seen["G"][[2, 3, 5, 7]].sum() == 0


def test_weighted_draw_is_one_integer_and_a_bisect():
    """The draw reads exactly one ``rng.integers(0, total)`` and maps it
    over the cumulative weights of the eligible relays not yet chosen."""
    from shadow_tpu.models.tor import relay_table

    tab = relay_table("10:GE,20:G,30:E,40:GE")

    class Fixed:
        def __init__(self, r):
            self.r, self.calls = r, []

        def integers(self, lo, hi):
            self.calls.append((lo, hi))
            return self.r

    # guards (G) without relay 3 chosen: relays 0, 1 -> cumulative 10, 30
    for r, want in ((0, 0), (9, 0), (10, 1), (29, 1)):
        rng = Fixed(r)
        assert tab.draw(rng, "G", (3,)) == want
        assert rng.calls == [(0, 30)]
    # middle among all but 0 and 3: relays 1, 2 -> cumulative 20, 50
    for r, want in ((0, 1), (19, 1), (20, 2), (49, 2)):
        rng = Fixed(r)
        assert tab.draw(rng, "", (3, 0)) == want
        assert rng.calls == [(0, 50)]


@pytest.mark.parametrize("n_relays, n_exits", [("4", "1"), ("5", "0")])
def test_relay_table_refuses_n_exits_and_a_short_table(n_relays, n_exits):
    """With a relay table the E flags name the exits: an ``n_exits``
    beside it, or a table of another length than ``n_relays``, is
    refused at the process's start."""
    from shadow_tpu.models.tor import TorClient

    args = [n_relays, "9001", "web", "80", "0", "2", n_exits,
            "10:GE,20:G,30:E,40:GE", "sizes=1000:2000"]
    with pytest.raises(ValueError, match="relay table"):
        TorClient(None, args, {})
    args[0], args[6] = "4", "0"
    assert TorClient(None, args, {}).n_circuits == 2
