"""The seeded random GML graph both configurations sit on: a copy of
``tools/gen_benchmarks.py::random_gml`` and ``indent`` at commit 9028760."""

from __future__ import annotations


def random_gml(rng, g, min_lat_ms=5, max_lat_ms=80, max_loss=0.004,
               bw_choices=("100 Mbit", "250 Mbit", "1 Gbit")) -> str:
    """Connected random graph: a ring plus g random chords, latencies, loss
    and node bandwidths drawn from ``rng``."""
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
