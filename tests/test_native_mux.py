"""I/O-multiplexing + UDP managed-process coverage: poll/epoll event-loop
servers and datagram sockets, dual-run (native kernel as oracle, then
inside the simulator)."""

import socket
import subprocess
import threading
import time as _time
from pathlib import Path

import pytest
import yaml

from shadow_tpu.config import parse_config
from shadow_tpu.core.controller import Controller

ROOT = Path(__file__).resolve().parents[1]
BUILD = ROOT / "native" / "build"


@pytest.mark.parametrize("mode", ["poll", "epoll"])
def test_mux_srv_native_oracle(mode):
    import random

    port = random.randint(20000, 60000)
    p = subprocess.Popen([str(BUILD / "mux_srv"), str(port), "3", mode],
                         stdout=subprocess.PIPE, text=True)
    _time.sleep(0.2)

    def fetch(n):
        s = socket.socket()
        s.connect(("127.0.0.1", port))
        s.sendall(str(n).encode().rjust(8))
        got = 0
        while got < n:
            b = s.recv(65536)
            assert b
            got += len(b)
        s.close()

    ts = [threading.Thread(target=fetch, args=(30000,)) for _ in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    out, _ = p.communicate(timeout=10)
    assert p.returncode == 0
    assert f"served=3 bytes=90000 mode={mode}" in out


def managed_cfg(server_args, client_count=3):
    clients = "\n".join(
        f"""  client{i}:
    network_node_id: 1
    processes:
      - path: pyapp:shadow_tpu.models.tgen:TGenClient
        args: ["100 kB", "1", serial, "8080", server]
        start_time: {1000 + 40 * i} ms
        expected_final_state: {{exited: 0}}"""
        for i in range(client_count))
    return f"""
general:
  stop_time: 30s
  seed: 13
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 1 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        edge [ source 0 target 1 latency "20 ms" ]
        edge [ source 0 target 0 latency "5 ms" ]
        edge [ source 1 target 1 latency "5 ms" ]
      ]
hosts:
  server:
    network_node_id: 0
    ip_addr: 11.0.0.1
    processes:
      - path: {BUILD}/mux_srv
        args: {server_args}
        expected_final_state: {{exited: 0}}
{clients}
"""


@pytest.mark.parametrize("mode", ["poll", "epoll"])
def test_mux_srv_managed_serves_concurrent_clients(mode):
    cfg = parse_config(yaml.safe_load(managed_cfg(f'["8080", "3", {mode}]')), {
        "general.data_directory": f"/tmp/st-mux-{mode}",
    })
    c = Controller(cfg, mirror_log=False)
    result = c.run()
    assert result["process_errors"] == [], result["process_errors"]
    out = Path(f"/tmp/st-mux-{mode}/hosts/server/mux_srv.0.stdout").read_text()
    assert f"served=3 bytes=300000 mode={mode}" in out, out
    # the three transfers overlapped in sim time (event-loop concurrency):
    # all clients started within 80 ms and the 50 Mbit downlink is shared,
    # so each took longer than it would alone
    clients = [p.app for p in c.processes[1:]]
    assert all(cl.completed == 1 for cl in clients)
    for h in c.hosts:
        assert h._conns == {}, h.name


def test_udp_echo_native_oracle():
    srv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    srv.bind(("127.0.0.1", 0))
    port = srv.getsockname()[1]

    def serve():
        for _ in range(4):
            data, addr = srv.recvfrom(1024)
            srv.sendto(data, addr)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    r = subprocess.run([str(BUILD / "udp_echo"), "127.0.0.1", str(port), "4"],
                       capture_output=True, text=True, timeout=30)
    srv.close()
    assert r.returncode == 0, r.stderr
    assert "ok count=4" in r.stdout


def test_udp_echo_managed():
    cfg_text = f"""
general:
  stop_time: 15s
  seed: 14
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 1 host_bandwidth_up "50 Mbit" host_bandwidth_down "50 Mbit" ]
        edge [ source 0 target 1 latency "25 ms" ]
        edge [ source 0 target 0 latency "5 ms" ]
        edge [ source 1 target 1 latency "5 ms" ]
      ]
hosts:
  server:
    network_node_id: 0
    ip_addr: 11.0.0.1
    processes:
      - path: pyapp:shadow_tpu.models.echo:EchoServer
        args: ["9000"]
  client:
    network_node_id: 1
    processes:
      - path: {BUILD}/udp_echo
        args: ["11.0.0.1", "9000", "4"]
        start_time: 1s
        expected_final_state: {{exited: 0}}
"""
    cfg = parse_config(yaml.safe_load(cfg_text), {
        "general.data_directory": "/tmp/st-udpecho",
    })
    c = Controller(cfg, mirror_log=False)
    result = c.run()
    assert result["process_errors"] == [], result["process_errors"]
    out = Path("/tmp/st-udpecho/hosts/client/udp_echo.0.stdout").read_text()
    assert "ok count=4" in out, out
    # RTT is SIMULATED: exactly 2 x 25 ms one-way latency
    for line in out.splitlines()[:4]:
        assert "rtt_ms=50" in line, line


def test_timer_tick_native_oracle():
    r = subprocess.run([str(BUILD / "timer_tick"), "5"], capture_output=True,
                       text=True, timeout=30)
    assert r.returncode == 0, r.stderr
    assert "done ticks=5 evt=7" in r.stdout


def test_timerfd_eventfd_managed_deterministic():
    cfg_text = f"""
general:
  stop_time: 8s
  seed: 15
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        edge [ source 0 target 0 latency "5 ms" ]
      ]
hosts:
  box:
    network_node_id: 0
    processes:
      - path: {BUILD}/timer_tick
        args: ["5"]
        start_time: 1s
        expected_final_state: {{exited: 0}}
"""
    outs = []
    for tag in ("t1", "t2"):
        cfg = parse_config(yaml.safe_load(cfg_text), {
            "general.data_directory": f"/tmp/st-timer-{tag}",
        })
        c = Controller(cfg, mirror_log=False)
        result = c.run()
        assert result["process_errors"] == [], result["process_errors"]
        outs.append(Path(f"/tmp/st-timer-{tag}/hosts/box/timer_tick.0.stdout"
                         ).read_text())
    # simulated periodic timer: ticks at exactly 100 ms steps, and the
    # virtual pid makes the whole output bit-deterministic across runs
    assert "tick 1 at 100 ms" in outs[0]
    assert "tick 5 at 500 ms" in outs[0]
    assert "done ticks=5 evt=7 pid=" in outs[0]
    assert outs[0] == outs[1]


def test_cpython_guest_fetches_http_in_sim():
    """An unmodified CPython interpreter as a managed guest: thousands of
    native startup syscalls pass through, then urllib's socket traffic
    rides the simulated network. getrandom interception makes even
    Python's hash randomization deterministic."""
    import sys

    cfg_text = f"""
general:
  stop_time: 30s
  seed: 21
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 1 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        edge [ source 0 target 1 latency "30 ms" ]
        edge [ source 0 target 0 latency "5 ms" ]
        edge [ source 1 target 1 latency "5 ms" ]
      ]
hosts:
  web:
    network_node_id: 0
    ip_addr: 11.0.0.1
    processes:
      - path: pyapp:shadow_tpu.models.httpd:HttpServer
        args: ["80", "250000"]
  client:
    network_node_id: 1
    processes:
      - path: {sys.executable}
        args: ["{ROOT}/native/tests/guest/http_fetch.py", "http://11.0.0.1:80/data", "250000"]
        start_time: 1s
        expected_final_state: {{exited: 0}}
"""
    outs = []
    for tag in ("p1", "p2"):
        cfg = parse_config(yaml.safe_load(cfg_text), {
            "general.data_directory": f"/tmp/st-pyguest-{tag}",
        })
        c = Controller(cfg, mirror_log=False)
        result = c.run()
        assert result["process_errors"] == [], result["process_errors"]
        outs.append(Path(f"/tmp/st-pyguest-{tag}/hosts/client/"
                         ).glob("*.stdout").__next__().read_text())
    assert "fetched 250000 bytes" in outs[0], outs[0]
    assert "status=200" in outs[0]
    # the reported elapsed time is simulated and bit-deterministic
    ms = int(outs[0].split(" in ")[1].split(" ms")[0])
    assert 150 <= ms <= 3000, ms
    assert outs[0] == outs[1]
