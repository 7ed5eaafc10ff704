"""The virtual file surface (native/vfs.py; VERDICT r2 item #3).

Managed processes now see a per-host virtual filesystem: path syscalls
trap, the worker serves the host data dir + synthesized /etc files, and
everything else re-issues natively through the shim's gadget (the
RETRY_NATIVE sentinel). The reference's dual-run discipline applies: the
same unmodified binary + config file must behave identically against the
real kernel and inside the simulator.
"""

import socket
import subprocess
import threading
from pathlib import Path

import pytest
import yaml

from shadow_tpu.config import parse_config
from shadow_tpu.core.controller import Controller

ROOT = Path(__file__).resolve().parents[1]
BUILD = ROOT / "native" / "build"


def _serve_native(srv, count):
    for _ in range(count):
        conn, _a = srv.accept()
        req = b""
        while len(req) < 8:
            req += conn.recv(8 - len(req))
        n = int(req.decode())
        conn.sendall(b"x" * n)
        conn.close()


def test_ftool_native_oracle(tmp_path):
    """The file-configured transfer tool against the real kernel."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    port = srv.getsockname()[1]
    t = threading.Thread(target=_serve_native, args=(srv, 3), daemon=True)
    t.start()
    (tmp_path / "ftool.conf").write_text(f"127.0.0.1 {port} 40000 3\n")
    r = subprocess.run([str(BUILD / "ftool"), "ftool.conf"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=60)
    srv.close()
    assert r.returncode == 0, r.stderr
    assert "ftool-ok transfers=3" in r.stdout
    log = (tmp_path / "transfer.log").read_text()
    assert log == ("transfer 0 bytes=40000\ntransfer 1 bytes=40000\n"
                   "transfer 2 bytes=40000\ndone transfers=3 total=120000\n")


FTOOL_CFG = f"""
general:
  stop_time: 30s
  seed: 5
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 1 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        edge [ source 0 target 1 latency "20 ms" ]
      ]
hosts:
  server:
    network_node_id: 0
    ip_addr: 11.0.0.1
    processes:
      - path: {BUILD}/tgen_srv
        args: ["8080", "3"]
        expected_final_state: {{exited: 0}}
  client:
    network_node_id: 1
    processes:
      - path: {BUILD}/ftool
        args: ["ftool.conf"]
        start_time: 1s
        expected_final_state: {{exited: 0}}
"""


def test_ftool_managed_dual_run():
    """The SAME binary + config-file shape inside the simulator: the
    config file is read through the vfs (host data dir), the transfers
    ride the simulated network, and the transfer log comes out IDENTICAL
    to the native-oracle run's."""
    cfg = parse_config(yaml.safe_load(FTOOL_CFG), {
        "general.data_directory": "/tmp/vfs-ftool",
    })
    # place the guest's config file in its host data dir (its cwd)
    cdir = Path("/tmp/vfs-ftool/hosts/client")
    cdir.mkdir(parents=True, exist_ok=True)
    (cdir / "ftool.conf").write_text("11.0.0.1 8080 40000 3\n")
    c = Controller(cfg, mirror_log=False)
    result = c.run()
    assert result["process_errors"] == [], result["process_errors"]
    out = (cdir / "ftool.0.stdout").read_text()
    assert "ftool-ok transfers=3" in out, out
    log = (cdir / "transfer.log").read_text()
    assert log == ("transfer 0 bytes=40000\ntransfer 1 bytes=40000\n"
                   "transfer 2 bytes=40000\ndone transfers=3 total=120000\n")
    assert not (cdir / "transfer.log.tmp").exists()  # rename committed


ETC_CFG = f"""
general:
  stop_time: 10s
  seed: 7
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
  alpha:
    network_node_id: 0
    ip_addr: 11.0.0.1
    processes: []
  beta:
    network_node_id: 0
    ip_addr: 11.0.0.2
    processes:
      - path: /bin/cat
        args: ["/etc/hosts"]
        start_time: 1s
        expected_final_state: {{exited: 0}}
"""


def test_etc_hosts_synthesized():
    """An unmodified /bin/cat reads the SYNTHESIZED /etc/hosts: every
    simulated host name with its simulated IPv4."""
    cfg = parse_config(yaml.safe_load(ETC_CFG), {
        "general.data_directory": "/tmp/vfs-etc",
    })
    c = Controller(cfg, mirror_log=False)
    result = c.run()
    assert result["process_errors"] == [], result["process_errors"]
    out = Path("/tmp/vfs-etc/hosts/beta/cat.0.stdout").read_text()
    assert "11.0.0.1 alpha" in out, out
    assert "11.0.0.2 beta" in out, out
    assert "127.0.0.1 localhost" in out, out


PY_FILE_GUEST = ROOT / "native" / "tests" / "guest" / "py_files.py"


def test_python_file_io_dual_run(tmp_path):
    """CPython doing real file work — mkdir, create, append, rename,
    listdir, stat, readback — produces byte-identical output natively
    and under the simulator (the kernel as oracle, SURVEY.md §4)."""
    import sys

    native = subprocess.run([sys.executable, str(PY_FILE_GUEST)],
                            cwd=tmp_path, capture_output=True, text=True,
                            timeout=60)
    assert native.returncode == 0, native.stderr
    cfg_text = ETC_CFG.replace(
        "path: /bin/cat\n        args: [\"/etc/hosts\"]",
        f"path: {sys.executable}\n        args: [\"{PY_FILE_GUEST}\"]")
    cfg = parse_config(yaml.safe_load(cfg_text), {
        "general.data_directory": "/tmp/vfs-py",
    })
    c = Controller(cfg, mirror_log=False)
    result = c.run()
    assert result["process_errors"] == [], result["process_errors"]
    name = Path(sys.executable).name
    managed = Path(f"/tmp/vfs-py/hosts/beta/{name}.0.stdout").read_text()
    assert managed == native.stdout, (managed, native.stdout)


PY_MMAP_GUEST = ROOT / "native" / "tests" / "guest" / "py_mmap.py"
PY_PROC_GUEST = ROOT / "native" / "tests" / "guest" / "py_proc.py"


def test_python_mmap_dual_run(tmp_path):
    """mmap over virtualized files (VERDICT r3 item #4): read-only maps,
    shared writable maps landing in the backing file, and a synthesized
    file mapped via a memfd snapshot — byte-identical stdout natively and
    under the simulator."""
    import sys

    native = subprocess.run([sys.executable, str(PY_MMAP_GUEST)],
                            cwd=tmp_path, capture_output=True, text=True,
                            timeout=60)
    assert native.returncode == 0, native.stderr
    cfg_text = ETC_CFG.replace(
        "path: /bin/cat\n        args: [\"/etc/hosts\"]",
        f"path: {sys.executable}\n        args: [\"{PY_MMAP_GUEST}\"]")
    cfg = parse_config(yaml.safe_load(cfg_text), {
        "general.data_directory": "/tmp/vfs-mmap",
    })
    c = Controller(cfg, mirror_log=False)
    result = c.run()
    assert result["process_errors"] == [], result["process_errors"]
    import sys as _s
    name = Path(_s.executable).name
    managed = Path(f"/tmp/vfs-mmap/hosts/beta/{name}.0.stdout").read_text()
    assert managed == native.stdout, (managed, native.stdout)
    # the shared-writable map's stores really landed in the host tree
    back = Path("/tmp/vfs-mmap/hosts/beta/rw.bin").read_bytes()
    assert back[:5] == b"HELLO" and back[-5:] == b"WORLD"


def test_proc_virtual_identity():
    """The synthesized /proc presents the 1-CPU / 2-GB / sim-uptime
    virtual identity on ANY host (VERDICT r3 item #8)."""
    import sys

    cfg_text = ETC_CFG.replace(
        "path: /bin/cat\n        args: [\"/etc/hosts\"]",
        f"path: {sys.executable}\n        args: [\"{PY_PROC_GUEST}\"]")
    cfg = parse_config(yaml.safe_load(cfg_text), {
        "general.data_directory": "/tmp/vfs-proc",
    })
    c = Controller(cfg, mirror_log=False)
    result = c.run()
    assert result["process_errors"] == [], result["process_errors"]
    name = Path(sys.executable).name
    out = Path(f"/tmp/vfs-proc/hosts/beta/{name}.0.stdout").read_text()
    assert "ncpu 1" in out, out
    assert "Shadow Virtual CPU" in out, out
    assert "MemTotal:       2097152 kB" in out, out
    assert "stat_pid_is_getpid True" in out, out
    assert "uptime_is_sim True" in out, out
    assert "maps_has_stack_heap True" in out, out
    assert "cpu_count 1" in out, out


def test_native_passthrough_surfaced_by_default():
    """VERDICT r3 item #7: every run (no audit flag) surfaces the
    syscall numbers the worker re-issued natively, in the host log and
    the counters — and the list is twice-run stable."""
    def go(tag):
        cfg = parse_config(yaml.safe_load(ETC_CFG), {
            "general.data_directory": f"/tmp/vfs-npt-{tag}",
        })
        c = Controller(cfg, mirror_log=False)
        r = c.run()
        assert r["process_errors"] == [], r["process_errors"]
        assert r["counters"].get("native_passthrough_syscalls", 0) > 0
        log = Path(f"/tmp/vfs-npt-{tag}/hosts/beta/beta.log").read_text()
        lines = [ln for ln in log.splitlines()
                 if "native-passthrough syscalls" in ln]
        assert lines, log
        return lines

    assert go("a") == go("b")
