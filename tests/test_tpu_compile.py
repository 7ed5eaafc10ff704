"""TPU compiles of the main path's kernels, and the bring-up contract on CPU.

The compiles run the TPU compiler against a described v5e:2x2 — no chip
is attached, nothing runs — at the widths the simulation dispatches. The
topology is described in a module fixture (never at import: only one
process may load the TPU library, and xdist workers import every test
file), and the persistent compile cache is off around them (a compile for
a described chip cannot be read back without one).

The CPU tests pin the contract that a device policy reports the platform
it ran on and fails instead of falling back to a host twin.
"""

import json
import os
import subprocess
import sys
from functools import partial
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("p", [256, 65536])
def test_draw_kernel_compiles_for_v5e(one_chip, p):
    from shadow_tpu.ops.propagate import _draw_kernel

    compiled = _draw_kernel.lower(
        _spec((4, p), jnp.uint32, one_chip),
        _spec((2,), jnp.uint32, one_chip)).compile()
    assert compiled.as_text()


def test_min_draw_kernel_compiles_at_spec_bucket(one_chip):
    from shadow_tpu.ops.propagate import DeviceDrawPlane, _min_draw_kernel

    k = DeviceDrawPlane.SPEC_BUCKET
    assert k == 16384
    compiled = _min_draw_kernel.lower(
        _spec((3, k), jnp.uint32, one_chip),
        _spec((2,), jnp.uint32, one_chip)).compile()
    assert compiled.as_text()


def test_ack_advance_compiles_int64(one_chip):
    from shadow_tpu.ops import transport_kernels as TK

    with jax.enable_x64(True):
        cols = [_spec((65536,), jnp.int64, one_chip) for _ in range(9)]
        compiled = jax.jit(
            lambda *c: TK.ack_advance(*c, xp=jnp)).lower(*cols).compile()
        assert "s64" in compiled.as_text()


def test_exchange_rounds_compiles_on_four_chips(topo):
    from shadow_tpu.parallel.mesh import AXIS, _exchange_rounds

    n, m, w = 4, 1024, 4096
    mesh = Mesh(np.array(topo.devices[:n]), (AXIS,))
    shard = NamedSharding(mesh, P(AXIS))
    with jax.enable_x64(True):
        fn = jax.jit(jax.shard_map(
            partial(_exchange_rounds, n, 7, 10, w), mesh=mesh,
            in_specs=((P(AXIS),) * 6,), out_specs=(P(AXIS), P()),
            check_vma=False))
        units = tuple(_spec((n, m), jnp.int64, shard) for _ in range(6))
        compiled = fn.lower(units).compile()
    assert "all-to-all" in compiled.as_text()


# -- the bring-up contract, on CPU ------------------------------------------

@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set (nothing is set in code);
    otherwise the cache sits at <repo>/.jax_cache. Process-global config,
    so each case runs in its own interpreter."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run(
        [sys.executable, "-c",
         "from shadow_tpu.ops import jaxcfg; jaxcfg.configure(); "
         "import jax; print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd=str(ROOT), capture_output=True, text=True,
        timeout=120)
    assert r.returncode == 0, r.stderr
    want = str(tmp_path) if env_dir else str(ROOT / ".jax_cache")
    assert r.stdout.strip() == want


def _cfg(tmp_path, tag, **over):
    from shadow_tpu.config import load_config

    return load_config(str(ROOT / "examples" / "tgen_100host.yaml"), {
        "general.data_directory": str(tmp_path / tag),
        "general.stop_time": "2s",
        "experimental.scheduler_policy": "tpu_batch", **over})


def test_attach_failure_fails_the_run(tmp_path, monkeypatch, capsys):
    """A tpu_batch run whose background device attach raises exits
    non-zero with the error — it does not finish on the numpy twin."""
    from shadow_tpu import cli
    from shadow_tpu.ops.propagate import DeviceDrawPlane

    def broken(*_a, **_k):
        raise RuntimeError("injected attach failure")

    monkeypatch.setattr(DeviceDrawPlane, "attach_cached", broken)
    monkeypatch.setattr(DeviceDrawPlane, "_cache", {})
    rc = cli.main([str(ROOT / "examples" / "tgen_100host.yaml"),
                   "--scheduler-policy", "tpu_batch", "--quiet",
                   "--set", "general.stop_time=2s",
                   "--data-directory", str(tmp_path / "d")])
    assert rc == 1
    assert "injected attach failure" in capsys.readouterr().err


def test_summary_names_the_device(tmp_path):
    """The run summary carries the device identity the plane attached
    to (here CPU XLA: the tests force the CPU platform)."""
    from shadow_tpu.core.controller import Controller

    s = Controller(_cfg(tmp_path, "dev",
                        **{"experimental.tpu_device_floor": 1}),
                   mirror_log=False).run()
    dev = s["device"]
    assert dev["platform"] == jax.devices()[0].platform == "cpu"
    assert dev["kind"] == jax.devices()[0].device_kind
    assert dev["count"] == len(jax.devices())  # tpu_mesh_shards 0: all
    assert s["device_windows_dispatched"] > 0


def test_missing_colcore_is_an_error(tmp_path, monkeypatch):
    """native_colcore on (the default) without a built engine refuses to
    build the plane instead of switching to the Python plane."""
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.network.devroute import DataPlaneError

    import shadow_tpu.native as native

    monkeypatch.setitem(sys.modules, "shadow_tpu.native._colcore", None)
    monkeypatch.delattr(native, "_colcore", raising=False)
    with pytest.raises(DataPlaneError, match="make -C native"):
        Controller(_cfg(tmp_path, "noc",
                        **{"experimental.tpu_device_floor": -1}),
                   mirror_log=False)


def test_fleet_member_without_service_records_it(tmp_path):
    """A sweep without the shared draw service: members never attach the
    chip themselves — they run the twin, and each manifest says so."""
    from shadow_tpu import fleet

    r = subprocess.run(
        [sys.executable, "-m", "shadow_tpu.fleet", "sweep",
         str(ROOT / "examples" / "tgen_100host.yaml"), "--seeds", "1",
         "--jobs", "1", "--sweep-dir", str(tmp_path / "sw"),
         "--no-device-service", "--quiet", "--json",
         "--set", "experimental.scheduler_policy=tpu_batch",
         "--set", "general.stop_time=2s"],
        cwd=str(ROOT), capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    summary = json.loads(r.stdout)
    seed = summary["completed"][0]
    man = json.loads((fleet.seed_dir(tmp_path / "sw", int(seed))
                      / fleet.SEED_MANIFEST).read_text())
    assert man["draw_service"] == "off (--no-device-service)"
