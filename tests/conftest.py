"""Test configuration: force an 8-virtual-device CPU JAX platform, and
build the native pieces once.

Tests run every device kernel on CPU-XLA (same integer ops as TPU-XLA) and
exercise the mesh data plane (shadow_tpu/parallel/mesh.py) on an 8-device
mesh — the stand-in for a pod recommended by SURVEY.md §4 ("multi-node
without a cluster"). A run reports the platform it ran on (the summary's
``device.platform``), so these CPU runs say ``cpu``; the chip is reached
only through ``chip_smoke.py``.

The C engine (_colcore) and the shim are built from the committed sources
before any test runs: a device policy with ``native_colcore`` on refuses
to run without the engine instead of falling back to the Python plane.
"""

import fcntl
import os
import pathlib
import subprocess

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

_NATIVE = pathlib.Path(__file__).resolve().parent.parent / "native"
(_NATIVE / "build").mkdir(exist_ok=True)
# xdist workers import this file concurrently: one builds, the rest find
# the tree up to date
with open(_NATIVE / "build" / ".lock", "w") as _lock:
    fcntl.flock(_lock, fcntl.LOCK_EX)
    subprocess.run(["make", "-C", str(_NATIVE)], check=True,
                   capture_output=True)
