"""Central JAX runtime configuration for shadow_tpu kernels.

Import-and-call before any kernel dispatch. Enables the persistent
compilation cache so the first-compile cost of each padded batch shape is
paid once per machine, not once per process — a simulation binary is a
short-lived CLI, unlike a training job.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads it itself; nothing is set here), otherwise the fixed path
``<repo>/.jax_cache/`` (gitignored). A fixed path matters because the
directory is part of the cache key: a cache that moves never hits.
"""

from __future__ import annotations

import os

#: default persistent compile cache: inside the checkout, beside the
#: ``shadow_tpu`` package
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_done = False


def configure() -> None:
    global _done
    if _done:
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        try:
            os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        except OSError as exc:
            raise RuntimeError(
                f"cannot create the JAX compile cache at "
                f"{DEFAULT_CACHE_DIR}: {exc} (set JAX_COMPILATION_CACHE_DIR "
                f"to a writable directory)") from exc
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # cache even fast compiles: a simulation CLI pays per-process compile
    # cost on every invocation, and the window kernels compile in ~0.1-0.3
    # s each — below JAX's default threshold, so they were rebuilt every
    # process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    _done = True
