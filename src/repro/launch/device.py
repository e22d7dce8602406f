"""Process-level device setup: who owns the accelerator, and where compiled
programs are cached.

One process per host owns the chip: the parent of a federation.  The shard
workers it starts (``ProcessWorkerHandle`` children, loopback
``repro.launch.shard_server`` processes) fold on the host CPU backend, so
they never contend for the chip the parent holds (docs/ARCHITECTURE.md,
"Who owns the device").

Importing this module does not import JAX: a child pins its backend here
before JAX reads its configuration.
"""

from __future__ import annotations

import os
import pathlib
import sys

#: the persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset — a fixed path inside the checkout, because the directory is part
#: of what a later run must find again
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    nothing is set here.  Otherwise the cache goes to ``CACHE_DIR``.  Call
    before the first compile."""
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def host_only_env() -> dict:
    """A copy of this process's environment for a child that must stay off
    the accelerator."""
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


def host_worker_main(*args) -> None:
    """Entry point of a spawned shard worker: hold JAX to the CPU backend,
    then run ``repro.core.server_proc.worker_main``.  A ``spawn`` child
    re-imports the parent's main script, which may import JAX before this
    runs, so the config is set as well as the environment; both take
    effect because no array operation has run yet."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")
    from repro.core.server_proc import worker_main

    worker_main(*args)
