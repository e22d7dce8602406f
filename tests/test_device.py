"""Process-level device setup (``repro.launch.device``).

The parent of a process-sharded or TCP federation owns the chip; the
workers it starts fold on the host CPU backend (docs/ARCHITECTURE.md,
"Who owns the device").  Each child here inherits a parent environment
that asks for the TPU, and must still answer ``ping`` from the CPU
backend.  The compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to one fixed directory in the checkout.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.checkpoint.msgpack_ckpt import packb
from repro.checkpoint.msgpack_ckpt import unpackb_np as unpackb
from repro.core.aggregation import AggregationConfig
from repro.core.server_proc import ProcessWorkerHandle, make_seed_blob
from repro.core.transport import LoopbackShardServers, TcpWorkerHandle
from repro.launch.device import CACHE_DIR


@pytest.mark.parametrize("flavor", ["process", "tcp"])
def test_worker_backend_is_cpu(flavor, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    blob = make_seed_blob([], 4, AggregationConfig(), None)
    servers = LoopbackShardServers(1) if flavor == "tcp" else None
    try:
        handle = (TcpWorkerHandle(0, blob, servers.hosts[0])
                  if servers is not None else ProcessWorkerHandle(0, blob))
        try:
            reply = unpackb(handle.rpc(packb(["ping"]), timeout=120.0))
        finally:
            handle.stop(10.0)
    finally:
        if servers is not None:
            servers.close()
    assert reply[:3] == ["pong", 0, []]
    assert reply[3] == "cpu"


_CACHE_PROBE = """
import json, os, jax, jax.numpy as jnp
from repro.launch.device import use_compile_cache
where = use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()
print(json.dumps({"where": where,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(from_env, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if from_env else str(CACHE_DIR)
    assert got == {"where": want, "config": want}
    if from_env:
        assert any(tmp_path.iterdir()), "nothing was cached in the env dir"
