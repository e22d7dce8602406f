"""Main-path kernels and the solar train step compile for a TPU v5e chip.

Nothing runs: each program is lowered with ``interpret=False`` and compiled
by the TPU compiler for one chip of a described ``v5e:2x2`` topology, which
refuses what the Pallas interpreter accepts (scalars in VMEM, blocks that
outgrow scoped VMEM, programs that do not fit the chip).  The topology is
described inside a fixture, so only the process that runs this file loads
the TPU compiler; every test here skips where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.solar_lstm import SolarLSTMConfig
from repro.kernels.dp_clip_noise.dp_clip_noise import dp_clip_noise_tiled
from repro.kernels.ewc_update.ewc_update import ewc_tiled
from repro.kernels.fedavg_agg.fedavg_agg import TILE, agg_tiled
from repro.kernels.lstm_cell.lstm_cell import BATCH_TILE, lstm_step_tiled
from repro.models.lstm import SolarForecaster
from repro.training.fed_solar import make_solar_fns

# a 141,953-float solar model pads to 18 kernel tiles
SOLAR_TILES = 18


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims)`` -> an f32 argument placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n", [3, 32, 100, 256])
def test_agg_tiled_compiles(shape, n):
    _compile_kernel(lambda x, w: agg_tiled(x, w, interpret=False),
                    shape((n, SOLAR_TILES * TILE)), shape((n,)))


@pytest.mark.parametrize("tiles", [1, SOLAR_TILES])
def test_dp_clip_noise_tiled_compiles(shape, tiles):
    t = tiles * TILE
    _compile_kernel(
        lambda d, z: dp_clip_noise_tiled(d, z, 1.0, 1.1, interpret=False),
        shape((t,)), shape((t,)))


@pytest.mark.parametrize("tiles", [1, SOLAR_TILES])
def test_ewc_tiled_compiles(shape, tiles):
    t = tiles * TILE
    _compile_kernel(
        lambda lam, g, p, a, f: ewc_tiled(lam, g, p, a, f, interpret=False),
        shape(()), shape((t,)), shape((t,)), shape((t,)), shape((t,)))


def test_lstm_step_tiled_compiles(shape):
    b, i, h = BATCH_TILE, SolarLSTMConfig().history_channels, 128
    _compile_kernel(
        lambda x, hh, c, wx, wh, bias: lstm_step_tiled(x, hh, c, wx, wh, bias,
                                                       interpret=False),
        shape((b, i)), shape((b, h)), shape((b, h)), shape((i, 4 * h)),
        shape((h, 4 * h)), shape((1, 4 * h)))


def test_solar_sgd_step_compiles_at_full_width(shape):
    cfg = SolarLSTMConfig(hidden_size=128)
    forecaster = SolarForecaster(cfg)
    sgd_step, _ = make_solar_fns(forecaster)
    params = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                          jax.eval_shape(forecaster.init, jax.random.key(0)))
    b = 8
    batch = {"history": shape((b, cfg.history_steps, cfg.history_channels)),
             "forecast": shape((b, cfg.horizon_steps, cfg.forecast_channels)),
             "target": shape((b, cfg.horizon_steps))}
    compiled = sgd_step.lower(params, batch, params, shape(())).compile()
    n_params = sum(a.size for a in jax.tree.leaves(params))
    assert n_params == 141_953
    # the step must fit one 16 GB chip with room for the federation's models
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_held_expert_layer_and_causal_attention_compile_at_published_widths(shape):
    """Kanana-2's MoE layer over 8 held of 128 experts (the grouped
    matmuls lower to the chip's ragged-dot kernel) and its 32-head causal
    attention at 8,192 tokens, forward and backward, in bfloat16."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.attention import causal_flash_attention
    from repro.models.moe import moe_forward, moe_schema
    from repro.sharding.logical import schema_shapes

    cfg = get_config("kanana2-30b-a3b")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, n_held=8))
    bf16 = jnp.bfloat16
    params = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                          schema_shapes(moe_schema(cfg), bf16))
    x = shape((1, 8192, cfg.d_model), bf16)

    def moe_loss(p, x):
        y, aux = moe_forward(cfg, p, x)
        return jnp.sum(y.astype(jnp.float32)), aux["load"]

    compiled = jax.jit(jax.grad(moe_loss, has_aux=True)).lower(
        params, x).compile()
    assert "tpu_custom_call" in compiled.as_text()

    q = shape((1, 8192, 32, 192), bf16)
    v = shape((1, 8192, 32, 128), bf16)

    def attn_loss(q, k, v):
        return jnp.sum(causal_flash_attention(q, k, v, 192 ** -0.5)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(attn_loss, (0, 1, 2))).lower(q, q, v).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
