"""Client-side DP update privatization (clip-by-global-norm + Gaussian noise).

The client never ships its trained parameters directly: the update delta
``new_params - fetched_params`` is clipped to L2 norm ``clip`` and perturbed
with noise of std ``noise_multiplier * clip`` (the Abadi et al. DP-SGD
recipe, applied at update granularity as in DP-FedAvg).  The privatized
parameters the server sees are ``fetched_params + privatized_delta`` — the
rest of the aggregation pipeline is unchanged.

Two arithmetic routes, validated against each other in tests:
  * ``use_pallas=True``  — the fused ``repro.kernels.dp_clip_noise`` kernel
    (two streaming passes over the flat delta);
  * ``use_pallas=False`` — the pure-jnp oracle.

Noise is drawn from a per-client jax PRNG key folded with a step counter, so
runs are deterministic given ``FedCCLConfig.seed``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.obs.record import current_telemetry, maybe_span
from repro.utils.tree import flatten_params, unflatten_params


@dataclass(frozen=True)
class DPConfig:
    clip: float                      # L2 sensitivity of one update delta
    noise_multiplier: float = 1.0    # noise std = noise_multiplier * clip
    use_pallas: bool = False


class DPPrivatizer:
    """Per-client privatization hook plugged into ``Client.train_update``."""

    def __init__(self, cfg: DPConfig, client_id: str, seed: int = 0,
                 accountant=None):
        if cfg.clip <= 0:
            raise ValueError(f"dp clip must be positive, got {cfg.clip}")
        self.cfg = cfg
        self.client_id = client_id
        self.accountant = accountant
        self._base_key = jax.random.key(seed)
        self._step = 0

    def privatize_delta(self, delta_flat, model_key: str = "__global__"):
        """Clip + noise one flat update delta and record the release with
        the accountant.  The flat form is the secure-aggregation fast path:
        masking happens in the same flat domain, so no pytree round trip.
        A ``dp.release`` span where a runtime put telemetry in scope."""
        with maybe_span(current_telemetry(), "dp.release",
                        args={"client": self.client_id, "key": model_key}):
            key = jax.random.fold_in(self._base_key, self._step)
            self._step += 1
            noise = jax.random.normal(key, delta_flat.shape, jnp.float32)
            if self.cfg.use_pallas:
                from repro.kernels.dp_clip_noise.ops import privatize_flat

                priv = privatize_flat(delta_flat, noise, self.cfg.clip,
                                      self.cfg.noise_multiplier)
            else:
                from repro.kernels.dp_clip_noise.ref import dp_clip_noise_ref

                priv = dp_clip_noise_ref(delta_flat, noise, self.cfg.clip,
                                         self.cfg.noise_multiplier)
            if self.accountant is not None:
                self.accountant.record(self.client_id, model_key,
                                       self.cfg.noise_multiplier)
        return priv

    def privatize(self, fetched_params, new_params, model_key: str = "__global__"):
        """Returns ``fetched_params + clip_noise(new_params - fetched_params)``
        and records the release with the accountant."""
        fetched_flat = flatten_params(fetched_params)
        delta = flatten_params(new_params) - fetched_flat
        priv = self.privatize_delta(delta, model_key)
        return unflatten_params(fetched_flat + priv, fetched_params)
