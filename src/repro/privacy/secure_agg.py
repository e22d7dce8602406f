"""Mask-based secure aggregation (Bonawitz et al.-style pairwise masking).

Each ordered client pair (i, j) of a round shares a mask seed; client i adds
``+m_ij`` and client j adds ``-m_ij`` to its submission, so the masks cancel
*inside* the server's single fused N-way sum (``secure_coalesced_aggregate``)
when every round participant is present — the server only ever sees masked
individual updates, never an unmasked one.

Because Algorithm-2 weights are server-side sample ratios the clients cannot
know, the masked quantity is the *weighted delta*: client i submits

    y_i = s_i * privatized_delta_i + sum_j sign(i,j) * m_ij

and the drain computes ``base + (sum_i y_i) / (sum_i s_i)`` — a plain sum in
which the masks cancel, divided by publicly known sample counts.

Dropout recovery (the paper's dynamic-availability setting): masks are
derived from per-pair seeds w.r.t. the *expected* member set, so when a
client drops mid-round the survivors' stray masks no longer cancel.  The
dealer reconstructs exactly those stray masks from the pair seeds
(``reconstruct``) and the drain subtracts them inside the same fused sum.

This in-process ``PairwiseMasker`` plays the trusted dealer that real
deployments replace with pairwise Diffie-Hellman key agreement plus
Shamir-shared seed recovery; the masking/cancellation/recovery arithmetic —
the part that must compose with the coalesced drain — is the real thing.
Masks are f32 Gaussians (``mask_scale`` std); cancellation is exact up to
float summation order, and ``mask_scale=0`` degrades to the unmasked secure
path (the parity baseline used in tests).

Mask magnitude caveat: a pair mask must be derived identically on both
endpoints, so it cannot be scaled by a per-client weight without breaking
cancellation — and a fixed-std mask only hides the weighted delta if
``mask_scale`` is set commensurate with ``n_samples * dp_clip`` (the payload
magnitude, which is publicly computable from the round's metadata).  Real
deployments sidestep the issue entirely with uniform masks over a finite
field, where hiding is magnitude-independent; in this f32 simulation,
choose ``FedCCLConfig.secure_mask_scale`` accordingly (the default 1.0 is a
*correctness* setting for the cancellation arithmetic, not a calibrated
hiding guarantee).
"""

from __future__ import annotations

import zlib

import jax.numpy as jnp
import numpy as np

from repro.obs.record import current_telemetry, maybe_span
from repro.utils.tree import flatten_params, unflatten_params


def _pair_seed(master: int, a: str, b: str, round_id: int, model_key: str):
    """Deterministic seed sequence for the (a, b) pair's round mask; both
    sides derive the identical sequence (ids are sorted)."""
    lo, hi = sorted((a, b))
    return [master, zlib.crc32(lo.encode()), zlib.crc32(hi.encode()),
            round_id, zlib.crc32(model_key.encode())]


class PairwiseMasker:
    """Pairwise mask generator + dropout-recovery reconstructor."""

    def __init__(self, seed: int = 0, mask_scale: float = 1.0):
        self.seed = int(seed)
        self.mask_scale = float(mask_scale)

    def _pair_mask(self, a: str, b: str, round_id: int, model_key: str,
                   t: int) -> np.ndarray:
        rng = np.random.default_rng(
            _pair_seed(self.seed, a, b, round_id, model_key))
        return rng.standard_normal(t, dtype=np.float32) * \
            np.float32(self.mask_scale)

    def mask_flat(self, client_id: str, participants, round_id: int,
                  model_key: str, t: int) -> np.ndarray:
        """Sum of this client's signed pairwise masks w.r.t. ``participants``
        (the round's expected member set, dropouts included)."""
        total = np.zeros(t, np.float32)
        if self.mask_scale == 0.0:
            return total
        for other in participants:
            if other == client_id:
                continue
            sign = 1.0 if client_id < other else -1.0
            total += sign * self._pair_mask(client_id, other, round_id,
                                            model_key, t)
        return total

    def mask_delta_flat(self, delta_flat, client_id: str, participants,
                        round_id: int, model_key: str, weight: float):
        """Client-side masking in the flat domain:
        ``weight * delta + signed masks``; a ``mask`` span, with the mask's
        upload counted in ``h2d_bytes`` and ``h2d_transfers``, where a
        runtime put telemetry in scope."""
        tel = current_telemetry()
        with maybe_span(tel, "mask",
                        args={"client": client_id, "key": model_key}):
            mask = self.mask_flat(client_id, participants, round_id,
                                  model_key, delta_flat.shape[0])
            if tel is not None:
                tel.metrics.counter("h2d_bytes").inc(mask.nbytes)
                tel.metrics.counter("h2d_transfers").inc()
            return delta_flat * jnp.float32(weight) + jnp.asarray(mask)

    def mask_update(self, base_params, new_params, client_id: str,
                    participants, round_id: int, model_key: str,
                    weight: float):
        """Pytree convenience over ``mask_delta_flat``: masks
        ``weight * (new - base)``, returned shaped like ``base_params``."""
        delta = flatten_params(new_params) - flatten_params(base_params)
        return unflatten_params(
            self.mask_delta_flat(delta, client_id, participants, round_id,
                                 model_key, weight), base_params)

    def reconstruct_flat(self, t: int, missing_ids, survivor_ids,
                         round_id: int, model_key: str) -> np.ndarray:
        """Flat-domain seed-reconstruction recovery: the sum of every stray
        mask the survivors included w.r.t. the dropped clients.  The drain
        subtracts it inside the same fused sum to restore exact cancellation.
        Per-shard drains call this independently per model — mask seeds are
        keyed by ``(pair, round, model_key)`` so one shard's recovery can
        never touch another shard's round."""
        total = np.zeros(t, np.float32)
        if self.mask_scale != 0.0:
            for dropped in missing_ids:
                for survivor in survivor_ids:
                    sign = 1.0 if survivor < dropped else -1.0
                    total += sign * self._pair_mask(survivor, dropped,
                                                    round_id, model_key, t)
        return total

    def reconstruct(self, template_params, missing_ids, survivor_ids,
                    round_id: int, model_key: str):
        """Pytree convenience over ``reconstruct_flat``, shaped like
        ``template_params``; a ``reconstruct`` span, with the upload counted
        in ``h2d_bytes`` and ``h2d_transfers``, where a runtime put
        telemetry in scope."""
        tel = current_telemetry()
        with maybe_span(tel, "reconstruct",
                        args={"key": model_key, "missing": len(missing_ids)}):
            t = flatten_params(template_params).shape[0]
            total = self.reconstruct_flat(t, missing_ids, survivor_ids,
                                          round_id, model_key)
            if tel is not None:
                tel.metrics.counter("h2d_bytes").inc(total.nbytes)
                tel.metrics.counter("h2d_transfers").inc()
            return unflatten_params(jnp.asarray(total), template_params)
