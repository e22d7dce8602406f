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

Masks are drawn on the device: one jitted program (``_pair_masks``) takes a
fixed block of ``PAIRS_PER_CALL`` pairs' seed words as traced data, derives
each pair's threefry key from them, and adds the pairs' signed normals one
at a time into one flat accumulator, so its memory is O(T) and its compiled
shape depends on the tree's width alone — never on how many members a round
has or how many dropped.  Both endpoints and the dealer derive the identical
key, so client masks and the dealer's recovery come from the same program.

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

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.record import current_telemetry, maybe_span
from repro.utils.tree import flatten_params, unflatten_params

#: Pairs one dispatch of the mask program draws.  A call's rows past its
#: pairs carry sign 0 and are skipped, so every mask and recovery of a
#: round of up to 32 members (at most 16 x 16 survivor x dropped pairs)
#: takes one dispatch and one upload; larger sets take one more per block.
PAIRS_PER_CALL = 256
#: Words of one pair's row: the master seed's low and high halves, the
#: lower and higher id's crc32, the round's low and high halves, the model
#: key's crc32, and the pair's sign (+1, -1, or 0 for padding) as int32 bits.
_ROW_WORDS = 8
_U32 = 0xFFFFFFFF


def _pair_row(master: int, a: str, b: str, round_id: int, model_key: str,
              sign: int) -> tuple:
    """The (a, b) pair's seed row for its round mask: both endpoints and
    the dealer derive the identical words (ids are sorted)."""
    lo, hi = sorted((a, b))
    return (master & _U32, (master >> 32) & _U32, zlib.crc32(lo.encode()),
            zlib.crc32(hi.encode()), round_id & _U32, (round_id >> 32) & _U32,
            zlib.crc32(model_key.encode()), sign & _U32)


@functools.partial(jax.jit, static_argnames=("t", "scale"))
def _pair_masks(rows, t: int, scale: float):
    """``sum_p sign_p * scale * N(0, 1)^t`` over the rows' pairs, each
    pair's normals drawn from the threefry key its seed words fold into."""

    def pair_key(row):
        key = jax.random.key(0)
        for w in range(_ROW_WORDS - 1):
            key = jax.random.fold_in(key, row[w])
        return key

    keys = jax.vmap(pair_key)(rows)
    signs = jax.lax.bitcast_convert_type(rows[:, -1], jnp.int32)
    coef = signs.astype(jnp.float32) * jnp.float32(scale)

    def add_pair(i, acc):
        return acc + coef[i] * jax.random.normal(keys[i], (t,), jnp.float32)

    # pairs fill the rows first: the loop stops at the padding
    return jax.lax.fori_loop(0, jnp.sum(signs != 0), add_pair,
                             jnp.zeros(t, jnp.float32))


class PairwiseMasker:
    """Pairwise mask generator + dropout-recovery reconstructor."""

    def __init__(self, seed: int = 0, mask_scale: float = 1.0):
        self.seed = int(seed)
        self.mask_scale = float(mask_scale)

    def _draw(self, pairs, round_id: int, model_key: str, t: int):
        """``sum sign * m_ab`` over ``pairs`` of ``(a, b, sign)`` on the
        device, ``PAIRS_PER_CALL`` pairs a dispatch; each block of seed rows
        counted in ``h2d_bytes`` and ``h2d_transfers`` and the pairs in
        ``mask_pairs``, where a runtime put telemetry in scope."""
        if self.mask_scale == 0.0:
            return jnp.zeros(t, jnp.float32)
        tel = current_telemetry()
        total = None
        for i in range(0, max(len(pairs), 1), PAIRS_PER_CALL):
            block = pairs[i:i + PAIRS_PER_CALL]
            rows = np.zeros((PAIRS_PER_CALL, _ROW_WORDS), np.uint32)
            if block:
                rows[:len(block)] = [
                    _pair_row(self.seed, a, b, round_id, model_key, sign)
                    for a, b, sign in block]
            part = _pair_masks(rows, t=t, scale=self.mask_scale)
            total = part if total is None else total + part
            if tel is not None:
                tel.metrics.counter("h2d_bytes").inc(rows.nbytes)
                tel.metrics.counter("h2d_transfers").inc()
        if tel is not None:
            tel.metrics.counter("mask_pairs").inc(len(pairs))
        return total

    def mask_flat(self, client_id: str, participants, round_id: int,
                  model_key: str, t: int):
        """Sum of this client's signed pairwise masks w.r.t. ``participants``
        (the round's expected member set, dropouts included), on the
        device."""
        pairs = [(client_id, other, 1 if client_id < other else -1)
                 for other in participants if other != client_id]
        return self._draw(pairs, round_id, model_key, t)

    def mask_delta_flat(self, delta_flat, client_id: str, participants,
                        round_id: int, model_key: str, weight: float):
        """Client-side masking in the flat domain:
        ``weight * delta + signed masks``; a ``mask`` span where a runtime
        put telemetry in scope."""
        with maybe_span(current_telemetry(), "mask",
                        args={"client": client_id, "key": model_key}):
            scaled = delta_flat * jnp.float32(weight)
            if self.mask_scale == 0.0:
                return scaled
            return scaled + self.mask_flat(client_id, participants, round_id,
                                           model_key, delta_flat.shape[0])

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
                         round_id: int, model_key: str):
        """Flat-domain seed-reconstruction recovery, on the device: the sum
        of every stray mask the survivors included w.r.t. the dropped
        clients.  The drain subtracts it inside the same fused sum to
        restore exact cancellation.  Per-shard drains call this
        independently per model — mask seeds are keyed by
        ``(pair, round, model_key)`` so one shard's recovery can never touch
        another shard's round."""
        pairs = [(survivor, dropped, 1 if survivor < dropped else -1)
                 for dropped in missing_ids for survivor in survivor_ids]
        return self._draw(pairs, round_id, model_key, t)

    def reconstruct(self, template_params, missing_ids, survivor_ids,
                    round_id: int, model_key: str):
        """Pytree convenience over ``reconstruct_flat``, shaped like
        ``template_params``; a ``reconstruct`` span where a runtime put
        telemetry in scope."""
        with maybe_span(current_telemetry(), "reconstruct",
                        args={"key": model_key, "missing": len(missing_ids)}):
            t = sum(int(np.prod(np.shape(x)))
                    for x in jax.tree.leaves(template_params))
            total = self.reconstruct_flat(t, missing_ids, survivor_ids,
                                          round_id, model_key)
            return unflatten_params(total, template_params)
