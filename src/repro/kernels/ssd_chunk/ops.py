"""Wrapper: full SSD scan with the fused intra-chunk Pallas kernel.

Same signature/semantics as ``repro.models.ssm.ssd_chunked``:
  x: (b, l, h, p), dt: (b, l, h), A: (h,), B/C: (b, l, g, n)
  -> (y (b, l, h, p), final_state (b, h, p, n))

Pipeline: pad+chunk -> kernel (y_diag + per-chunk states) -> jax scan for
the inter-chunk recurrence -> small jnp einsum for the off-diagonal term.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssd_chunk.ssd_chunk import ssd_intra_chunk


def ssd_chunked_pallas(x, dt, A, B, C, chunk: int, init_state=None, *,
                       interpret=None):
    interpret = interpret_mode(interpret)
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    L = l + pad
    c = L // chunk
    rep = h // g

    xc = x.reshape(b, c, chunk, h, p).astype(jnp.float32)
    dtc = dt.reshape(b, c, chunk, h).astype(jnp.float32)
    Bh = jnp.repeat(B.reshape(b, c, chunk, g, n), rep, axis=3).astype(jnp.float32)
    Ch = jnp.repeat(C.reshape(b, c, chunk, g, n), rep, axis=3).astype(jnp.float32)
    xdt = xc * dtc[..., None]
    dA = dtc * A[None, None, None, :]

    y_diag, states = ssd_intra_chunk(xdt, dA, Bh, Ch, interpret=interpret)
    # states from kernel: (b, c, h, n, p) -> (b, c, h, p, n)
    states = states.transpose(0, 1, 2, 4, 3)

    # inter-chunk recurrence (sequential over c)
    dA_cum = jnp.cumsum(dA.transpose(0, 3, 1, 2), -1)      # (b,h,c,l)
    chunk_decay = jnp.exp(dA_cum[..., -1])                 # (b,h,c)

    def step(carry, inp):
        st, dec = inp
        new = carry * dec[..., None, None] + st
        return new, carry

    s0 = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))
    final, prev_states = jax.lax.scan(
        step, s0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(2, 0, 1)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)     # (b,c,h,p,n)

    # off-diagonal output: prior state flowing into each chunk position
    state_decay_out = jnp.exp(dA_cum)                      # (b,h,c,l)
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Ch, prev_states,
                       state_decay_out)

    y = (y_diag + y_off).reshape(b, L, h, p)
    return y[:, :l].astype(x.dtype), final.astype(x.dtype)
