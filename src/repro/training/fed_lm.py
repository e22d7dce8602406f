"""Cross-silo fine-tuning of a language model through FedCCL.

Each silo (one organisation) holds a corpus of token sequences, packed to
the model's training length.  ``make_lm_step`` builds the jitted SGD step
(next-token cross-entropy, the MoE load-balance loss, the continual-learning
anchor of ``core/continual.py``), and ``make_train_fn`` adapts it into the
``train_fn`` the ``FedCCL`` facade is given, built as
``fed_solar.make_train_fn`` is: a training uploads its sequences once and
then only dispatches steps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.record import current_telemetry, maybe_span
from repro.training.losses import softmax_cross_entropy

#: the deferred counter family of the held experts' routed assignments
LOAD_COUNTER = "moe_assignments_held"
#: tokens whose logits one block of the loss makes
LOSS_BLOCK = 2048


def make_lm_step(model, *, lr: float):
    """The step ``step(params, tokens, i, anchor, lam, load, owned=False)
    -> (params, loss, load)``, one jitted program ``lm_sgd_step``.

    ``tokens`` is a training's (steps, seq_len + 1) block on the device and
    ``i`` the row this step trains on: inputs are its first ``seq_len``
    tokens, labels the next token at each.  The loss is the mean
    cross-entropy plus the MoE load-balance loss plus ``lam / 2 * sum (p -
    anchor)^2``.  Plain SGD: each parameter is updated in float32 and
    stored back in its own dtype.  ``load`` is the running count of
    (token, k) assignments routed to each held expert, added to here on the
    device (a model without routed experts passes it through).  With
    ``owned=True`` the caller gives up ``params`` (a training's own
    intermediate model) and the step writes the new one in its place, so a
    step after the first holds one model less.  Both take the program's
    name."""

    def lm_sgd_step(params, tokens, i, anchor, lam, load):
        seq = jax.lax.dynamic_index_in_dim(tokens, i, keepdims=True)

        def loss_fn(p):
            # the logits are made and reduced a block of tokens at a time,
            # recomputed in the backward pass: never all at once in float32
            _, aux = model.forward(p, tokens=seq[:, :-1])
            h, labels = aux["hidden"], seq[:, 1:]
            n = h.shape[1]
            blk = min(LOSS_BLOCK, n)

            @jax.checkpoint
            def block_ce(hb, lb):
                return softmax_cross_entropy(model.head(p, hb), lb) * hb.shape[1]

            ce = sum(block_ce(h[:, a:a + blk], labels[:, a:a + blk])
                     for a in range(0, n, blk)) / n
            reg = sum(jnp.sum(jnp.square(a.astype(jnp.float32)
                                         - b.astype(jnp.float32)))
                      for a, b in zip(jax.tree.leaves(p),
                                      jax.tree.leaves(anchor), strict=True))
            return ce + aux["moe_loss"] + 0.5 * lam * reg, aux.get("moe_load")

        (loss, moe_load), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        new = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return new, loss, load if moe_load is None else load + moe_load

    fresh = jax.jit(lm_sgd_step)
    owned_step = jax.jit(lm_sgd_step, donate_argnums=0)

    def step(params, tokens, i, anchor, lam, load, owned=False):
        return (owned_step if owned else fresh)(params, tokens, i, anchor,
                                                lam, load)

    return step


def make_train_fn(lm_step, *, steps: int, n_load: int = 0,
                  first_expert: int = 0):
    """Adapts the jitted step into the FedCCL protocol's train_fn.

    A silo's dataset is its (n, seq_len + 1) int32 array of packed
    sequences.  A training draws ``steps`` of them without replacement from
    the client's generator, uploads them in one transfer and runs one step
    on each, with the fetched model as the anchor (a local training with no
    anchor yet anchors to its own start with weight 0: one program either
    way).  It returns the tokens trained as the samples learned.

    Where a runtime put telemetry in scope, the upload is a ``train.stage``
    span and each step's dispatch a ``train.step`` span (profiler and the
    ``train_stage_host_ns`` / ``train_step_host_ns`` histograms); the
    ``tokens_trained``, ``h2d_bytes`` and ``h2d_transfers`` counters count
    the tokens, the bytes uploaded and the uploads.  The ``n_load`` held
    experts' assignments are summed on the device by the steps and read
    once, when the telemetry is dumped: deferred counters
    ``moe_assignments_held.<expert>``, experts numbered from
    ``first_expert``."""
    loads: dict = {}            # telemetry sink -> [device counts]
    zeros = jnp.zeros((n_load,), jnp.int32)
    rows_of = [jnp.int32(i) for i in range(steps)]

    def train_fn(params, dataset, rng: np.random.Generator, anchor):
        tel = current_telemetry()
        anchor_params = params if anchor is None else anchor.anchor
        lam = jnp.float32(0.0 if anchor is None else anchor.lam)
        with maybe_span(tel, "train.stage", ring=False,
                        hist="train_stage_host_ns"):
            rows = rng.choice(len(dataset), size=steps, replace=False)
            host = np.ascontiguousarray(dataset[np.sort(rows)])
            if tel is not None:
                tel.metrics.counter("h2d_bytes").inc(host.nbytes)
                tel.metrics.counter("h2d_transfers").inc(1)
            tokens = jnp.asarray(host)
        box = _load_box(loads, tel, zeros, first_expert)
        load = box[0]
        for i in range(steps):
            with maybe_span(tel, "train.step", ring=False,
                            hist="train_step_host_ns"):
                params, _, load = lm_step(params, tokens, rows_of[i],
                                          anchor_params, lam, load,
                                          owned=i > 0)
        n_tokens = steps * (host.shape[1] - 1)
        if tel is not None:
            box[0] = load
            tel.metrics.counter("tokens_trained").inc(n_tokens)
        return params, n_tokens, 1

    return train_fn


def _load_box(loads: dict, tel, zeros, first_expert: int) -> list:
    """The running assignment counts kept for one telemetry sink, its
    deferred counters registered on first use; with telemetry off a
    throwaway box of zeros."""
    if tel is None:
        return [zeros]
    box = loads.get(id(tel))
    if box is None:
        box = loads[id(tel)] = [zeros]
        tel.metrics.defer(LOAD_COUNTER, lambda: {
            f"{LOAD_COUNTER}.{first_expert + j}": int(c)
            for j, c in enumerate(np.asarray(box[0]))})
    return box
