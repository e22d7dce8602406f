"""Plain references for what the timed path produces.

Written from the model's and the protocol's descriptions, in
straightforward ``jax.numpy`` and NumPy, with no kernels, no batching of
folds and nothing imported from the program:

* the forecaster (paper §III, sized by ``configs/*.json``): an LSTM
  encoder over 672 history steps, its final state seeding an LSTM decoder
  over the 96 forecast steps, a linear head and a sigmoid.  Gates are
  ordered i, f, g, o in one fused weight; the forget gate carries a +1
  bias and the output a -2.5 offset, as the repository's model states;
* a client's training call: mini-batch SGD on the MSE loss plus the
  continual-learning anchor ``lam / 2 * sum (p - anchor)^2`` (paper
  §II.E), batches drawn as ``fleet.batch_iter`` draws them;
* Algorithm 2's fold, one update at a time in arrival order, in float64;
* the secure round's fold, ``base + sum_i s_i * priv_i / sum_i s_i``,
  with no masks in it at all;
* DBSCAN (Ester et al. 1996) over the sites' static features.

``precision="highest"`` is the reference: float32 with every matrix
product at full precision.  ``precision="bfloat16"`` is the control: the
same arithmetic carried out in bfloat16, the step below the float32 that
the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from fleet import batch_iter

def _dtype(precision: str):
    return jnp.bfloat16 if precision == "bfloat16" else jnp.float32


def _dot_precision(precision: str):
    return (jax.lax.Precision.HIGHEST if precision == "highest"
            else jax.lax.Precision.DEFAULT)


def init_params(key, model: dict) -> dict:
    """Seeded float32 weights in the forecaster's layout: one fused
    ``wx``/``wh``/``b`` per LSTM, a linear head.  Weights are normal with
    std 1/sqrt(fan_in); biases start at zero."""
    h = model["hidden_size"]
    shapes = {
        "encoder": {"wx": (model["history_channels"], 4 * h),
                    "wh": (h, 4 * h), "b": (4 * h,)},
        "decoder": {"wx": (model["forecast_channels"], 4 * h),
                    "wh": (h, 4 * h), "b": (4 * h,)},
        "head_w": (h, 1), "head_b": (1,),
    }
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    out = [jnp.zeros(s, jnp.float32) if len(s) == 1 else
           jax.random.normal(k, s, jnp.float32) / np.sqrt(s[0])
           for k, s in zip(keys, leaves, strict=True)]
    return jax.tree.unflatten(treedef, out)


def _lstm(p, xs, h, c, prec):
    def step(carry, x):
        h, c = carry
        gates = (jnp.dot(x, p["wx"], precision=prec)
                 + jnp.dot(h, p["wh"], precision=prec) + p["b"])
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    (h, c), ys = jax.lax.scan(step, (h, c), jnp.swapaxes(xs, 0, 1))
    return jnp.swapaxes(ys, 0, 1), h, c


def forward(p, history, forecast, precision: str = "highest"):
    """history (b, 672, 10), forecast (b, 96, 9) -> (b, 96) normalised
    production."""
    dt, prec = _dtype(precision), _dot_precision(precision)
    p = jax.tree.map(lambda x: x.astype(dt), p)
    history, forecast = history.astype(dt), forecast.astype(dt)
    hsz = p["encoder"]["wh"].shape[0]
    zero = jnp.zeros((history.shape[0], hsz), dt)
    _, h, c = _lstm(p["encoder"], history, zero, zero, prec)
    ys, _, _ = _lstm(p["decoder"], forecast, h, c, prec)
    pred = jnp.dot(ys, p["head_w"], precision=prec)[..., 0] + p["head_b"][0]
    return jax.nn.sigmoid(pred - 2.5)


def loss(p, batch, anchor, lam, precision: str = "highest"):
    pred = forward(p, batch["history"], batch["forecast"], precision)
    mse = jnp.mean(jnp.square(pred - batch["target"].astype(pred.dtype)))
    reg = sum(jnp.sum(jnp.square(a - b.astype(a.dtype)))
              for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(anchor),
                              strict=True))
    return mse + 0.5 * jnp.asarray(lam, mse.dtype) * reg


@functools.lru_cache(maxsize=None)
def _sgd_step(precision: str):
    def step(p, batch, anchor, lam, lr):
        val, g = jax.value_and_grad(loss)(p, batch, anchor, lam, precision)
        return jax.tree.map(lambda x, gx: x - jnp.asarray(lr, x.dtype) * gx,
                            p, g), val
    return jax.jit(step)


@functools.lru_cache(maxsize=None)
def _forward_jit(precision: str):
    return jax.jit(functools.partial(forward, precision=precision))


def predict(p, history, forecast, precision: str = "highest"):
    out = _forward_jit(precision)(p, jnp.asarray(history),
                                  jnp.asarray(forecast))
    return np.asarray(out.astype(jnp.float32))


def train(p0, windows: dict, rng_state: dict, anchor, lam: float, lr: float,
          batch_size: int, epochs: int, precision: str = "highest"):
    """One client training call from the same inputs the program had:
    parameters, anchor (None: no anchor), the window arrays and the state
    of the client's generator when the call began.  Returns the trained
    float32 parameters and the loss of every step."""
    rng = np.random.Generator(np.random.PCG64())
    rng.bit_generator.state = rng_state
    dt = _dtype(precision)
    p = jax.tree.map(lambda x: x.astype(dt), p0)
    a = p if anchor is None else jax.tree.map(lambda x: x.astype(dt), anchor)
    lam = 0.0 if anchor is None else float(lam)
    step = _sgd_step(precision)
    losses = []
    for _ in range(epochs):
        for b in batch_iter(windows, batch_size, rng):
            batch = {k: jnp.asarray(b[k]) for k in
                     ("history", "forecast", "target")}
            p, val = step(p, batch, a, lam, lr)
            losses.append(val)
    return (jax.tree.map(lambda x: np.asarray(x.astype(jnp.float32)), p),
            [float(v) for v in losses])


# ------------------------------------------------------------------ folds
def flat(tree) -> np.ndarray:
    """Leaves in ``jax.tree.leaves`` order, concatenated, float64."""
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree.leaves(tree)])


def fold_algorithm2(base: np.ndarray, base_meta: tuple, updates,
                    precision: str = "highest") -> np.ndarray:
    """Algorithm 2 applied to each update in arrival order.

    ``base_meta`` and each update's meta are ``(samples, round)``;
    ``updates`` is ``[(flat_params, (samples, round), delta_samples,
    delta_rounds)]``.  An update computed against the current base
    (``round == base round + 1``) replaces it; otherwise the two are
    averaged by their samples learned.  float64, or bfloat16 for the
    control."""
    cast = _caster(precision)
    samples, rnd = base_meta
    p = cast(base)
    for upd, (u_samples, u_round), d_samples, d_rounds in updates:
        u = cast(upd)
        total = samples + u_samples
        if u_round == rnd + 1 or total <= 0:
            p = u
        else:
            rb = samples / total
            p = cast(cast(p * rb) + cast(u * (1.0 - rb)))
        samples += d_samples
        rnd += d_rounds
    return np.asarray(p, np.float64)


def secure_fold(base: np.ndarray, privatized, weights,
                precision: str = "highest") -> np.ndarray:
    """One secure round: ``base + sum_i w_i * priv_i / sum_i w_i`` over
    the members that submitted, with no masks (they must cancel)."""
    cast = _caster(precision)
    total = float(sum(weights))
    acc = cast(np.zeros_like(base))
    for priv, w in zip(privatized, weights, strict=True):
        acc = cast(acc + cast(cast(priv) * w))
    return np.asarray(cast(cast(base) + cast(acc / total)), np.float64)


def _caster(precision: str):
    if precision == "bfloat16":
        return lambda x: np.asarray(
            jnp.asarray(x, jnp.float32).astype(jnp.bfloat16), np.float64)
    return lambda x: np.asarray(x, np.float64)


def dp_noise_z(delta: np.ndarray, privatized: np.ndarray, clip: float,
               noise_multiplier: float) -> float:
    """How far a release's noise is from the Gaussian the configuration
    states, in standard errors.  The release minus the clipped delta is
    the noise; the standard deviation of P Gaussian draws lies within
    about ``sigma / sqrt(2P)`` of ``sigma``, so this is ``|std / sigma - 1|
    * sqrt(2P)``, below a few on any sound release whatever its draw."""
    norm = float(np.sqrt(np.sum(delta * delta)))
    clipped = delta * min(1.0, clip / max(norm, 1e-12))
    noise = privatized - clipped
    ratio = float(np.std(noise) / (noise_multiplier * clip))
    return abs(ratio - 1.0) * float(np.sqrt(2.0 * noise.size))


# -------------------------------------------------------------- clustering
def _distances(X: np.ndarray, metric: str) -> np.ndarray:
    if metric == "haversine":
        a = np.radians(X)
        dlat = a[:, None, 0] - a[None, :, 0]
        dlon = a[:, None, 1] - a[None, :, 1]
        h = (np.sin(dlat / 2) ** 2 + np.cos(a[:, None, 0])
             * np.cos(a[None, :, 0]) * np.sin(dlon / 2) ** 2)
        return 2 * 6371.0 * np.arcsin(np.sqrt(np.clip(h, 0, 1)))
    if metric == "cyclic":
        d = np.abs(X[:, None, 0] - X[None, :, 0]) % 360.0
        return np.minimum(d, 360.0 - d)
    return np.sqrt(((X[:, None] - X[None, :]) ** 2).sum(-1))


def dbscan(X, eps: float, min_samples: int, metric: str) -> np.ndarray:
    """Cluster labels (-1 for noise).  A point with at least
    ``min_samples`` points within ``eps`` (itself included) is a core
    point; clusters are the connected components of core points, and a
    non-core point within ``eps`` of a core point joins the cluster of
    the nearest one."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    d = _distances(np.asarray(X, np.float64), metric)
    near = d <= eps
    core = near.sum(1) >= min_samples
    _, comp = connected_components(
        csr_matrix(near & core[:, None] & core[None, :]), directed=False)
    labels = np.where(core, comp, -1)
    for i in np.flatnonzero(~core):
        cores = np.flatnonzero(near[i] & core)
        if cores.size:
            labels[i] = labels[cores[np.argmin(d[i, cores])]]
    return labels
