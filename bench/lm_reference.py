"""Plain reference for the silo fine-tuning cell's model: a DeepSeek-V3
layer stack (Kanana-2-30B-A3B's widths), its loss and its SGD step.

Written from the published descriptions (DeepSeek-V3, arXiv 2412.19437
§2.1; Hugging Face ``DeepseekV3`` config keys) in straightforward
``jax.numpy``, with nothing imported from the program:

* RMSNorm, x / sqrt(mean(x^2) + eps) * scale, before attention and FFN;
* multi-head latent attention without the query's low-rank path: q = x Wq
  split into a 128-wide part and a 64-wide rotary part; the latent c = x
  Wkv_a, RMS-normalised; per-head keys c Wk_b and values c Wv_b; one rotary
  key x Wk_rope shared by every head; rotary embedding on adjacent pairs
  (x[2i], x[2i+1]) at angle pos * theta^(-2i/64) (``rope_interleave``, no
  YaRN: ``rope_scaling`` is null); causal softmax over q.k / sqrt(192);
* the first ``first_k_dense_replace`` layers a SwiGLU FFN, the rest a
  mixture of experts: sigmoid scores over all routed experts, the top-k
  chosen by score plus the correction bias, gated by the unbiased scores
  normalised over the k and scaled by ``routed_scaling_factor``; the
  chip's held experts' SwiGLU outputs for the tokens routed to them, plus
  the shared experts' SwiGLU (width ``moe_intermediate_size *
  n_shared_experts``);
* the untied head over the vocabulary slice, mean next-token
  cross-entropy, plus ``lam / 2 * sum (p - anchor)^2``;
* plain SGD: each step in float32, each parameter rounded to its storage
  dtype after it, as the configuration stores them.

Departures, each where the program makes the same one: the layers are the
chip's share of a 16-chip expert-parallel deployment, so the routed
contribution is the held experts' alone (the rest is another chip's);
the router's correction bias carries no gradient and no auxiliary loss is
trained.  Attention runs over blocks of queries, each under
``jax.checkpoint``, so that the float32 scores of 8,192 tokens fit: each
block's softmax is over every key at once (the later ones masked), not
online.  The held experts run densely over every token, each weighted by
its gate (0 where a token did not choose it).

``precision="highest"``: float32 with every product at full precision.
``precision="bfloat16"``: the control, everything in bfloat16 (norms,
softmax, router and loss too).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: queries per attention block
BLOCK = 512


def dims(cfg: dict) -> dict:
    """The sizes the reference reads, from a configuration in the
    Hugging Face keys (``configs/*.json``) with its ``expert_share``."""
    share = cfg["expert_share"]
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"], "rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "dense_ff": cfg["intermediate_size"],
        "ff": cfg["moe_intermediate_size"],
        "router": share["router_experts"], "held": cfg["n_routed_experts"],
        "first_held": share["first_held"], "k": cfg["num_experts_per_tok"],
        "shared": cfg["n_shared_experts"],
        "scaling": cfg["routed_scaling_factor"], "vocab": cfg["vocab_size"],
        "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
    }


def _shapes(m: dict) -> dict:
    d, h, f = m["d"], m["heads"], m["ff"]
    attn = {"wq": (d, h, m["nope"] + m["rope"]), "wkv_a": (d, m["rank"]),
            "kv_norm": (m["rank"],), "wk_rope": (d, m["rope"]),
            "wk_b": (m["rank"], h, m["nope"]), "wv_b": (m["rank"], h, m["v"]),
            "wo": (h, m["v"], d)}

    def ffn(w):
        return {"w_gate": (d, w), "w_up": (d, w), "w_down": (w, d)}

    def block(mix):
        return {"norm1": {"scale": (d,)}, "attn": attn,
                "norm2": {"scale": (d,)}, **mix}

    moe = {"moe": {"router": (d, m["router"]), "router_bias": (m["router"],),
                   "experts": {"w_gate": (m["held"], d, f),
                               "w_up": (m["held"], d, f),
                               "w_down": (m["held"], f, d)},
                   "shared": ffn(f * m["shared"])}}
    n_moe = m["layers"] - m["dense"]
    segs = {}
    if m["dense"]:
        segs["seg0"] = {f"b{i}": block({"mlp": ffn(m["dense_ff"])})
                        for i in range(m["dense"])}
    stacked = jax.tree.map(lambda s: (n_moe,) + s, block(moe),
                           is_leaf=lambda x: isinstance(x, tuple))
    segs[f"seg{len(segs)}"] = {"b0": stacked}
    return {"embed": (m["vocab"], d), "segments": segs,
            "final_norm": {"scale": (d,)}, "head": (d, m["vocab"])}


def init_params(key, cfg: dict, dtype: str = "bfloat16") -> dict:
    """Seeded weights in the program's layout: matrices normal with std
    1/sqrt(fan in) (the embedding 1, the router 0.02), norm scales 1 and
    float32, the router's correction bias float32 and normal with std 0.05,
    so that it moves the selection as a trained bias does."""
    m = dims(cfg)
    stacked = f"seg{1 if m['dense'] else 0}"
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves, strict=True):
        names = [str(q.key) for q in path]
        name = names[-1]
        if name in ("scale", "kv_norm"):
            out.append(jnp.ones(shape, jnp.float32))
            continue
        if name == "router_bias":
            out.append(0.05 * jax.random.normal(k, shape, jnp.float32))
            continue
        core = shape[1:] if stacked in names else shape
        if name == "embed":
            std = 1.0
        elif name == "router":
            std = 0.02
        else:
            fan = (core[0] * core[1] if name == "wo" else
                   core[1] if "experts" in names else core[0])
            std = 1.0 / np.sqrt(fan)
        out.append((jax.random.normal(k, shape, jnp.float32) * std)
                   .astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------------ model
def _dt(precision: str):
    return jnp.bfloat16 if precision == "bfloat16" else jnp.float32


def _prec(precision: str):
    return (jax.lax.Precision.HIGHEST if precision == "highest"
            else jax.lax.Precision.DEFAULT)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(x.dtype)


def _rope_pairs(x, theta: float):
    """Rotate adjacent pairs (x[2i], x[2i+1]) of the last axis by pos *
    theta^(-2i/dim); x: (s, ..., dim)."""
    s, dim = x.shape[0], x.shape[-1]
    freq = theta ** (-np.arange(0, dim, 2) / dim)
    ang = np.arange(s)[:, None] * freq[None, :]              # (s, dim/2)
    shape = (s,) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos = jnp.asarray(np.cos(ang).reshape(shape), x.dtype)
    sin = jnp.asarray(np.sin(ang).reshape(shape), x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(p, x, prec):
    g = jnp.dot(x, p["w_gate"], precision=prec)
    u = jnp.dot(x, p["w_up"], precision=prec)
    return jnp.dot(jax.nn.silu(g) * u, p["w_down"], precision=prec)


def _attention(p, x, m, prec):
    """x: (s, d) one sequence -> (s, d)."""
    s = x.shape[0]
    q = jnp.einsum("sd,dhk->shk", x, p["wq"], precision=prec)
    q_nope, q_rot = q[..., :m["nope"]], _rope_pairs(q[..., m["nope"]:],
                                                    m["theta"])
    c = _rms(jnp.dot(x, p["wkv_a"], precision=prec), p["kv_norm"], m["eps"])
    k_nope = jnp.einsum("sr,rhk->shk", c, p["wk_b"], precision=prec)
    v = jnp.einsum("sr,rhk->shk", c, p["wv_b"], precision=prec)
    k_rot = _rope_pairs(jnp.dot(x, p["wk_rope"], precision=prec), m["theta"])
    scale = (m["nope"] + m["rope"]) ** -0.5
    blk = min(BLOCK, s)
    n = -(-s // blk)
    pad = n * blk - s
    q_nope = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0)))
    q_rot = jnp.pad(q_rot, ((0, pad), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * blk, blk)
        qr = jax.lax.dynamic_slice_in_dim(q_rot, i * blk, blk)
        sc = (jnp.einsum("qhk,thk->hqt", qn, k_nope, precision=prec)
              + jnp.einsum("qhk,tk->hqt", qr, k_rot, precision=prec))
        sc = sc * jnp.asarray(scale, sc.dtype)
        causal = jnp.arange(s)[None, :] <= i * blk + jnp.arange(blk)[:, None]
        w = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", w, v, precision=prec)

    o = jax.lax.map(block, jnp.arange(n)).reshape(n * blk, *v.shape[1:])[:s]
    return jnp.einsum("shk,hkd->sd", o, p["wo"], precision=prec)


def _moe(p, x, m, prec):
    """x: (s, d) -> (s, d) and the chosen experts (s, k): the held
    experts' share plus the shared ones."""
    logits = jnp.dot(x, p["router"], precision=prec)
    scores = jax.nn.sigmoid(logits)
    _, top = jax.lax.top_k(scores + p["router_bias"].astype(scores.dtype),
                           m["k"])
    gates = jnp.take_along_axis(scores, top, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True) * \
        jnp.asarray(m["scaling"], gates.dtype)
    held = m["first_held"] + jnp.arange(m["held"])
    # each held expert's gate on each token (0 where not chosen)
    g = jnp.sum(jnp.where(top[None] == held[:, None, None], gates[None], 0),
                axis=-1)                                         # (held, s)
    ex = p["experts"]
    a = jnp.einsum("sd,edf->esf", x, ex["w_gate"], precision=prec)
    b = jnp.einsum("sd,edf->esf", x, ex["w_up"], precision=prec)
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(a) * b, ex["w_down"],
                   precision=prec)
    return _swiglu(p["shared"], x, prec) + jnp.sum(g[..., None] * y, 0), top


def _layer(p, h, m, prec, kind):
    h = h + _attention(p["attn"], _rms(h, p["norm1"]["scale"], m["eps"]), m,
                       prec)
    x = _rms(h, p["norm2"]["scale"], m["eps"])
    if kind == "dense":
        return h + _swiglu(p["mlp"], x, prec), None
    y, top = _moe(p["moe"], x, m, prec)
    return h + y, top


def forward(params, tokens, cfg: dict, precision: str = "highest"):
    """tokens (s,) one sequence -> (logits (s, vocab), each MoE layer's
    chosen experts (moe_layers, s, k)).  Each layer is recomputed in the
    backward pass."""
    m, dt, prec = dims(cfg), _dt(precision), _prec(precision)
    params = jax.tree.map(lambda a: a.astype(dt), params)
    segs = params["segments"]
    names = sorted(segs)
    h = params["embed"][tokens]
    for i in range(m["dense"]):
        h, _ = jax.checkpoint(functools.partial(
            _layer, m=m, prec=prec, kind="dense"))(segs[names[0]][f"b{i}"], h)

    @jax.checkpoint
    def moe_layer(h, p):
        return _layer(p, h, m, prec, "moe")

    h, routes = jax.lax.scan(moe_layer, h, segs[names[-1]]["b0"])
    h = _rms(h, params["final_norm"]["scale"], m["eps"])
    return jnp.dot(h, params["head"], precision=prec), routes


def loss(params, seq, anchor, lam, cfg: dict, precision: str = "highest"):
    """Mean next-token cross-entropy of seq (s + 1,) plus the anchor."""
    logits, _ = forward(params, seq[:-1], cfg, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, seq[1:, None], axis=-1))
    dt = _dt(precision)
    reg = sum(jnp.sum(jnp.square(a.astype(dt) - b.astype(dt)))
              for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(anchor),
                              strict=True))
    return ce + jnp.asarray(0.5 * lam, dt) * reg


@functools.lru_cache(maxsize=None)
def _step(precision: str, cfg_key: str):
    cfg = _CFGS[cfg_key]
    dt = _dt(precision)

    def step(p, seq, anchor, lam, lr):
        val, g = jax.value_and_grad(loss)(
            jax.tree.map(lambda a: a.astype(dt), p), seq, anchor, lam, cfg,
            precision)
        new = jax.tree.map(
            lambda a, ga: (a.astype(dt) - jnp.asarray(lr, dt) * ga.astype(dt))
            .astype(a.dtype), p, g)
        return new, val
    return jax.jit(step)


_CFGS: dict = {}


def _key(cfg: dict) -> str:
    import json

    k = json.dumps(dims(cfg), sort_keys=True)
    _CFGS[k] = cfg
    return k


def train(p0, seqs, anchor, lam: float, lr: float, cfg: dict,
          precision: str = "highest"):
    """One training: one SGD step on each row of ``seqs`` (steps, s + 1),
    from parameters ``p0`` (stored dtypes) and ``anchor`` (None: no
    anchor).  Returns the parameters after it, in their stored dtypes, and
    the loss of every step."""
    with jax.default_matmul_precision(
            "highest" if precision == "highest" else "default"):
        step = _step(precision, _key(cfg))
        p = jax.device_put(p0)
        a = p if anchor is None or anchor is p0 else jax.device_put(anchor)
        lam = 0.0 if anchor is None else float(lam)
        losses = []
        for row in np.asarray(seqs):
            p, val = step(p, jnp.asarray(row), a, lam, lr)
            losses.append(float(val))
    return p, losses


@functools.lru_cache(maxsize=None)
def _logits_routes(precision: str, cfg_key: str):
    cfg = _CFGS[cfg_key]

    def run(p, t):
        out, routes = forward(p, t, cfg, precision)
        return out.astype(jnp.float32), routes
    return jax.jit(run)


def logits_and_routes(params, tokens, cfg: dict, precision: str = "highest"):
    """One sequence's logits (s, vocab) in float32, on the device, and each
    MoE layer's chosen experts (moe_layers, s, k) on the host."""
    with jax.default_matmul_precision(
            "highest" if precision == "highest" else "default"):
        out, routes = _logits_routes(precision, _key(cfg))(
            params, jnp.asarray(tokens))
    return out, np.asarray(routes)


def round_to(x: np.ndarray, dtype: str) -> np.ndarray:
    """Values as the storage dtype holds them, back in float64."""
    return np.asarray(jnp.asarray(np.asarray(x, np.float32)).astype(dtype)
                      .astype(jnp.float32), np.float64)
