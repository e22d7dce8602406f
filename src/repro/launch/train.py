"""Training launcher.

Two entry modes:
  * ``--federated``: FedCCL end-to-end on the solar case study (the paper's
    deployment) — clients, clustering, async rounds, Table-II style eval.
  * default: single-model LM training on synthetic data for a reduced
    assigned architecture (CPU-scale driver used by examples/tests).

Runs on JAX's default device, with the persistent compilation cache
placed by ``repro.launch.device.use_compile_cache``.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.launch.device import use_compile_cache


def train_lm(arch: str, steps: int, batch: int, seq: int, lr: float,
             log_every: int = 10):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced_for_smoke
    from repro.data.lm_synth import audio_batch, lm_batch, vlm_batch
    from repro.models.model import build_model
    from repro.optim.optimizers import adamw
    from repro.optim.schedules import warmup_cosine
    from repro.training.train_step import build_train_step, init_train_state

    cfg = reduced_for_smoke(get_config(arch))
    model = build_model(cfg)
    opt = adamw(warmup_cosine(lr, steps // 10 + 1, steps))
    state = init_train_state(model, opt, jax.random.key(0))
    step_fn = jax.jit(build_train_step(model, cfg, opt))
    rng = np.random.default_rng(0)

    for i in range(steps):
        if cfg.family == "audio":
            b = audio_batch(rng, batch, seq, cfg.frontend.embed_dim, cfg.vocab_size)
        elif cfg.family == "vlm":
            b = vlm_batch(rng, batch, seq, 4, cfg.frontend.embed_dim, cfg.vocab_size)
        else:
            b = lm_batch(rng, batch, seq, cfg.vocab_size)
        state, metrics = step_fn(state, {k: jnp.asarray(v) for k, v in b.items()})
        if i % log_every == 0 or i == steps - 1:
            print(f"step {i:4d} loss {float(metrics['loss']):.4f} "
                  f"ce {float(metrics['ce']):.4f}")
    return state


def train_federated(n_sites: int, n_days: int, rounds: int, seed: int):
    from repro.training.fed_solar import run_fedccl_solar

    report = run_fedccl_solar(n_sites=n_sites, n_days=n_days, rounds=rounds,
                              seed=seed)
    print(json.dumps({k: v for k, v in report.items() if k != "models"},
                     indent=2, default=str))
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--federated", action="store_true")
    ap.add_argument("--sites", type=int, default=9)
    ap.add_argument("--days", type=int, default=60)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    t0 = time.time()
    if args.federated:
        train_federated(args.sites, args.days, args.rounds, args.seed)
    else:
        train_lm(args.arch, args.steps, args.batch, args.seq, args.lr)
    print(f"[train] done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
