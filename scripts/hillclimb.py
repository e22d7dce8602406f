import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Perf-hillclimb runner: one named experiment variant per invocation
(fresh process so XLA device config and env knobs are clean).

  PYTHONPATH=src python scripts/hillclimb.py <variant> [--out artifacts/perf.jsonl]

Variants encode hypothesis -> change on the three chosen (arch x shape)
pairs; results append to the JSONL consumed by EXPERIMENTS.md §Perf.
"""

import argparse
import json

VARIANTS = {}


def variant(name):
    def deco(fn):
        VARIANTS[name] = fn
        return fn
    return deco


# =========================================================================
# A. internvl2-76b x train_4k — compute-dominant, 261 GB temp memory
# =========================================================================

@variant("A0_baseline")
def a0():
    from repro.launch.dryrun import run_one
    return run_one("internvl2-76b", "train_4k", remat="full", verbose=False)


@variant("A1_flash_train")
def a1():
    """H: dropping FLASH_THRESHOLD to 1024 removes the materialized
    (b,h,4k,4k) f32 score tensors -> temp memory way down, terms ~equal."""
    os.environ["REPRO_FLASH_THRESHOLD"] = "1024"
    from repro.launch.dryrun import run_one
    return run_one("internvl2-76b", "train_4k", remat="full", verbose=False)


@variant("A2_flash_dots_saveable")
def a2():
    """H: with flash keeping activations small, relaxing remat full ->
    dots_saveable cuts the recompute pass: compute mult 4x -> 3x
    (analytic compute term -25%) at an acceptable temp-memory cost."""
    os.environ["REPRO_FLASH_THRESHOLD"] = "1024"
    from repro.launch.dryrun import run_one
    return run_one("internvl2-76b", "train_4k", remat="dots_saveable",
                   verbose=False)


@variant("A3_flash_dots_bf16_moments")
def a3():
    """H: bf16 Adam moments halve optimizer HBM traffic and shard bytes
    (memory term down; compute unchanged)."""
    os.environ["REPRO_FLASH_THRESHOLD"] = "1024"
    from repro.launch.dryrun import run_one
    return run_one("internvl2-76b", "train_4k", remat="dots_saveable",
                   moment_dtype="bfloat16", verbose=False)


@variant("A4_sequence_parallel")
def a4():
    """H(from A1/A2 refutations): the 261 GB temp is per-layer scan carries
    + CE chain, both (b, s, ...) activations — sharding the activation
    `seq` axis over `model` (Megatron sequence parallelism) divides those
    temps by 16 at the cost of per-layer seq all-gathers before attention.
    Predict: temp ~261/16 + params-ish ~= 20-30 GB; collective term up."""
    os.environ["REPRO_FLASH_THRESHOLD"] = "1024"
    from repro.launch.dryrun import run_one
    return run_one("internvl2-76b", "train_4k", remat="full",
                   extra_rules={"seq": "model"}, verbose=False)


@variant("A5_seqpar_dots")
def a5():
    """H: with sequence parallelism paying the memory bill, retry
    dots_saveable for the 4x->3x compute win (A2's 913 GB becomes ~57 GB
    when the saved dots are seq-sharded)."""
    os.environ["REPRO_FLASH_THRESHOLD"] = "1024"
    from repro.launch.dryrun import run_one
    return run_one("internvl2-76b", "train_4k", remat="dots_saveable",
                   extra_rules={"seq": "model"}, verbose=False)


@variant("A6_seqpar_microbatch8")
def a6():
    """H: gradient accumulation over 8 microbatches divides the remaining
    (b, ...) activation temps by 8 on top of A4: predict ~71/8 + params
    ~= 10-15 GB/device — the first variant that actually fits v5e HBM.
    Compute/memory/collective terms unchanged (same total work)."""
    os.environ["REPRO_FLASH_THRESHOLD"] = "1024"
    from repro.launch.dryrun import run_one
    return run_one("internvl2-76b", "train_4k", remat="full",
                   extra_rules={"seq": "model"}, n_microbatches=8,
                   verbose=False)


# =========================================================================
# B. deepseek-v3-671b x decode_32k — most collective-bound (26% useful)
# =========================================================================

@variant("B0_baseline_absorbed")
def b0():
    from repro.launch.dryrun import run_one
    return run_one("deepseek-v3-671b", "decode_32k", verbose=False)


@variant("B0n_paper_naive_mla")
def b0n():
    """Paper-faithful naive MLA decode (re-expand K/V from the latent every
    step) — recorded as the reproduction baseline; absorbed path (B0) is
    the beyond-paper optimization."""
    from repro.launch.dryrun import run_one
    return run_one("deepseek-v3-671b", "decode_32k", mla_absorb=False,
                   verbose=False)


@variant("B1_no_fsdp_gather_at_decode")
def b1():
    """H: at decode there is no optimizer, so FSDP (embed->data) param
    sharding only adds a 617 MB/step all-gather over `data`; resharding
    params to model-only (embed->None) kills it.  Risk: params/device grow
    16x for non-expert weights — check memory_analysis."""
    from repro.launch.dryrun import run_one
    return run_one("deepseek-v3-671b", "decode_32k",
                   extra_rules={"embed": None}, verbose=False)


@variant("B2_experts_over_full_mesh")
def b2():
    """H: expert weights dominate dsv3 params; sharding the expert axis
    over BOTH mesh axes (256 experts / 256 chips) keeps per-device memory
    flat while removing the expert-tensor share of the data all-gather;
    token dispatch becomes a small all-to-all."""
    from repro.launch.dryrun import run_one
    return run_one("deepseek-v3-671b", "decode_32k",
                   extra_rules={"embed": None, "expert": ("data", "model")},
                   verbose=False)


# =========================================================================
# C. deepseek-moe-16b x train_4k — representative of the paper's technique
#    (federated fine-tune target); compute-dominant, 9.9 GB all-reduce
# =========================================================================

@variant("C0_baseline")
def c0():
    from repro.launch.dryrun import run_one
    return run_one("deepseek-moe-16b", "train_4k", remat="full", verbose=False)


@variant("C1_dots_saveable")
def c1():
    """H: remat full->dots_saveable drops the extra fwd recompute:
    analytic compute mult 4->3 (-25% on the dominant term)."""
    from repro.launch.dryrun import run_one
    return run_one("deepseek-moe-16b", "train_4k", remat="dots_saveable",
                   verbose=False)


@variant("C2_flash_and_dots")
def c2():
    """H: flash attention at 4k additionally cuts temp memory (score
    tensors) with no compute-term change — memory headroom banked."""
    os.environ["REPRO_FLASH_THRESHOLD"] = "1024"
    from repro.launch.dryrun import run_one
    return run_one("deepseek-moe-16b", "train_4k", remat="dots_saveable",
                   verbose=False)


@variant("C4_expert_over_full_mesh")
def c4():
    """H: 64 experts over (data x model)=256 won't divide (64 < 256 uses
    the divisibility guard -> falls back) — try experts over data (16-way,
    4 experts/device) instead of model: moves expert all-gathers off the
    model axis, trades with grad all-reduce locality."""
    os.environ["REPRO_FLASH_THRESHOLD"] = "1024"
    from repro.launch.dryrun import run_one
    return run_one("deepseek-moe-16b", "train_4k", remat="dots_saveable",
                   extra_rules={"expert": "data"}, verbose=False)


# =========================================================================
# D. recurrentgemma-9b x prefill_32k — bonus pair: collective-dominant at
#    98% useful flops (the collectives are pure overhead, not work)
# =========================================================================

@variant("D0_baseline")
def d0():
    from repro.launch.dryrun import run_one
    return run_one("recurrentgemma-9b", "prefill_32k", verbose=False)


@variant("D1_seqpar_prefill")
def d1():
    """H: the 19 GB/step of all-reduce comes from activation resharding
    between recurrent blocks (lru axis on `model`) and local-attn blocks
    (heads on `model`): the residual stream bounces between layouts every
    pattern group.  Sharding the residual's seq axis over `model` gives
    both block types one stable layout; predict most all-reduce replaced
    by cheaper gathers."""
    from repro.launch.dryrun import run_one
    return run_one("recurrentgemma-9b", "prefill_32k",
                   extra_rules={"seq": "model"}, verbose=False)


@variant("D2_replicate_lru")
def d2():
    """H(alt): keep activations replicated on `model` for the recurrent
    branch by NOT sharding the lru width (lru->None): removes the
    per-block reshard at the cost of 16x more per-device lru compute —
    likely a net loss (compute term up), but measures the attribution."""
    from repro.launch.dryrun import run_one
    return run_one("recurrentgemma-9b", "prefill_32k",
                   extra_rules={"lru": None}, verbose=False)


@variant("C5_best_combo")
def c5():
    """H: combine the confirmed wins under a memory-feasible policy:
    remat=full (C1's dots_saveable exploded temps 45->260 GB), sequence
    parallelism + microbatch 4 (A4/A6 lesson) to push temp under ~12 GB.
    Routing is dropless, so there is no capacity factor to trade."""
    os.environ["REPRO_FLASH_THRESHOLD"] = "1024"
    from repro.launch.dryrun import run_one
    return run_one("deepseek-moe-16b", "train_4k", remat="full",
                   extra_rules={"seq": "model"}, n_microbatches=4,
                   verbose=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("variant", choices=sorted(VARIANTS))
    ap.add_argument("--out", default="artifacts/perf.jsonl")
    args = ap.parse_args()
    rec = VARIANTS[args.variant]()
    rec["variant"] = args.variant
    with open(args.out, "a") as f:
        f.write(json.dumps(rec, default=str) + "\n")
    t = rec.get("roofline", {})
    print(json.dumps({
        "variant": args.variant, "status": rec.get("status"),
        "compute_s": t.get("compute_s"), "memory_s": t.get("memory_s"),
        "collective_s": t.get("collective_s"), "dominant": t.get("dominant"),
        "temp_GB": (rec.get("memory", {}).get("temp_bytes") or 0) / 1e9,
        "useful": rec.get("useful_flops_ratio"),
    }, indent=None))


if __name__ == "__main__":
    main()
