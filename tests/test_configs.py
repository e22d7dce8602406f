"""Config registry: every assigned architecture exists with exact numbers."""

import pytest

from repro.configs import (
    ALL_ARCHS,
    INPUT_SHAPES,
    get_config,
    reduced_for_smoke,
    shape_is_applicable,
)

EXPECTED = {
    "recurrentgemma-9b": dict(n_layers=38, d_model=4096, n_heads=16,
                              n_kv_heads=1, d_ff=12288, vocab_size=256000),
    "hubert-xlarge": dict(n_layers=48, d_model=1280, n_heads=16,
                          n_kv_heads=16, d_ff=5120, vocab_size=504),
    "mamba2-370m": dict(n_layers=48, d_model=1024, d_ff=0, vocab_size=50280),
    "internvl2-76b": dict(n_layers=80, d_model=8192, n_heads=64,
                          n_kv_heads=8, d_ff=28672, vocab_size=128256),
    "granite-8b": dict(n_layers=36, d_model=4096, n_heads=32, n_kv_heads=8,
                       d_ff=14336, vocab_size=49152),
    "deepseek-v3-671b": dict(n_layers=61, d_model=7168, n_heads=128,
                             d_ff=2048, vocab_size=129280),
    "gemma-2b": dict(n_layers=18, d_model=2048, n_heads=8, n_kv_heads=1,
                     d_ff=16384, vocab_size=256000),
    "deepseek-moe-16b": dict(n_layers=28, d_model=2048, n_heads=16,
                             n_kv_heads=16, d_ff=1408, vocab_size=102400),
    "glm4-9b": dict(n_layers=40, d_model=4096, n_heads=32, n_kv_heads=2,
                    d_ff=13696, vocab_size=151552),
    "deepseek-7b": dict(n_layers=30, d_model=4096, n_heads=32, n_kv_heads=32,
                        d_ff=11008, vocab_size=102400),
    "kanana2-30b-a3b": dict(n_layers=48, d_model=2048, n_heads=32,
                            n_kv_heads=32, d_ff=768, vocab_size=128256),
}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_exact_assigned_numbers(arch):
    cfg = get_config(arch)
    for field, val in EXPECTED[arch].items():
        assert getattr(cfg, field) == val, (arch, field)
    assert cfg.citation


def test_all_ten_archs_present():
    assert len(ALL_ARCHS) == 11
    families = {get_config(a).family for a in ALL_ARCHS}
    assert families == {"dense", "moe", "ssm", "hybrid", "audio", "vlm"}


def test_moe_details():
    v3 = get_config("deepseek-v3-671b")
    assert v3.moe.n_routed_experts == 256 and v3.moe.top_k == 8
    assert v3.moe.n_shared_experts == 1 and v3.mla is not None
    assert v3.mtp_depth == 1
    m16 = get_config("deepseek-moe-16b")
    assert m16.moe.n_routed_experts == 64 and m16.moe.top_k == 6
    assert m16.moe.n_shared_experts == 2


def test_input_shapes():
    assert INPUT_SHAPES["train_4k"].seq_len == 4096
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["prefill_32k"].global_batch == 32
    assert INPUT_SHAPES["decode_32k"].global_batch == 128
    assert INPUT_SHAPES["long_500k"].seq_len == 524288
    assert INPUT_SHAPES["long_500k"].global_batch == 1


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_variant_bounds(arch):
    red = reduced_for_smoke(get_config(arch))
    assert red.n_layers == 2
    assert red.d_model <= 512
    if red.moe:
        assert red.moe.n_routed_experts <= 4


def test_applicability_matrix():
    hubert = get_config("hubert-xlarge")
    ok, reason = shape_is_applicable(hubert, INPUT_SHAPES["decode_32k"])
    assert not ok and "encoder-only" in reason
    ok, _ = shape_is_applicable(hubert, INPUT_SHAPES["prefill_32k"])
    assert ok
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        if arch == "hubert-xlarge":
            continue
        ok, _ = shape_is_applicable(cfg, INPUT_SHAPES["long_500k"])
        assert ok, arch
