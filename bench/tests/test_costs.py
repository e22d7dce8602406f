"""``costs.py`` against counts made by hand for the published width."""

import json
import pathlib

import pytest

import costs

MODEL = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                    / "solar-lstm-fleet.json").read_text())["model"]


def test_lstm_step_is_two_flops_per_mac():
    # 2*in*4H + 2*H*4H at in 10, H 128
    assert costs.lstm_step_flops(10, 128) == 2 * 10 * 512 + 2 * 128 * 512


@pytest.mark.parametrize("part,want", [
    ("encoder", 94_961_664), ("decoder", 13_467_648), ("head", 24_576)])
def test_forward_parts(part, want):
    h = MODEL["hidden_size"]
    got = {"encoder": MODEL["history_steps"]
           * costs.lstm_step_flops(MODEL["history_channels"], h),
           "decoder": MODEL["horizon_steps"]
           * costs.lstm_step_flops(MODEL["forecast_channels"], h),
           "head": 2 * h * MODEL["horizon_steps"]}[part]
    assert got == want


def test_window_flops_and_parameters():
    assert costs.forward_flops(MODEL) == 108_453_888
    assert costs.train_flops(MODEL) == 3 * 108_453_888
    assert costs.param_count(MODEL) == 141_953
    assert costs.param_count(MODEL) * costs.dtype_bytes(MODEL) == 567_812


def test_bytes_and_roofline():
    p = costs.param_count(MODEL)
    assert costs.fold_bytes(17, p, 4) == 18 * 567_812
    assert costs.dp_bytes(p, 4) == 3 * 567_812
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # bound by bytes: 50 B at 10 B/s is 5 s of a 10 s kernel
    assert costs.roofline_share(100.0, 50.0, 10.0, peak) == pytest.approx(50.0)
    # bound by FLOPs
    assert costs.roofline_share(800.0, 10.0, 10.0, peak) == pytest.approx(80.0)
    assert costs.roofline_share(1.0, 1.0, 0.0, peak) is None
