"""The silo fine-tuning cell (``silo-moe-finetune``): found by its names,
rehearsed on the CPU at a tiny size through the harness's whole run but
its look for a chip, and its analytic costs against the configuration."""

import json
import pathlib

import pytest

import harness
import lm_costs

NAME = "silo-moe-finetune"
#: a tiny model of the same layout (a dense layer, two MoE layers, a share
#: of 2 of the router's 8 experts from the third) in float32, where the
#: program and the reference agree to rounding
SMALL = {"model": {"hidden_size": 64, "num_hidden_layers": 3,
                   "num_attention_heads": 2, "kv_lora_rank": 16,
                   "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                   "v_head_dim": 16, "intermediate_size": 64,
                   "moe_intermediate_size": 32, "n_routed_experts": 2,
                   "num_experts_per_tok": 2, "n_shared_experts": 1,
                   "vocab_size": 128, "seq_len": 32, "dtype": "float32",
                   "expert_share": {"router_experts": 8, "first_held": 2,
                                    "chips_per_layer": 4}},
         "traffic": {"sequences_per_silo": 4, "check_train_rate": 1.0,
                     "check_fold_rate": 1.0, "check_elements_per_leaf": 64}}
CONFIG = json.loads((pathlib.Path(harness.BENCH) / "configs"
                     / "kanana2-30b-a3b-silo.json").read_text())


def _run(**kw):
    return harness.run_cell(harness.Cell.find(NAME), 2**33 + 5, 0.3, False,
                            require_tpu=False, small=SMALL,
                            log=lambda m: None, **kw)


def test_cell_and_metrics_found_by_name():
    cell = harness.Cell.find(NAME)
    assert cell.traffic["driver"] == "silo_lm"
    assert cell.config["name"] == "kanana2-30b-a3b-silo"
    assert {m["name"] for m in cell.end_to_end} == {"updates_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names == {"lm_step_ms", "lm_step_roofline", "mfu.lm_train",
                     "moe_load_max_ratio", "lm_fold_roofline"}
    for n in names:
        assert hasattr(harness.load_module(cell.bench / "metrics"
                                           / f"{n}.py"), "read")
    # the logits and the held experts' loads are readings, not limits: no
    # control or fault on the chip reads the logits above the program
    assert set(cell.limits) == {"train_change_gap", "train_loss_gap",
                                "fold_gap"}


def test_driver_rehearsal():
    out = _run(control=True)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"updates_per_s", "setup_s"}
    assert out["_diagnostics"]["compiles_in_window"] == 0
    assert out["program_readings"]["held_load_gap"] == 0.0
    # the control fails at least one of the cell's numbers
    assert any(v is not None and v > out["checks"][k]["limit"]
               for k, v in out["control"].items() if k in out["checks"])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_planted_fault_reads_not_correct(fault):
    out = _run(fault=fault)
    assert out["correct"] is False, (fault, out["checks"])


def test_traced_rehearsal_reads_the_new_metrics(tmp_path):
    """With telemetry on, the counters the metrics read are there: tokens
    and the held experts' assignments, read once at the dump."""
    out = harness.run_cell(harness.Cell.find(NAME), 7, 0.3, True,
                           require_tpu=False, small=SMALL,
                           out_dir=tmp_path, log=lambda m: None)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["moe_load_max_ratio"]["value"] >= 1.0


def _fold_run(modules, counters):
    from devtrace import Summary

    return harness.Run(model={}, peak={"bf16_flops_per_s": 197e12,
                                       "hbm_bytes_per_s": 819e9},
                       window_s=60.0, spans={}, counters=counters,
                       trace=Summary(window_s=60.0, busy_s=58.0,
                                     modules=modules))


def _fold_read(run):
    return harness.load_module(harness.BENCH / "metrics"
                               / "lm_fold_roofline.py").read(run)


def test_fold_route_roofline_reads_every_chunk_program():
    """The fold's logical bytes over the time of its gathers, kernel and
    scatters together, against the chip's HBM bandwidth."""
    mods = {"jit__gather_chunk": [1.43, 210], "jit_agg_tiled": [0.52, 210],
            "jit__scatter_chunk": [0.26, 210], "jit_lm_sgd_step": [55.5, 144]}
    run = _fold_run(mods, {"fold_bytes": 30 * 3 * 424_961_024 * 2})
    want = 100.0 * 30 * 3 * 424_961_024 * 2 / 819e9 / (1.43 + 0.52 + 0.26)
    assert _fold_read(run) == pytest.approx(want)
    assert 0 < _fold_read(run) < 100


@pytest.mark.parametrize("modules,counters", [
    # a fold without the chunk programs (an older program's whole route)
    ({"jit_agg_tiled": [0.5, 30]}, {"fold_bytes": 10**9}),
    # no fold in the window
    ({"jit_lm_sgd_step": [55.5, 144]}, {}),
    # the programs ran, the bytes were not counted
    ({"jit__gather_chunk": [1.4, 7], "jit_agg_tiled": [0.5, 7],
      "jit__scatter_chunk": [0.3, 7]}, {})],
    ids=["whole-route", "no-fold", "no-bytes"])
def test_fold_route_roofline_reads_nothing_without_its_route(modules,
                                                             counters):
    assert _fold_read(_fold_run(modules, counters)) is None
    assert _fold_read(harness.Run(model={}, peak={}, window_s=1.0, spans={},
                                  counters={})) is None


def test_parameter_count_of_the_share():
    m = CONFIG["model"]
    assert lm_costs.param_count(m) == 424_961_024


def test_step_flops():
    """22.5 TFLOP a step under even routing: 915 MFLOP a token forward,
    46 % of it in attention's scores and values."""
    m = CONFIG["model"]
    s = m["seq_len"]
    fwd = (s * lm_costs.unrouted_flops_per_token(m)
           + m["num_hidden_layers"] * lm_costs.attention_core_flops(m, s)
           + lm_costs.expected_assignments(m, 1)
           * lm_costs.routed_flops_per_assignment(m))
    assert fwd / s == pytest.approx(915.9e6, rel=1e-3)
    core = m["num_hidden_layers"] * lm_costs.attention_core_flops(m, s)
    assert core / fwd == pytest.approx(0.458, abs=0.005)
    assert lm_costs.train_flops(m, 1, lm_costs.expected_assignments(m, 1)) \
        == pytest.approx(3 * fwd)


def test_configuration_holds_the_catalog_numbers():
    """Every number of the published config, the cut keys at their cut
    values with the published ones beside them, and the model group as
    run agreeing with the top level."""
    cut = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 16032}
    published = {"num_hidden_layers": 48, "n_routed_experts": 128,
                 "vocab_size": 128256}
    assert CONFIG["published"] == published
    assert sorted(CONFIG["reduced"]) == sorted(cut)
    for k, v in cut.items():
        assert CONFIG[k] == v
    m = CONFIG["model"]
    for k, v in m.items():
        if k in CONFIG:
            assert CONFIG[k] == v, k


def test_the_registered_architecture_matches_the_file():
    import sys

    sys.path.insert(0, str(pathlib.Path(harness.BENCH) / "drivers"))
    drv = harness.load_module(pathlib.Path(harness.BENCH) / "drivers"
                              / "silo_lm.py")
    harness.prepare_jax(False, 1)
    assert drv.registry_mismatches(CONFIG) == []
