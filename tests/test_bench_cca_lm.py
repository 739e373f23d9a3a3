"""The benchmark's files for the ``cca_moe_lm`` cell: its entries, its
configuration against the catalog row, the operation counts, and the
``train_cca_lm`` driver end to end on a tiny cell on the CPU."""

import importlib
import json
import os
import tempfile
import time

import jax
import numpy as np
import pytest

from benchmark import flops_cca_lm, flops_lm
from benchmark.drivers import train_cca_lm
from benchmark.run import Cell, Run, judge, run_cell
from distributed_machine_learning_tpu import tune
from test_cca_moe_lm import REF_CFG, TRIAL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "train_zaya1_s8192"

# The catalog row's settings (model-configs guide, ``ZAYA1-8B``), key for
# key; nested groups whole.
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272,
}


@pytest.fixture(scope="module")
def cell():
    return Cell.load(ROOT, CELL)


def test_cell_loads_with_its_files(cell):
    assert cell.chips == 1 and cell.traffic["driver"] == "train_cca_lm"
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "setup_s"}
    for metric in cell.per_layer:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        assert hasattr(
            importlib.import_module(f"benchmark.readers.{spec['reader']}"),
            "read",
        ), metric["name"]
    names = {m["name"] for m in cell.per_layer}
    assert {"cca_share_pct.lm", "router_share_pct.lm", "head_share_pct.lm",
            "moe_share_pct.lm", "attn_share_pct.lm",
            "flash_causal_fwd_roofline", "flash_causal_bwd_roofline",
            "expert_load_max_over_mean.lm", "expert_pairs_per_step.lm",
            "step_mfu", "peak_hbm_pct.train"} <= names
    assert "gdn_share_pct.lm" not in names
    assert not any(n.startswith("ckpt_") for n in names)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_the_published_key(cell, key):
    """The catalog row's settings, key for key, but the three in
    ``reduced``, which state the published number beside the chip's."""
    cfg = cell.config
    if key in cfg["reduced"]:
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] < PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_trial_block_has_the_published_widths(cell):
    cfg, trial = cell.config, cell.config["trial"]
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert (trial["d_model"], trial["num_heads"], trial["num_kv_heads"],
            trial["head_dim"], trial["expert_width"], trial["num_experts"],
            trial["top_k"], trial["router_hidden"]) == (
        2048, 8, 2, 128, 2048, 16, 1, 256)
    rope = cfg["rope_parameters"]["hybrid"]
    assert trial["rotary_dim"] == cfg["head_dim"] * rope["partial_rotary_factor"]
    assert trial["rope_theta"] == rope["rope_theta"]
    assert (trial["conv_time0"], trial["conv_time1"]) == (2, 2)
    assert trial["held_experts"] == cfg["held_experts"] == [0, cfg["num_experts"]]
    assert trial["vocab_size"] == cfg["vocab_size"] == 262272 // 8
    assert trial["num_layers"] == cfg["num_hidden_layers"] >= 5
    assert trial["checkpoint_freq"] == 0
    for block in ("assumed", "departures", "deployment", "precision", "sizing"):
        assert cfg[block], block


def test_the_parameters_are_the_sizing_blocks(cell):
    from benchmark.reference import cca_moe_lm as ref

    shapes = ref.parameter_shapes(cell.config)

    def count(keys):
        return sum(int(np.prod(shapes[k])) for k in keys)

    layer0 = [k for k in shapes if k.startswith("L0.")]
    assert count(layer0) == pytest.approx(106.9e6, rel=2e-3)
    assert count(["embed"]) == 32784 * 2048
    assert count(shapes) == pytest.approx(708.5e6, rel=1e-3)


def test_train_flops_count_what_the_shapes_ask(cell):
    cfg = cell.config
    pairs = 8192 / 2          # a uniform top-1 router, half the experts held
    whole = flops_cca_lm.forward_flops_per_sequence(cfg, 8192, pairs)
    head = 2 * 8192 * 2048 * 32784
    # The issue's arithmetic: 386 M a token, 35 % of it the head.
    assert whole / 8192 == pytest.approx(386e6, rel=0.01)
    assert head / whole == pytest.approx(0.35, abs=0.01)
    more = flops_cca_lm.forward_flops_per_sequence(cfg, 8192, pairs + 100)
    assert more - whole == pytest.approx(6 * 100 * 6 * 2048 * 2048)
    assert flops_cca_lm.train_flops_per_sequence(cfg, 8192, pairs) == 3 * whole
    # Attention's square grows fourfold with the length, the rest twofold.
    short = flops_cca_lm.forward_flops_per_sequence(cfg, 4096, pairs / 2)
    assert 2 * short < whole < 4 * short
    # The kernels' costs are the hybrid cell's functions at this call.
    ops, _ = flops_lm.flash_causal_forward(
        batch=2, seq_len=8192, heads=8, kv_heads=2, head_dim=128)
    assert ops == 2 * 2 * 2 * 8 * (8192 * 8192 / 2) * 128


# -- the driver on a tiny cell ------------------------------------------------

TINY_LIMITS = {
    "init_gap": 0.0, "loss_rerun_gap": 0.0, "loss_e0_gap": 1e-4, "val_e0_gap": 1e-4,
    "param_change_gap_med": 1e-3, "param_change_gap_p90": 1e-2,
    "param_change_half_ratio_med": 0.5,
}


def _tiny_cell():
    trial = dict(TRIAL, optimizer="adam", loss_function="cross_entropy",
                 learning_rate=1e-2, weight_decay=0.0, checkpoint_freq=0)
    return Cell(
        name="tiny_cca_lm", chips=1, config_name="tiny-cca",
        config=dict(REF_CFG, trial=trial),
        traffic_name="tiny", traffic={
            "driver": "train_cca_lm", "seq_len": 16, "batch_size": 2,
            "steps_per_epoch": 2, "val_sequences": 2, "num_epochs": 1000,
            "trace_seconds": 1, "reference_block_rows": 1, "control": "bf16",
            "limits": dict(TINY_LIMITS),
        },
        end_to_end=[{"name": "train_tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[],
    )


@pytest.fixture
def fresh_programs():
    tune.clear_program_cache()
    yield
    tune.clear_program_cache()


def test_one_trial_through_the_driver_is_correct_and_repeats(
        tmp_path, fresh_programs):
    result = run_cell(
        _tiny_cell(), seed=2_147_483_659, seconds=0.5, traced=False,
        devices=jax.devices()[:1], work_dir=str(tmp_path),
        process_start=time.time(),
    )
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    assert result["checks"]["loss_rerun_gap"]["value"] == 0.0
    assert result["checks"]["init_gap"]["value"] == 0.0
    assert set(TINY_LIMITS) <= set(result["checks"])


@pytest.fixture(scope="module")
def verdicts():
    """The driver's ``readings`` on one seed, each judged by the tiny
    cell's limits: {what: [names not correct]}."""
    from benchmark.reference import regressor

    tune.clear_program_cache()
    cell = _tiny_cell()

    def make_run(seed):
        return Run(cell=cell, seed=seed, seconds=0.0, traced=False,
                   devices=jax.devices()[:1], peaks=None,
                   work_dir=tempfile.mkdtemp(prefix="dml_cca_"))

    out = {}
    for _, what, numbers in train_cca_lm.readings(
        make_run, [5], 1, regressor.bf16, broken=1
    ):
        numbers.pop("loss_rerun_gap", None)
        out[what] = [name for name, _, _, ok
                     in judge(numbers, TINY_LIMITS) if not ok]
    tune.clear_program_cache()
    return out


@pytest.mark.parametrize("what,fails", [
    ("sound", None),
    # bfloat16 operands under this tiny cell's float32: the precision below.
    ("control", "any"),
    ("unchanged", "param_change_gap_med"),
    # Nearer to the reference with a row left out than to the whole one.
    ("half_batch", "param_change_half_ratio_med"),
])
def test_the_drivers_numbers_tell_a_planted_fault(verdicts, what, fails):
    if fails is None:
        assert verdicts[what] == []
    elif fails == "any":
        assert verdicts[what], f"{what} passed every limit"
    else:
        assert fails in verdicts[what]


def test_the_cell_refuses_a_program_without_its_family(monkeypatch):
    """What the new files do on a tree from before the family (the parent
    commit): exit at once, before any data is made."""
    from distributed_machine_learning_tpu.models import models

    cfg = train_cca_lm.trial_config(type("R", (), {
        "cell": _tiny_cell(), "seed": 1})())
    train_cca_lm.require_program(cfg)  # this program has both
    with pytest.raises(SystemExit, match="not 'cca_moe_lm'"):
        train_cca_lm.require_program(dict(cfg, model="gated_hybrid_lm"))
    monkeypatch.delitem(models._entries, "cca_moe_lm")
    with pytest.raises(SystemExit, match="no model 'cca_moe_lm'"):
        train_cca_lm.require_program(cfg)
