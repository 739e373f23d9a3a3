"""The benchmark's files for the language-model cell: the cost functions,
the readers that this cell brought, and the cell's entries."""

import importlib
import json
import os

import pytest

from benchmark import flops, flops_gdn, flops_lm
from benchmark.readers import kernel_roofline_cfg, kernel_roofline_from, scope_share
from benchmark.run import Cell, Run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "train_q3next_s8192"


@pytest.fixture(scope="module")
def cell():
    return Cell.load(ROOT, CELL)


def test_cell_loads_with_its_files(cell):
    assert cell.chips == 1 and cell.traffic["driver"] == "train_lm"
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
    assert {"gdn_share_pct.lm", "moe_share_pct.lm", "attn_share_pct.lm",
            "flash_causal_fwd_roofline", "flash_causal_bwd_roofline",
            "expert_load_max_over_mean.lm", "expert_pairs_per_step.lm",
            "step_mfu", "peak_hbm_pct.train"} <= names
    assert not any(n.startswith("ckpt_") for n in names)


def test_configuration_keeps_every_published_width(cell):
    """The catalog row's numbers, key for key, but the three in
    ``reduced``; the trial's widths are the published ones."""
    cfg = cell.config
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936,
    }
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
            assert cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    trial = cfg["trial"]
    assert (trial["d_model"], trial["head_dim"], trial["expert_width"],
            trial["num_experts"], trial["top_k"]) == (2048, 256, 512, 512, 10)
    assert trial["rotary_dim"] == cfg["head_dim"] * cfg["partial_rotary_factor"]
    assert trial["held_experts"] == cfg["held_experts"] == [0, cfg["num_experts"]]
    assert trial["vocab_size"] == cfg["vocab_size"] == 18992
    assert trial["checkpoint_freq"] == 0


def test_train_flops_count_what_the_shapes_ask(cell):
    cfg = cell.config
    pairs = 8192 * 10 * 32 / 512
    whole = flops_lm.forward_flops_per_sequence(cfg, 8192, pairs)
    # The head alone: 2 x S x d x V.
    head = 2 * 8192 * 2048 * 18992
    assert head < whole < 20 * head
    # More pairs routed here cost 3 x 2 x d x f each, in every layer.
    more = flops_lm.forward_flops_per_sequence(cfg, 8192, pairs + 100)
    assert more - whole == pytest.approx(4 * 100 * 6 * 2048 * 512)
    assert flops_lm.train_flops_per_sequence(cfg, 8192, pairs) == 3 * whole
    # Attention's square grows fourfold with the length, the rest twofold.
    short = flops_lm.forward_flops_per_sequence(cfg, 4096, pairs / 2)
    assert 2 * short < whole < 4 * short


def test_causal_flash_costs_are_half_the_square_with_grouped_kv():
    call = dict(batch=2, seq_len=8192, heads=16, head_dim=256)
    full_ops, full_bytes = flops.flash_forward(**call)
    ops, nbytes = flops_lm.flash_causal_forward(kv_heads=2, **call)
    assert ops == full_ops / 2
    assert nbytes < full_bytes  # k and v are read at 2 heads, not 16
    same, same_bytes = flops_lm.flash_causal_forward(kv_heads=16, **call)
    assert same_bytes == full_bytes
    bwd_ops, _ = flops_lm.flash_causal_backward(kv_heads=2, **call)
    assert bwd_ops == 2.5 * ops


def test_scope_share_counts_self_time_under_the_scope():
    from benchmark import trace as T

    run = Run(cell=None, seed=0, seconds=0, traced=True, devices=[],
              work_dir="", peaks=None)
    # A while of 10 s holding 4 s under the scope and 5 s of another's;
    # then 2 s of the backward outside the loop; then an evaluation program
    # whose %fusion.1 is another operation than the epoch's %fusion.1.
    dev = T.DeviceTrace(
        ops=[
            ("%while.1 = (s32[]) while(...)", 0.0, 10.0),
            ("%fusion.1 = f32[8] fusion(...)", 0.0, 4.0),
            ("%fusion.2 = f32[8] fusion(...)", 4.0, 5.0),
            ("%fusion.3 = f32[8] fusion(...)", 10.0, 2.0),
            ("%fusion.1 = f32[8] fusion(...)", 12.0, 8.0),
        ],
        modules=[("jit_epoch(123)", 0.0, 12.0), ("jit_evaluate(9)", 12.0, 8.0)],
    )
    run.trace = T.Trace(devices={"d": dev})
    run.trace_window = (0.0, 20.0)
    run.facts["op_sources"] = {
        "jit_epoch": {
            "while.1": "jit(epoch)/while",
            "fusion.1": "jit(epoch)/while/body/layer_0/linear_attention/gated_delta/dot_general",
            "fusion.2": "jit(epoch)/while/body/layer_0/moe/routed_experts/sort",
            "fusion.3": "jit(epoch)/transpose(jvp(gated_delta))/mul",
        },
        "jit_evaluate": {"fusion.1": "jit(evaluate)/gated_delta_other/mul"},
    }
    assert scope_share.read({"scope": "gated_delta"}, run) == pytest.approx(
        100.0 * 6.0 / 20.0)
    assert scope_share.read({"scope": "routed_experts"}, run) == pytest.approx(
        100.0 * 5.0 / 20.0)
    # A program without the scope, a driver that gave no sources, an
    # untraced run: nothing to read, and no error.
    assert scope_share.read({"scope": "absent_scope"}, run) is None
    del run.facts["op_sources"], run.facts["scope_self_seconds"]
    assert scope_share.read({"scope": "gated_delta"}, run) is None
    run.trace = None
    assert scope_share.read({"scope": "gated_delta"}, run) is None


def test_op_sources_reads_the_loaded_programs_text():
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train_lm

    @jax.jit
    def scoped_program(x):
        with jax.named_scope("gated_delta"):
            return (jnp.sin(x) @ x).sum()

    scoped_program(jnp.ones((8, 8))).block_until_ready()
    sources = train_lm.op_sources(jax.devices()[:1])
    paths = sources["jit_scoped_program"].values()
    assert any("/gated_delta/" in p for p in paths)


def test_kernel_roofline_takes_its_cost_module_from_the_metric_file():
    from benchmark import trace as T

    fwd = ('%causal_attention.7 = (bf16[32,8192,256]{2,1,0}, f32[32,1,8192]{2,1,0}) '
           'custom-call(bf16[32,8192,256] %q), custom_call_target="tpu_custom_call"')
    run = Run(cell=None, seed=0, seconds=0, traced=True, devices=[None],
              work_dir="", peaks={"bf16_flops_per_s": 1e14,
                                  "hbm_bytes_per_s": 1e12})
    run.trace = T.Trace(devices={"d": T.DeviceTrace(ops=[(fwd, 0.0, 0.1)])})
    run.trace_window = (0.0, 1.0)
    call = dict(batch=2, seq_len=8192, heads=16, kv_heads=2, head_dim=256)
    run.facts["attention_call"] = call
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "flash_causal_fwd_roofline.json")) as f:
        spec = json.load(f)
    ops, _ = flops_lm.flash_causal_forward(**call)
    assert kernel_roofline_from.read(spec, run) == pytest.approx(
        100.0 * (ops / 1e14) / 0.1)
    # The backward's file does not match a forward event.
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "flash_causal_bwd_roofline.json")) as f:
        assert kernel_roofline_from.read(json.load(f), run) is None


def test_the_norms_taken_on_every_core_are_the_train_drivers(monkeypatch):
    """``train_lm.numbers`` works a leaf through in pieces on several
    threads; every number it reads is ``train.numbers``' own (float64
    sums, so to round-off), a leaf of one piece or of several."""
    import numpy as np

    from benchmark.drivers import train, train_lm

    monkeypatch.setattr(train_lm, "_CHUNK", 7)
    monkeypatch.setattr(train_lm, "_BLOCK", 3)
    rng = np.random.default_rng(5)

    def side(scale):
        return {
            "train_loss": 1.0 + scale, "val_loss": 2.0 - scale,
            "mu": {"a": rng.normal(size=(5, 9)).astype(np.float32) * scale,
                   "b": rng.normal(size=(3,)).astype(np.float32)},
            "dparam": {"a": rng.normal(size=(5, 9)).astype(np.float32),
                       "b": rng.normal(size=(3,)).astype(np.float32) * scale},
        }

    got, want, half = side(1.0), side(1.1), side(0.7)
    mine, theirs = (f(got, want, half) for f in (train_lm.numbers, train.numbers))
    assert set(mine) == set(theirs)
    for name, value in theirs.items():
        assert mine[name] == pytest.approx(value, rel=1e-12), name
    # The starting weights' gap, in pieces too: the worst leaf's worst entry.
    far = dict(want["mu"], a=want["mu"]["a"] + 0.0)
    far["a"][4, 8] += 0.5
    assert train_lm.init_gap(far, want["mu"]) == pytest.approx(
        0.5 / float(np.abs(want["mu"]["a"]).max()), rel=1e-6)
    assert train_lm.init_gap(want["mu"], want["mu"]) == 0.0


def test_the_gated_delta_costs_are_flops_lms_chunk_rule_term(cell):
    """With no model width (``hidden_size`` 0: no projection, expert or
    head) and no attention layer, ``flops_lm`` counts the chunked rule
    alone, layer by layer: the kernels' forward call is that term over the
    batch, the backward twice it; the bytes are the call's inputs read
    and its outputs written once, at the configuration's dtypes."""
    cfg = dict(cell.config, hidden_size=0, full_attention_interval=5)
    rule_only = flops_lm.forward_flops_per_sequence(cfg, 8192, 0.0)
    traffic = cell.traffic
    ops, nbytes = flops_gdn.gdn_forward(cell.config, traffic)
    assert ops == pytest.approx(traffic["batch_size"] * rule_only / 4)
    bwd_ops, bwd_bytes = flops_gdn.gdn_backward(cell.config, traffic)
    assert bwd_ops == 2 * ops
    qk = 2 * 2 * 8192 * 16 * 128 * 4          # float32 at the key heads
    v, o = 2 * 8192 * 32 * 128 * 2, 2 * 8192 * 32 * 128 * 4
    g_beta = 2 * 2 * 8192 * 32 * 4
    assert nbytes == qk + v + g_beta + o
    assert bwd_bytes == 2 * (qk + v + g_beta) + o


def test_the_gated_delta_rooflines_tell_forward_from_backward(cell):
    """Each metric's match takes its own kernel's events alone (the
    backward is the one that yields a bfloat16), costs the call from the
    cell's files, and reads nothing from a trace without the kernels."""
    from benchmark import trace as T

    fwd = ('%gated_delta_fwd.1 = (f32[2,8192,4096]{2,1,0}, '
           'f32[2,32,16,128,128]{4,3,2,1,0}) custom-call(f32[2,8192,2048] %q), '
           'custom_call_target="tpu_custom_call"')
    bwd = ('%gated_delta_bwd.1 = (f32[2,8192,2048]{2,1,0}, '
           'f32[2,8192,2048]{2,1,0}, bf16[2,8192,4096]{2,1,0}, '
           'f32[2,32,128,64]{3,2,1,0}, f32[2,32,128,64]{3,2,1,0}) '
           'custom-call(f32[2,8192,2048] %q), '
           'custom_call_target="tpu_custom_call"')
    flash = ('%causal_attention.7 = (bf16[2,8192,4096]{2,1,0}, '
             'f32[2,16,1,8192]{3,2,1,0}) custom-call(bf16[2,8192,4096] %q), '
             'custom_call_target="tpu_custom_call"')
    peaks = {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12}
    run = Run(cell=cell, seed=0, seconds=0, traced=True, devices=[None],
              work_dir="", peaks=peaks)
    run.trace = T.Trace(devices={"d": T.DeviceTrace(ops=[
        (fwd, 0.0, 0.01), (fwd, 0.02, 0.01), (bwd, 0.04, 0.03),
        (flash, 0.08, 0.01)])})
    run.trace_window = (0.0, 1.0)

    def spec(name):
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            return json.load(f)

    for name, cost, calls, seconds in (
            ("gdn_fwd_roofline", flops_gdn.gdn_forward, 2, 0.02),
            ("gdn_bwd_roofline", flops_gdn.gdn_backward, 1, 0.03)):
        floor, _ = flops.roofline_floor_s(*cost(cell.config, cell.traffic),
                                          peaks)
        assert kernel_roofline_cfg.read(spec(name), run) == pytest.approx(
            100.0 * floor * calls / seconds)
    # The causal flash forward's match does not take the rule's kernels.
    flash_spec = spec("flash_causal_fwd_roofline")["match"]
    assert T.kernel_seconds(run.trace.devices["d"], (0.0, 1.0),
                            **flash_spec) == (pytest.approx(0.01), 1)
    run.trace = T.Trace(devices={"d": T.DeviceTrace(ops=[(flash, 0.0, 0.1)])})
    assert kernel_roofline_cfg.read(spec("gdn_fwd_roofline"), run) is None
    assert kernel_roofline_cfg.read(spec("gdn_bwd_roofline"), run) is None
