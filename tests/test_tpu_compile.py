"""The main path's Pallas kernels compiled for a DESCRIBED v5e chip.

Nothing runs: the TPU compiler installed here compiles for a chip that is
described, not attached, and refuses what the chip's compiler would refuse
(a block the tiling rejects, a kernel past the scoped fast-memory limit) —
what interpret mode and the CPU branch of the models can never show.

The topology is described inside a module-scoped fixture, never at import:
one process at a time may load the TPU library, so only the worker that is
given this file may touch it, and every worker must collect the same tests.
Everything compiles in this process, with the persistent cache off around
it (an entry compiled for a described chip cannot be read back without one).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from distributed_machine_learning_tpu.ops import pallas_attention as pa


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _qkv(sharding, S, *, B=8, H=8, Hkv=None, D=64, dtype=jnp.bfloat16):
    q = jax.ShapeDtypeStruct((B, S, H, D), dtype, sharding=sharding)
    kv = jax.ShapeDtypeStruct((B, S, Hkv or H, D), dtype, sharding=sharding)
    return q, kv, kv


def _flash_loss(causal=False):
    def loss(q, k, v):
        out = pa.flash_attention(q, k, v, None, causal)
        return out.astype(jnp.float32).sum()

    return loss


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_yields(compiled):
    """What each ``tpu_custom_call`` of a compiled program yields: the text
    between ``=`` and ``custom-call(`` of its HLO line, layouts and all."""
    return [
        line.split(" custom-call(")[0].split("=", 1)[1]
        for line in compiled.as_text().splitlines()
        if "tpu_custom_call" in line and " custom-call(" in line
    ]


# The `train` phase of chip_smoke.py runs B 8, S 2048, 8 heads x 64.
TRAIN_SHAPES = {
    "bf16": dict(),
    "f32": dict(dtype=jnp.float32),
    "grouped_kv_8to2": dict(Hkv=2),
    "head_dim_128": dict(H=4, D=128),
    "S4096": dict(S=4096),
}


@pytest.mark.parametrize("name", sorted(TRAIN_SHAPES))
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_forward_compiles(one_chip, name, causal):
    kw = dict(TRAIN_SHAPES[name])
    q, k, v = _qkv(one_chip, kw.pop("S", 2048), **kw)
    compiled = _compile(
        lambda q, k, v: pa.flash_attention(q, k, v, None, causal), q, k, v
    )
    assert compiled.as_text().count("tpu_custom_call") == 1
    # The forward is the kernel that yields float32 row statistics beside
    # its output: benchmark/metrics/flash_fwd_roofline.json finds it so.
    (yields,) = _kernel_yields(compiled)
    assert yields.count("f32[") == (2 if kw.get("dtype") == jnp.float32 else 1)
    # lse is [B, H, 1, S]: its block (1, c, 1, block_q) holds the column
    # block's heads.
    assert f"f32[8,{kw.get('H', 8)},1,{q.shape[1]}]" in yields


@pytest.mark.parametrize("name", sorted(TRAIN_SHAPES))
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_backward_compiles(one_chip, name, causal):
    """Forward + dK/dV + dQ: three kernels in the gradient program."""
    kw = dict(TRAIN_SHAPES[name])
    q, k, v = _qkv(one_chip, kw.pop("S", 2048), **kw)
    compiled = _compile(
        jax.grad(_flash_loss(causal), argnums=(0, 1, 2)), q, k, v
    )
    assert compiled.as_text().count("tpu_custom_call") == 3
    # One kernel yields float32 (the forward's lse) and two yield gradients
    # in the input's dtype only, which is how flash_fwd_roofline.json and
    # flash_bwd_roofline.json tell them apart (``yields`` / ``yields_no``
    # "f32["). With float32 inputs everything is f32 and nothing matches;
    # the benchmark's cells are bfloat16.
    if kw.get("dtype", jnp.bfloat16) == jnp.bfloat16:
        with_f32 = ["f32[" in y for y in _kernel_yields(compiled)]
        assert sorted(with_f32) == [False, False, True]


@pytest.mark.parametrize("S", [50, 96, 100, 200, 500, 1536])
def test_flash_compiles_at_search_space_lengths(one_chip, S):
    """examples/hpo_full.py's max_seq_length choices that the kernel can
    tile (whole-axis blocks below the caps; 1536 = 3 x 512)."""
    q, k, v = _qkv(one_chip, S, B=2)
    compiled = _compile(jax.grad(_flash_loss(), argnums=(0, 1, 2)), q, k, v)
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("S,dtype,causal", [
    (1000, jnp.float32, False), (1000, jnp.bfloat16, True),
    (2000, jnp.bfloat16, False), (2000, jnp.float32, False),
])
def test_untileable_length_raises_named_error(one_chip, S, dtype, causal):
    """1000 and 2000 have no divisor that is a multiple of 128 under the
    backward's caps (2000 under any; 1000 under the 512 of float32 operands
    or a mask): asked for by name, the kernel says so at trace time — never
    the lowering's own block-shape error, and nothing is padded."""
    q, k, v = _qkv(one_chip, S, B=2, dtype=dtype)
    with pytest.raises(ValueError, match=f"cannot tile seq len {S}"):
        _compile(jax.grad(_flash_loss(causal), argnums=(0, 1, 2)), q, k, v)
    assert not pa.flash_can_tile(S, 64)


def test_untileable_1000_forward_alone_compiles(one_chip):
    """Forward-only (eval/serve) S=1000 fits one whole-axis block under the
    forward cap; only the backward's 512 cap refuses it."""
    q, k, v = _qkv(one_chip, 1000, B=2, dtype=jnp.float32)
    _compile(lambda q, k, v: pa.flash_attention(q, k, v), q, k, v)


def test_1000_fits_the_unmasked_bfloat16_backward_whole(one_chip):
    """Under that body's 1024 cap S=1000 is one whole-axis block, forward
    and backward; the automatic routes still decline it (``flash_can_tile``
    answers for every dtype and mask, and is not asked about one)."""
    q, k, v = _qkv(one_chip, 1000, B=2)
    compiled = _compile(jax.grad(_flash_loss(), argnums=(0, 1, 2)), q, k, v)
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("S,D,ok", [
    (2048, 64, True), (4096, 64, True), (1024, 64, True), (1536, 64, True),
    (96, 64, True), (1000, 64, False), (2000, 64, False), (1096, 64, False),
])
def test_flash_can_tile_matches_block_rule(S, D, ok):
    """Pure shape rule (no compiler): what the automatic routes ask."""
    assert pa.flash_can_tile(S, D) is ok


def test_automatic_routes_decline_untileable(monkeypatch):
    """On a TPU the softmax->flash, ring and Ulysses routes select the
    kernel only for an S it can tile (the backend answer is steered here;
    the routes themselves are the code under test)."""
    import importlib

    from distributed_machine_learning_tpu.models import layers

    # The package re-exports the ring_attention FUNCTION under the module's
    # own name, so the module is fetched by its dotted path.
    ring_mod = importlib.import_module(
        "distributed_machine_learning_tpu.parallel.ring_attention"
    )
    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert layers._route_softmax_to_flash(2048, 64)
    assert not layers._route_softmax_to_flash(2000, 64)
    assert not layers._route_softmax_to_flash(1000, 64)
    assert ring_mod._use_flash_inner("auto", 2048, 2048, 64)
    assert not ring_mod._use_flash_inner("auto", 2000, 2000, 64)


def test_ring_chunk_kernels_compile_at_quarter_length(topo):
    """Ring attention at sp 4 over S 8192: the per-shard chunk kernels run
    at S/4 = 2048 inside shard_map, forward and backward, with the kv
    rotation as collective-permutes."""
    from distributed_machine_learning_tpu.parallel.ring_attention import (
        ring_attention,
    )

    mesh = Mesh(np.array(topo.devices), ("sp",))
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    q, k, v = _qkv(sharding, 8192, B=1)

    def ring(q, k, v):
        return ring_attention(q, k, v, mesh, use_flash=True)

    fwd = _compile(ring, q, k, v).as_text()
    assert "tpu_custom_call" in fwd and "collective-permute" in fwd
    bwd = _compile(
        jax.grad(lambda q, k, v: ring(q, k, v).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2)),
        q, k, v,
    ).as_text()
    assert bwd.count("tpu_custom_call") == 3 and "collective-permute" in bwd


def test_partitioned_model_keeps_the_kernel_out(topo, monkeypatch):
    """Under a dp x tp mesh the program is GSPMD-partitioned and the
    compiler refuses a bare Mosaic kernel ("cannot be automatically
    partitioned"): the automatic softmax->flash route must decline there,
    and attention_type='flash' by name must raise the named ValueError."""
    from distributed_machine_learning_tpu.models import build_model, layers
    from distributed_machine_learning_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    mesh = make_mesh({"dp": 2, "tp": 2}, list(topo.devices))
    config = {
        "model": "transformer", "d_model": 512, "num_heads": 8,
        "num_layers": 1, "dim_feedforward": 2048, "max_seq_length": 2048,
        "dropout": 0.0, "mesh": mesh,
    }
    x = jax.ShapeDtypeStruct(
        (8, 2048, 16), jnp.float32,
        sharding=NamedSharding(mesh, P("dp", None, None)),
    )
    rngs = jax.eval_shape(
        lambda: {"params": jax.random.key(0), "dropout": jax.random.key(1)}
    )

    def forward_of(model):
        variables = jax.eval_shape(
            lambda r, x: model.init(r, x, deterministic=True), rngs, x
        )
        replicated = NamedSharding(mesh, P())
        variables = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=replicated
            ),
            variables,
        )
        return (
            lambda v, x: model.apply(v, x, deterministic=True), variables
        )

    fn, variables = forward_of(build_model(dict(config)))
    text = _compile(fn, variables, x).as_text()
    assert "tpu_custom_call" not in text

    with pytest.raises(ValueError, match="cannot be partitioned"):
        forward_of(build_model(dict(config, attention_type="flash")))


def _copies_of(text, elements):
    """The copies (fused or not) of a compiled program whose result holds
    ``elements`` entries: at train_s2048's shapes, an attention activation
    in any of its layouts."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= (?:bf16|f32)\[([\d,]+)\]\{[^}]*\} copy\(", line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) == elements:
            found.append(line)
    return found


def test_attention_layer_feeds_the_kernels_its_own_layout(one_chip, monkeypatch):
    """train_s2048's attention layer (d_model 512, 8 heads x 64, S 2048,
    batch 32, bfloat16, the softmax route to the kernels): the flat
    projections' [B, S, H*D] goes into the kernels and their output into
    the out projection as it is. No copy of an attention activation in the
    forward; in the gradient at most the out projection's two weight-
    gradient copies (the parent: 4 and 8 copies of bf16[32,8,2048,64])."""
    from distributed_machine_learning_tpu.models import layers

    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    m = layers.MultiHeadAttention(d_model=512, num_heads=8, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((32, 2048, 512), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda x: m.init({"params": jax.random.key(0)}, x), x),
    )

    def loss(p, x):
        return m.apply(p, x).astype(jnp.float32).sum()

    fwd = _compile(m.apply, params, x).as_text()
    assert fwd.count("tpu_custom_call") == 1
    assert _copies_of(fwd, 32 * 2048 * 512) == []
    grad = _compile(jax.grad(loss, argnums=(0, 1)), params, x).as_text()
    assert grad.count("tpu_custom_call") == 3
    copies = _copies_of(grad, 32 * 2048 * 512)
    assert len(copies) <= 2, copies
    assert all("/out/dot_general" in c for c in copies), copies


@pytest.mark.parametrize("window", [4096, 1000])
def test_window_kernels_compile_at_the_smallthinker_cell(one_chip, window):
    """Forward + dK/dV + dQ under a window at train_smallthinker_s16384's
    call ([1, 16384, 28:4, 128], a group of 7) and at a window that is no
    multiple of a block; the forward still the one kernel that yields a
    float32 (the window metrics' match)."""
    q, k, v = _qkv(one_chip, 16384, B=1, H=28, Hkv=4, D=128)

    def loss(q, k, v):
        out = pa.flash_attention(q, k, v, None, True, window=window)
        return out.astype(jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert ["f32[" in y for y in _kernel_yields(compiled)].count(True) == 1
    assert compiled.as_text().count("tpu_custom_call") == 3


# The causal and full kernels' lowered text (value and gradient) at the
# three older cells' calls, from the parent commit's tree on the sandbox
# (jax 0.9.0, described v5e), with the source locations that Mosaic
# serializes into a kernel's body left out: the window is an argument
# whose default leaves every kernel as it was.
PARENT_KERNEL_SHA256 = {
    (32, 2048, 8, 8, 64, False): "a06da29ebde0350b",
    (2, 8192, 8, 2, 128, True): "c6a3ef3a48a6af63",
    (2, 8192, 16, 2, 256, True): "5c28c662a45af4de",
}


@pytest.mark.parametrize("call", sorted(PARENT_KERNEL_SHA256))
def test_the_kernels_without_a_window_lower_as_the_parents(
        one_chip, call, monkeypatch):
    import hashlib

    from jax._src import tpu_custom_call
    from jax._src.lib.mlir import ir

    lower = tpu_custom_call._lower_mosaic_module_to_asm

    def without_locations(module, **kwargs):
        with module.context:
            bare = ir.Module.parse(
                module.operation.get_asm(enable_debug_info=False))
        return lower(bare, **kwargs)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm",
                        without_locations)
    B, S, H, Hkv, D, causal = call
    q, k, v = _qkv(one_chip, S, B=B, H=H, Hkv=Hkv, D=D)
    text = jax.jit(jax.value_and_grad(_flash_loss(causal), argnums=(0, 1, 2))
                   ).lower(q, k, v).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_KERNEL_SHA256[call]


# -- the gated delta rule's kernels ------------------------------------------

def test_gated_delta_kernels_compile_at_the_q3next_cell(one_chip):
    """Forward (with the states the backward reads) and backward at
    train_q3next_s8192's call: [2, 8192], 16 key heads serving 32 value
    heads of 128, bfloat16 products.  The backward is the one kernel that
    yields a bfloat16 (dv), which the roofline metrics' matches rely on."""
    from distributed_machine_learning_tpu.ops import pallas_gated_delta as pg

    B, S, hk, hv, D = 2, 8192, 16, 32, 128
    f32 = jnp.float32
    q = jax.ShapeDtypeStruct((B, S, hk * D), f32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((B, S, hv * D), jnp.bfloat16, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((B, hv, S // 64, 64), f32, sharding=one_chip)

    def loss(q, k, v, gc, beta):
        return pg.gated_delta_chunks(
            q, k, v, gc, beta, D, jnp.dtype(jnp.bfloat16), False).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                        q, q, v, rows, rows)
    yields = _kernel_yields(compiled)
    assert len(yields) == 2
    assert ["bf16[" in y for y in yields].count(True) == 1


def test_the_epoch_program_runs_the_rule_in_kernels_under_its_scope(
        one_chip, monkeypatch):
    """A one-layer gated_hybrid_lm whose linear layer has heads of 128:
    its epoch program (two steps) holds the rule's forward, its
    rematerialisation and its backward as the kernels ``gated_delta_fwd``
    and ``gated_delta_bwd``, every one under a source path that holds the
    scope ``gated_delta``, so ``readers/scope_share.py`` counts them in
    ``gdn_share_pct.lm``."""
    import optax

    from benchmark.drivers import train_lm
    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.ops import gated_delta
    from distributed_machine_learning_tpu.ops.losses import get_loss
    from distributed_machine_learning_tpu.tune._regression_program import (
        make_epoch_fn,
        make_forward,
    )

    monkeypatch.setattr(gated_delta, "_on_tpu", lambda: True)
    model = build_model({
        "model": "gated_hybrid_lm", "vocab_size": 97, "num_layers": 1,
        "d_model": 128, "full_attention_interval": 2,
        "linear_key_heads": 1, "linear_value_heads": 2,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "num_experts": 4, "top_k": 2, "expert_width": 64,
        "shared_width": 64, "expert_tile": 16, "compute_dtype": "bfloat16",
    })
    tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32, sharding=one_chip)
    params = jax.eval_shape(
        lambda t: model.init({"params": jax.random.key(0)}, t[:1])["params"],
        tokens)
    tx = optax.adam(1e-3)
    on_chip = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip))
    epoch = make_epoch_fn(make_forward(model, "deterministic", False), tx,
                          get_loss("cross_entropy"), 4, 2, 2)
    text = _compile(epoch, on_chip(params),
                    on_chip(jax.eval_shape(tx.init, params)), {}, tokens,
                    tokens, jax.random.key(0)).as_text()
    sources = dict(train_lm._INSTRUCTION_SOURCE.findall(text))
    kernels = {name: path for name, path in sources.items()
               if name.startswith("gated_delta_")}
    assert sorted(n.rsplit(".", 1)[0] for n in kernels) == [
        "gated_delta_bwd", "gated_delta_fwd", "gated_delta_fwd"]
    inside = re.compile(r"(^|[/(])gated_delta(/|\)|$)")
    assert all(inside.search(path) for path in kernels.values()), kernels
