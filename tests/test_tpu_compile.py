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
    assert f"f32[{8 * kw.get('H', 8)},1,{q.shape[1]}]" in yields


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
