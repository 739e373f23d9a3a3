"""Model zoo + config->model factory.

``build_model(config)`` constructs a model from a trial config dict, deriving
architecture fields from the config keys the reference's search spaces use
(`/root/reference/ray-tune-hpo-regression.py:379-400`).

Families (``config["model"]``): ``transformer``, ``simple_transformer``,
``mlp``, ``cnn1d``, ``resnet18``, ``rnn`` over float windows, and two
decoders over int32 token ids whose trial keys are the fields of their
sizes dataclass with ``vocab_size``, ``num_layers`` and ``compute_dtype``:
``gated_hybrid_lm`` (``HybridSizes``: Gated DeltaNet 3 : 1 gated attention
over a top-k mixture with a shared expert) and ``cca_moe_lm`` (``CCASizes``:
``d_model, num_heads, num_kv_heads, head_dim, rotary_dim, rope_theta,
conv_time0, conv_time1, num_experts, top_k, expert_width, router_hidden,
held_experts, expert_tile``; compressed convolutional attention over a
top-1 mixture behind an MLP router, the embedding tied to the head).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax.numpy as jnp

from distributed_machine_learning_tpu.models.cca_lm import CCAMoELM, CCASizes
from distributed_machine_learning_tpu.models.cnn import CNN1DRegressor
from distributed_machine_learning_tpu.models.hybrid_lm import (
    GatedHybridLM,
    HybridSizes,
)
from distributed_machine_learning_tpu.models.mlp import MLPRegressor
from distributed_machine_learning_tpu.models.moe import MoEFF
from distributed_machine_learning_tpu.models.rnn import RNNRegressor
from distributed_machine_learning_tpu.models.resnet import (
    ResNet18Regressor,
    ResNetRegressor,
)
from distributed_machine_learning_tpu.models.transformer import (
    SimpleTransformerRegressor,
    TransformerRegressor,
)
from distributed_machine_learning_tpu.utils.registry import Registry

models: Registry = Registry("model")

_DTYPE_NAMES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "f32": jnp.float32,
    "bf16": jnp.bfloat16,
}


def compute_dtype_of(config: Dict[str, Any]):
    """Resolve ``config["compute_dtype"]`` to a jnp dtype (None = float32
    promotion, flax's default). One lookup shared by every family builder
    AND the train loops' input staging, so the model's matmul dtype and the
    staged data dtype can never disagree."""
    cd = config.get("compute_dtype")
    if cd is None or not isinstance(cd, str):
        return cd
    try:
        return _DTYPE_NAMES[cd]
    except KeyError:
        raise ValueError(
            f"Unknown compute_dtype {cd!r}; expected one of "
            f"{sorted(_DTYPE_NAMES)}"
        ) from None


@models.register("mlp")
def _build_mlp(config: Dict[str, Any]):
    return MLPRegressor(
        hidden_sizes=tuple(config.get("hidden_sizes", (128, 64))),
        dropout_rate=config.get("dropout", 0.0),
        out_features=config.get("out_features", 1),
        dtype=compute_dtype_of(config),
    )


@models.register("cnn1d")
def _build_cnn(config: Dict[str, Any]):
    return CNN1DRegressor(
        channels=tuple(config.get("channels", (32, 64))),
        kernel_size=config.get("kernel_size", 5),
        dropout_rate=config.get("dropout", 0.0),
        head_hidden=config.get("head_hidden", 64),
        out_features=config.get("out_features", 1),
        dtype=compute_dtype_of(config),
    )


@models.register("transformer")
def _build_transformer(config: Dict[str, Any]):
    d_model = config.get("d_model", 64)
    return TransformerRegressor(
        d_model=d_model,
        num_heads=config.get("num_heads", 4),
        num_layers=config.get("num_encoder_layers", config.get("num_layers", 2)),
        dim_feedforward=config.get("dim_feedforward", d_model * 2),
        dropout_rate=config.get("dropout", 0.1),
        attention_type=config.get("attention_type", "scaled_dot_product"),
        key_dim_scaling=config.get("key_dim_scaling", 0.5),
        depthwise_separable_conv=config.get("depthwise_separable_conv", False),
        attn_kernel_size=config.get("attn_kernel_size", 3),
        stochastic_depth_rate=config.get("stochastic_depth_rate", 0.0),
        feedforward_type=config.get("feedforward_type"),
        num_experts=config.get("num_experts", 8),
        expert_top_k=config.get("expert_top_k", 2),
        capacity_factor=config.get("capacity_factor", 1.25),
        moe_aux_coef=config.get("moe_aux_coef", 1e-2),
        shared_weights=config.get("shared_weights", False),
        max_seq_length=config.get("max_seq_length", 2000),
        out_features=config.get("out_features", 1),
        seq_axis=config.get("seq_axis"),
        seq_parallel_mode=config.get("seq_parallel_mode", "ring"),
        batch_axis=config.get("batch_axis", "dp"),
        head_axis=config.get("head_axis", "tp"),
        mesh=config.get("mesh"),
        dtype=compute_dtype_of(config),
        position_encoding=config.get("position_encoding", "sincos"),
        num_kv_heads=config.get("num_kv_heads"),
        block_size=config.get("block_size"),
        remat=config.get("remat", False),
        remat_policy=config.get("remat_policy"),
    )


@models.register("simple_transformer")
def _build_simple_transformer(config: Dict[str, Any]):
    return SimpleTransformerRegressor(
        d_model=config.get("d_model", 64),
        num_heads=config.get("num_heads", 4),
        num_layers=config.get("num_layers", 2),
        dim_feedforward=config.get("dim_feedforward", 256),
        dropout_rate=config.get("dropout", 0.1),
        max_seq_length=config.get("max_seq_length", 2000),
        dtype=compute_dtype_of(config),
    )


@models.register("resnet18")
def _build_resnet18(config: Dict[str, Any]):
    return ResNet18Regressor(
        out_features=config.get("out_features", 1),
        dtype=compute_dtype_of(config),
    )


@models.register("rnn")
def _build_rnn(config: Dict[str, Any]):
    return RNNRegressor(
        hidden_size=config.get("hidden_size", 64),
        num_layers=config.get("num_layers", 1),
        cell_type=config.get("cell_type", "lstm"),
        dropout_rate=config.get("dropout", 0.0),
        head_hidden_sizes=tuple(config.get("head_hidden_sizes", (64,))),
        out_features=config.get("out_features", 1),
        dtype=compute_dtype_of(config),
    )


def _sizes_from(config: Dict[str, Any], sizes_class) -> Dict[str, Any]:
    """The config's keys that are fields of ``sizes_class``, a language
    model's block widths; ``held_experts`` as the tuple a module takes."""
    sizes = {
        f.name: config[f.name]
        for f in dataclasses.fields(sizes_class) if f.name in config
    }
    if sizes.get("held_experts") is not None:
        sizes["held_experts"] = tuple(int(v) for v in sizes["held_experts"])
    return sizes


@models.register("gated_hybrid_lm")
def _build_gated_hybrid_lm(config: Dict[str, Any]):
    """A decoder LM over int32 token ids (models/hybrid_lm.py).  Every size
    is a config key of ``HybridSizes``'s name; ``held_experts`` is
    ``[first id, count]`` of the experts this chip holds."""
    return GatedHybridLM(
        vocab_size=int(config["vocab_size"]),
        num_layers=int(config.get("num_layers", 4)),
        sizes=HybridSizes(**_sizes_from(config, HybridSizes)),
        dtype=compute_dtype_of(config),
    )


@models.register("cca_moe_lm")
def _build_cca_moe_lm(config: Dict[str, Any]):
    """A decoder LM over int32 token ids whose mixer is compressed
    convolutional attention and whose feed-forward is a top-k mixture
    behind an MLP router, the embedding tied to the head
    (models/cca_lm.py).  Every size is a config key of ``CCASizes``'s name;
    ``held_experts`` is ``[first id, count]`` of the experts this chip
    holds."""
    return CCAMoELM(
        vocab_size=int(config["vocab_size"]),
        num_layers=int(config.get("num_layers", 4)),
        sizes=CCASizes(**_sizes_from(config, CCASizes)),
        dtype=compute_dtype_of(config),
    )


def build_model(config: Dict[str, Any]):
    """Construct a model from a trial config; ``config['model']`` picks the family."""
    return models.get(config.get("model", "transformer"))(config)


__all__ = [
    "models",
    "build_model",
    "compute_dtype_of",
    "MLPRegressor",
    "CCAMoELM",
    "CCASizes",
    "GatedHybridLM",
    "HybridSizes",
    "MoEFF",
    "CNN1DRegressor",
    "TransformerRegressor",
    "SimpleTransformerRegressor",
    "ResNetRegressor",
    "ResNet18Regressor",
    "RNNRegressor",
]
