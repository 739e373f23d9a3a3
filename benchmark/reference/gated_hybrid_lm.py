"""Plain reference of the hybrid decoder (Gated DeltaNet 3 : 1 gated
attention over a top-k mixture of gated experts with a shared expert), of
its loss and of its training epoch.

Straight ``jax.numpy`` in float32 with every matrix product at ``highest``
precision: no kernels, no mixed precision, no chunked recurrence, no
sorting of tokens.  It imports nothing of the program.  It follows the
family's public modelling code (``modeling_qwen3_next.py``) and the
published ``config.json``, whose key names it reads; departures are listed
in the configuration file.  What it does in blocks it does only so that
the timed sizes fit a chip: the delta rule is a scan over positions in
rematerialised blocks, attention is dense over blocks of query rows, the
experts are a loop over the ids held with a mask on the tokens, a batch is
worked through in blocks of rows.

Parameters are a flat dict of float32 arrays under this file's own names
(``parameter_shapes`` lists them).  ``cfg`` is the configuration file:
the published keys, ``held_experts`` = [first id, count] and
``published.num_experts`` (the router's width).  ``quant`` is the
control's hook: a function applied to both operands of every matrix
product but the router's (identity for the reference; a cast through fp8
for the precision below the configuration's bfloat16 products; the router
is float32 in the configuration too).
"""

from __future__ import annotations

import functools
import json
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.regressor import (
    adam_init,
    adam_update,
    fold_path,
    program_seed,
)

HIGHEST = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 128        # positions of the delta rule kept a backward block
QUERY_BLOCK = 512       # query rows of attention worked at a time
LOSS_BLOCK = 1024       # positions whose logits are held at a time
_ID = lambda a: a  # noqa: E731


def layer_kinds(cfg: dict):
    """"linear" or "full" for each layer held: every
    ``full_attention_interval``-th is full attention."""
    every = int(cfg["full_attention_interval"])
    return ["full" if (i + 1) % every == 0 else "linear"
            for i in range(int(cfg["num_hidden_layers"]))]


def parameter_shapes(cfg: dict) -> Dict[str, tuple]:
    d = int(cfg["hidden_size"])
    v = int(cfg["vocab_size"])
    hk, hv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    cw = int(cfg["linear_conv_kernel_dim"])
    h, hkv, hd = (int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    f, fs = (int(cfg["moe_intermediate_size"]),
             int(cfg["shared_expert_intermediate_size"]))
    held = int(cfg["held_experts"][1])
    e_all = int(cfg["published"]["num_experts"])
    out = {"embed": (v, d), "final_norm": (d,), "head": (d, v)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"L{i}."
        out[p + "in_norm"] = (d,)
        out[p + "post_norm"] = (d,)
        if kind == "linear":
            out.update({
                p + "gdn.wq": (d, hk * dk), p + "gdn.wk": (d, hk * dk),
                p + "gdn.wv": (d, hv * dv), p + "gdn.wz": (d, hv * dv),
                p + "gdn.wb": (d, hv), p + "gdn.wa": (d, hv),
                p + "gdn.conv_q": (hk * dk, cw), p + "gdn.conv_k": (hk * dk, cw),
                p + "gdn.conv_v": (hv * dv, cw),
                p + "gdn.A_log": (hv,), p + "gdn.dt_bias": (hv,),
                p + "gdn.o_norm": (dv,), p + "gdn.wo": (hv * dv, d),
            })
        else:
            out.update({
                p + "att.wq": (d, h, hd), p + "att.w_gate": (d, h, hd),
                p + "att.wk": (d, hkv, hd), p + "att.wv": (d, hkv, hd),
                p + "att.q_norm": (hd,), p + "att.k_norm": (hd,),
                p + "att.wo": (h * hd, d),
            })
        out.update({
            p + "moe.router": (d, e_all),
            p + "moe.w_gate": (held, d, f), p + "moe.w_up": (held, d, f),
            p + "moe.w_down": (held, f, d),
            p + "moe.shared.w_gate": (d, fs), p + "moe.shared.w_up": (d, fs),
            p + "moe.shared.w_down": (fs, d), p + "moe.shared.gate": (d, 1),
        })
    return out


# ---------------------------------------------------------------------------
# Starting weights

INIT_STD = 0.02     # the family's initializer_range (``assumed.initialisers``)


def init_key(seed: int):
    """The key that a run from ``seed`` draws its starting weights under
    (the program's rule for the stream, restated)."""
    return jax.random.key(program_seed(seed, "init"))


def init_params(cfg: dict, root) -> Dict[str, jax.Array]:
    """The weights a run starts from whose ``init_key`` is ``root`` (an
    argument, so that one compiled program serves every seed), drawn here
    from the
    configuration's ``assumed`` block: normal(0.02) for every projection,
    the embedding, the head, the router and the experts; zero-centred norms
    from 0 and the linear layer's output norm from 1; the convolution
    uniform(-1/2, 1/2) at width 4 (fan-in ** -1/2); ``A_log`` the log of a
    uniform(1e-3, 16) draw; ``dt_bias`` the inverse softplus of a step
    drawn log-uniformly from [1e-3, 1e-1].

    A leaf's key is flax's for the leaf's place in the model (the stream's
    key from the seed by the program's rule, the module path and the
    count of the parameter within its module folded in, as
    ``regressor.init_params`` does), and a projection that the family
    fuses is one draw at the fused shape, cut here into this file's
    leaves: ``in_proj_qkvz`` = [q | k | v | z], ``in_proj_ba`` = [b | a],
    the convolution [q | k | v], ``q_proj`` a head's query then its gate.
    """
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    hk, hv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    cw = int(cfg["linear_conv_kernel_dim"])
    h, hkv, hd = (int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    f, fs = (int(cfg["moe_intermediate_size"]),
             int(cfg["shared_expert_intermediate_size"]))
    held = int(cfg["held_experts"][1])
    e_all = int(cfg["published"]["num_experts"])
    key_dim, value_dim = hk * dk, hv * dv

    def normal(shape, *path):
        return jax.random.normal(fold_path(root, *path), shape, jnp.float32) \
            * INIT_STD

    def uniform(shape, lo, hi, *path):
        return jax.random.uniform(fold_path(root, *path), shape, jnp.float32,
                                  lo, hi)

    out = {
        "embed": normal((v, d), 1),
        "final_norm": jnp.zeros((d,), jnp.float32),
        "head": normal((d, v), "lm_head", 1),
    }
    for i, kind in enumerate(layer_kinds(cfg)):
        layer, p = f"layer_{i}", f"L{i}."
        out[p + "in_norm"] = jnp.zeros((d,), jnp.float32)
        out[p + "post_norm"] = jnp.zeros((d,), jnp.float32)
        if kind == "linear":
            at = (layer, "linear_attention")
            qkvz = normal((d, 2 * key_dim + 2 * value_dim), *at,
                          "in_proj_qkvz", 1)
            ba = normal((d, 2 * hv), *at, "in_proj_ba", 1)
            # The mixer's own leaves in the order it makes them: the
            # convolution, A_log, dt_bias, the output norm.
            conv = uniform((2 * key_dim + value_dim, cw), -cw ** -0.5,
                           cw ** -0.5, *at, 1)
            cuts = (0, key_dim, 2 * key_dim, 2 * key_dim + value_dim,
                    2 * key_dim + 2 * value_dim)
            for name, lo, hi in zip("qkvz", cuts, cuts[1:]):
                out[p + "gdn.w" + name] = qkvz[:, lo:hi]
            for name, lo, hi in zip("qkv", cuts, cuts[1:]):
                out[p + "gdn.conv_" + name] = conv[lo:hi]
            out[p + "gdn.wb"], out[p + "gdn.wa"] = ba[:, :hv], ba[:, hv:]
            out[p + "gdn.A_log"] = jnp.log(uniform((hv,), 1e-3, 16.0, *at, 2))
            dt = jnp.exp(uniform((hv,), math.log(1e-3), math.log(1e-1),
                                 *at, 3))
            out[p + "gdn.dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            out[p + "gdn.o_norm"] = jnp.ones((dv,), jnp.float32)
            out[p + "gdn.wo"] = normal((value_dim, d), *at, "out_proj", 1)
        else:
            at = (layer, "attention")
            wq = normal((d, h * 2 * hd), *at, "q_proj", 1).reshape(d, h, 2 * hd)
            out[p + "att.wq"], out[p + "att.w_gate"] = wq[..., :hd], wq[..., hd:]
            out[p + "att.wk"] = normal((d, hkv * hd), *at, "k_proj", 1) \
                .reshape(d, hkv, hd)
            out[p + "att.wv"] = normal((d, hkv * hd), *at, "v_proj", 1) \
                .reshape(d, hkv, hd)
            out[p + "att.q_norm"] = jnp.zeros((hd,), jnp.float32)
            out[p + "att.k_norm"] = jnp.zeros((hd,), jnp.float32)
            out[p + "att.wo"] = normal((h * hd, d), *at, "o_proj", 1)
        at = (layer, "moe")
        out[p + "moe.w_gate"] = normal((held, d, f), *at, 1)
        out[p + "moe.w_up"] = normal((held, d, f), *at, 2)
        out[p + "moe.w_down"] = normal((held, f, d), *at, 3)
        out[p + "moe.router"] = normal((d, e_all), *at, "router", 1)
        shared = at + ("shared_expert",)
        out[p + "moe.shared.w_gate"] = normal((d, fs), *shared, "gate_proj", 1)
        out[p + "moe.shared.w_up"] = normal((d, fs), *shared, "up_proj", 1)
        out[p + "moe.shared.w_down"] = normal((fs, d), *shared, "down_proj", 1)
        out[p + "moe.shared.gate"] = normal((d, 1), *at, "shared_expert_gate", 1)
    return out


# ---------------------------------------------------------------------------
# Layers


def zero_centred_rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, quant(a), quant(b), precision=HIGHEST)


def causal_conv_silu(x, w):
    """Depthwise causal convolution over positions, then SiLU: x [B, S, C],
    w [C, W]; position t reads t - W + 1 .. t."""
    width = w.shape[1]
    xp = jnp.concatenate(
        [jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype), x], axis=1
    )
    y = jnp.zeros_like(x)
    for j in range(width):
        y = y + xp[:, j:j + x.shape[1], :] * w[:, j]
    return jax.nn.silu(y)


def gated_delta_recurrence(q, k, v, g, beta, quant: Callable = _ID,
                           block: int = SCAN_BLOCK):
    """The rule position by position: q, k [B, S, H, Dk], v [B, S, H, Dv],
    g, beta [B, S, H]; o [B, S, H, Dv].  The scan runs in blocks whose
    inside is rematerialised in the backward pass, so that one state a
    block is kept and not one a position."""
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    block = min(block, S)
    pad = -S % block
    if pad:
        # beta 0 and g 0: the padding writes nothing and is cut off.
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    n = (S + pad) // block

    def position(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        read = _mm("bhk,bhkv->bhv", k_t, state, quant)
        delta = b_t[..., None] * (v_t - read)
        state = state + _mm("bhk,bhv->bhkv", k_t, delta, quant)
        return state, _mm("bhk,bhkv->bhv", q_t, state, quant)

    @jax.checkpoint
    def run_block(state, xs):
        return jax.lax.scan(position, state, xs)

    def blocks(a):  # [B, S, ...] -> [n, block, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(n, block, *a.shape[1:])

    state0 = jnp.zeros((B, H, Dk, Dv), jnp.float32)
    _, o = jax.lax.scan(run_block, state0,
                        tuple(blocks(a) for a in (q, k, v, g, beta)))
    o = o.reshape(n * block, B, H, Dv)[:S]
    return jnp.moveaxis(o, 0, 1)


def gated_delta_net(p: dict, x, cfg: dict, quant: Callable = _ID):
    B, S, _ = x.shape
    hk, hv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    q = causal_conv_silu(_mm("bsd,de->bse", x, p["wq"], quant), p["conv_q"])
    k = causal_conv_silu(_mm("bsd,de->bse", x, p["wk"], quant), p["conv_k"])
    v = causal_conv_silu(_mm("bsd,de->bse", x, p["wv"], quant), p["conv_v"])
    z = _mm("bsd,de->bse", x, p["wz"], quant).reshape(B, S, hv, dv)
    b = _mm("bsd,dh->bsh", x, p["wb"], quant)
    a = _mm("bsd,dh->bsh", x, p["wa"], quant)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    rep = hv // hk
    q = jnp.repeat(unit(q.reshape(B, S, hk, dk)), rep, axis=2) * dk ** -0.5
    k = jnp.repeat(unit(k.reshape(B, S, hk, dk)), rep, axis=2)
    v = v.reshape(B, S, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = gated_delta_recurrence(q, k, v, g, beta, quant)
    eps = float(cfg["rms_norm_eps"])
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * p["o_norm"]
    o = o * jax.nn.silu(z)
    return _mm("bse,ed->bsd", o.reshape(B, S, hv * dv), p["wo"], quant)


def partial_rotary(x, rotary_dim: int, theta: float):
    """Rotate-half rotary positions on the first ``rotary_dim`` of the head:
    x [B, S, H, D]."""
    S = x.shape[1]
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                         / rotary_dim)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate([rot * cos + turned * sin, rest], -1)


def gated_attention(p: dict, x, cfg: dict, quant: Callable = _ID,
                    query_block: int = QUERY_BLOCK):
    B, S, _ = x.shape
    h, hkv, hd = (int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    eps = float(cfg["rms_norm_eps"])
    rotary_dim = int(hd * float(cfg["partial_rotary_factor"]))
    theta = float(cfg["rope_theta"])
    q = _mm("bsd,dhk->bshk", x, p["wq"], quant)
    gate = _mm("bsd,dhk->bshk", x, p["w_gate"], quant)
    k = _mm("bsd,dhk->bshk", x, p["wk"], quant)
    v = _mm("bsd,dhk->bshk", x, p["wv"], quant)
    q = partial_rotary(zero_centred_rms_norm(q, p["q_norm"], eps), rotary_dim, theta)
    k = partial_rotary(zero_centred_rms_norm(k, p["k_norm"], eps), rotary_dim, theta)
    group = h // hkv
    q = q.reshape(B, S, hkv, group, hd)
    rows = min(query_block, S)
    pad = -S % rows
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    starts = jnp.arange(0, S + pad, rows)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, rows, 1)
        scores = _mm("bqcgk,btck->bcgqt", qb, k, quant) * hd ** -0.5
        q_pos = start + jnp.arange(rows)
        keep = q_pos[:, None] >= jnp.arange(S)[None, :]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return _mm("bcgqt,btck->bqcgk", probs, v, quant)

    out = jax.lax.map(block, starts)            # [n, B, rows, hkv, group, hd]
    out = jnp.moveaxis(out, 0, 1).reshape(B, S + pad, h, hd)[:, :S]
    out = out * jax.nn.sigmoid(gate)
    return _mm("bse,ed->bsd", out.reshape(B, S, h * hd), p["wo"], quant)


def _gated_mlp(x, w_gate, w_up, w_down, quant):
    hidden = jax.nn.silu(_mm("td,df->tf", x, w_gate, quant)) \
        * _mm("td,df->tf", x, w_up, quant)
    return _mm("tf,fd->td", hidden, w_down, quant)


def route(p: dict, x, cfg: dict):
    """(weights [T, K] renormalised, expert ids [T, K]) over all experts."""
    logits = jnp.einsum("td,de->te", x, p["router"], precision=HIGHEST)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, int(cfg["num_experts_per_tok"]))
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_p, top_e


def routed_part(p: dict, x, cfg: dict, held, quant: Callable = _ID):
    """What the experts ``held`` = (first id, count) add for x [T, d]: a
    loop over them, each applied to every token and weighted by the
    router's weight where the token chose it, else by nought."""
    top_p, top_e = route(p, x, cfg)
    first, count = int(held[0]), int(held[1])

    @jax.checkpoint
    def one(e, w_gate, w_up, w_down):
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0.0), -1)
        return weight[:, None] * _gated_mlp(x, w_gate, w_up, w_down, quant)

    # The running sum is no input of the rematerialised part, or the
    # backward pass would keep one copy of it an expert.
    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(*xs), None), jnp.zeros_like(x),
        (first + jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]),
    )
    return y


def shared_part(p: dict, x, quant: Callable = _ID):
    y = _gated_mlp(x, p["shared.w_gate"], p["shared.w_up"], p["shared.w_down"], quant)
    return y * jax.nn.sigmoid(_mm("td,do->to", x, p["shared.gate"], quant))


def moe(p: dict, x, cfg: dict, quant: Callable = _ID):
    B, S, d = x.shape
    t = x.reshape(B * S, d)
    y = routed_part(p, t, cfg, cfg["held_experts"], quant) + shared_part(p, t, quant)
    return y.reshape(B, S, d)


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def hidden_states(params: dict, tokens, cfg: dict, quant: Callable = _ID):
    """The final norm's output [B, S, d] for int tokens [B, S]."""
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"][tokens]
    for i, kind in enumerate(layer_kinds(cfg)):
        lp = _sub(params, f"L{i}.")

        @jax.checkpoint
        def layer(x, lp, kind=kind):
            h = zero_centred_rms_norm(x, lp["in_norm"], eps)
            if kind == "linear":
                h = gated_delta_net(_sub(lp, "gdn."), h, cfg, quant)
            else:
                h = gated_attention(_sub(lp, "att."), h, cfg, quant)
            x = x + h
            h = zero_centred_rms_norm(x, lp["post_norm"], eps)
            return x + moe(_sub(lp, "moe."), h, cfg, quant)

        x = layer(x, lp)
    return zero_centred_rms_norm(x, params["final_norm"], eps)


def forward(params: dict, tokens, cfg: dict, quant: Callable = _ID):
    """Logits [B, S, V] in float32 for int tokens [B, S]."""
    return _mm("bsd,dv->bsv", hidden_states(params, tokens, cfg, quant),
               params["head"], quant)


def token_losses(params, tokens, targets, cfg, quant: Callable = _ID,
                 position_block: int = LOSS_BLOCK):
    """-log softmax(logits)[target] at every position, [B, S]; the head
    and the softmax a block of positions at a time, so that the logits of
    a whole sequence are never held at once."""
    x = hidden_states(params, tokens, cfg, quant)
    B, S, _ = x.shape
    rows = min(position_block, S)
    pad = -S % rows
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    t = jnp.pad(targets, ((0, 0), (0, pad)))

    @jax.checkpoint
    def block(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows, 1)
        tb = jax.lax.dynamic_slice_in_dim(t, start, rows, 1)
        logp = jax.nn.log_softmax(
            _mm("bsd,dv->bsv", xb, params["head"], quant), -1
        )
        return -jnp.take_along_axis(logp, tb[..., None], -1)[..., 0]

    nll = jax.lax.map(block, jnp.arange(0, S + pad, rows))   # [n, B, rows]
    return jnp.moveaxis(nll, 0, 1).reshape(B, S + pad)[:, :S]


# ---------------------------------------------------------------------------
# Training


_STEP_PROGRAMS: Dict[tuple, Callable] = {}


def _shapes(cfg: dict, rows: int, seq_len: int):
    """(parameters, a block of token rows) as shapes, to compile for."""
    params = {k: jax.ShapeDtypeStruct(shape, jnp.float32)
              for k, shape in parameter_shapes(cfg).items()}
    return params, jax.ShapeDtypeStruct((rows, seq_len), jnp.int32)


def _step_program(cfg_key, cfg: dict, batch_size: int, block_rows: int,
                  total_steps: int, quant: Callable, seq_len: Optional[int]):
    """The jitted step shared by every ``rows_used``: how many rows count
    is an argument, so the whole batch and the batch with rows left out
    are one compiled program."""
    key = (cfg_key, batch_size, block_rows, total_steps, quant, seq_len)
    if key in _STEP_PROGRAMS:
        return _STEP_PROGRAMS[key]
    assert batch_size % block_rows == 0, (batch_size, block_rows)

    def batch_loss(params, xb, yb, used):
        counts = (jnp.arange(batch_size) < used).astype(jnp.float32)

        @jax.checkpoint
        def block(start):
            xs = jax.lax.dynamic_slice_in_dim(xb, start, block_rows, 0)
            ys = jax.lax.dynamic_slice_in_dim(yb, start, block_rows, 0)
            ws = jax.lax.dynamic_slice_in_dim(counts, start, block_rows, 0)
            return jnp.sum(ws * jnp.sum(
                token_losses(params, xs, ys, cfg, quant), -1
            ))

        if block_rows == batch_size:
            total = block(0)
        else:
            total = jnp.sum(
                jax.lax.map(block, jnp.arange(0, batch_size, block_rows))
            )
        return total / (used * xb.shape[1])

    # The state is donated: at the timed sizes a second copy does not fit.
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt, xb, yb, lr, wd, used):
        loss, grads = jax.value_and_grad(batch_loss)(params, xb, yb, used)
        params, opt = adam_update(params, grads, opt, lr, wd, total_steps)
        return params, opt, loss

    if seq_len is not None:
        params, rows = _shapes(cfg, batch_size, seq_len)
        step = step.lower(
            params, jax.eval_shape(adam_init, params), rows, rows, 0.0, 0.0, 1.0
        ).compile()
    _STEP_PROGRAMS[key] = step
    return step


def make_step(cfg: dict, batch_size: int, block_rows: int, total_steps: int,
              quant: Callable = _ID, rows_used: Optional[int] = None,
              seq_len: Optional[int] = None):
    """One optimizer step on a batch of token rows, worked through in
    blocks of rows: mean cross-entropy over the rows used, Adam as the
    program's.  ``rows_used`` plants the fault "the last rows of the batch
    left out, the mean taken over the rest" when it is smaller than the
    batch.  The rows left out are still computed, with weight nought.
    With ``seq_len`` the program is compiled here and now for rows of that
    length (a thread of the caller's can do that while the chip works for
    another), and not at its first call."""
    used = float(rows_used or batch_size)
    program = _step_program(
        json.dumps(cfg, sort_keys=True, default=str), cfg, batch_size,
        min(block_rows, batch_size), total_steps, quant, seq_len,
    )

    def step(params, opt, xb, yb, dkey, lr, wd):
        del dkey  # no dropout
        return program(params, opt, xb, yb, lr, wd, used)

    return step


def make_eval(cfg: dict, block_rows: int, quant: Callable = _ID,
              seq_len: Optional[int] = None):
    """Mean cross-entropy over a split, in blocks of rows; compiled here
    and now with ``seq_len``, as ``make_step``."""

    @jax.jit
    def block(params, xb, yb):
        return jnp.sum(jnp.mean(token_losses(params, xb, yb, cfg, quant), -1))

    if seq_len is not None:
        params, rows = _shapes(cfg, block_rows, seq_len)
        block = block.lower(params, rows, rows).compile()

    def evaluate(params, x, y):
        total = 0.0
        for start in range(0, x.shape[0], block_rows):
            total = total + block(
                params, x[start:start + block_rows], y[start:start + block_rows]
            )
        return total / x.shape[0]

    return evaluate
