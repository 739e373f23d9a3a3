"""Plain reference of the compressed-convolutional-attention decoder (CCA
over a top-1 mixture of gated experts behind an MLP router, the embedding
tied to the head), of its loss and of its training epoch.

Straight ``jax.numpy`` in float32 with every matrix product at ``highest``
precision: no kernels, no mixed precision, no sorting of tokens.  It
imports nothing of the program.  The layer equations are the reading of
the published ``config.json``'s keys (whose names it reads) and of the two
public descriptions of the family (arXiv:2510.04476, arXiv:2511.17127)
that the configuration file states; what neither fixes is listed there
under ``assumed`` and ``departures``.  What it does in blocks it does only
so that the timed sizes fit a chip and a run's time: attention is dense
over blocks of query rows, the experts are a loop over the ids held with a
mask on the tokens, the head and the softmax take a block of positions at
a time, a batch is worked through in blocks of rows, and the training
step takes the chain rule a layer at a time (``make_step``: one compiled
forward and one compiled ``jax.vjp`` of a layer, called for each layer in
turn; as one program the six-layer step is 425 MB of code for the chip
and three minutes of its compiler).

Parameters are a flat dict of float32 arrays under this file's own names
(``parameter_shapes`` lists them; layer ``i``'s leaves start ``L{i}.``).
``cfg`` is the configuration file: the published keys, ``held_experts`` =
[first id, count] and ``published.num_experts`` (the router's width).
``quant`` is the control's hook: a function applied to both operands of
every matrix product but the router's (identity for the reference; a cast
through fp8 for the precision below the configuration's bfloat16
products; the router is float32 in the configuration too).
"""

from __future__ import annotations

import json
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.regressor import (
    adam_init,
    adam_update,
    fold_path,
    program_seed,
)

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512       # query rows of attention worked at a time
LOSS_BLOCK = 1024       # positions whose logits are held at a time
ROUTER_DEPTH = 2        # hidden layers of the router (``assumed.router``)
_ID = lambda a: a  # noqa: E731


def _sizes(cfg: dict):
    """(d, H, Hkv, hd, t0, t1, router width, f, held, all experts, v)."""
    return (
        int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
        int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
        int(cfg["cca_time0"]), int(cfg["cca_time1"]),
        int(cfg["router_hidden_size"]), int(cfg["moe_intermediate_size"]),
        int(cfg["held_experts"][1]), int(cfg["published"]["num_experts"]),
        int(cfg["vocab_size"]),
    )


def layer_shapes(cfg: dict) -> Dict[str, tuple]:
    """One layer's leaves."""
    d, h, hkv, hd, t0, t1, rh, f, held, e_all, _ = _sizes(cfg)
    g = h + hkv
    out = {
        "in_norm": (d,), "post_norm": (d,),
        "cca.wq": (d, h * hd), "cca.wk": (d, hkv * hd),
        "cca.wv": (d, hkv * hd // 2), "cca.wv_shift": (d, hkv * hd // 2),
        "cca.wo": (h * hd, d),
        "cca.conv0_w": (g * hd, t0), "cca.conv0_b": (g * hd,),
        "cca.conv1_w": (g, t1, hd, hd), "cca.conv1_b": (g * hd,),
        "cca.temperature": (hkv,),
        "moe.router.down_w": (d, rh), "moe.router.down_b": (rh,),
        "moe.router.norm": (rh,), "moe.router.out_w": (rh, e_all),
        "moe.w_gate": (held, d, f), "moe.w_up": (held, d, f),
        "moe.w_down": (held, f, d),
    }
    for j in range(ROUTER_DEPTH):
        out[f"moe.router.h{j}_w"] = (rh, rh)
        out[f"moe.router.h{j}_b"] = (rh,)
    return out


def parameter_shapes(cfg: dict) -> Dict[str, tuple]:
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    out = {"embed": (v, d), "final_norm": (d,)}
    for i in range(int(cfg["num_hidden_layers"])):
        out.update({f"L{i}.{name}": shape
                    for name, shape in layer_shapes(cfg).items()})
    return out


# ---------------------------------------------------------------------------
# Starting weights

INIT_STD = 0.02     # ``assumed.initialisers``


def init_key(seed: int):
    """The key that a run from ``seed`` draws its starting weights under
    (the program's rule for the stream, restated)."""
    return jax.random.key(program_seed(seed, "init"))


def init_params(cfg: dict, root) -> Dict[str, jax.Array]:
    """The weights a run starts from whose ``init_key`` is ``root`` (an
    argument, so that one compiled program serves every seed), drawn here
    from the configuration's ``assumed`` block: normal(0.02) for every
    projection, the embedding, the router's matrices and the experts;
    norms and the temperature from 1; biases of the router from 0; each
    convolution's weight and bias uniform(-b, b) with b = fan-in ** -1/2
    (fan-in ``cca_time0`` for the depth-wise one, ``cca_time1`` x head size
    for the one inside a head).

    A leaf's key is flax's for the leaf's place in the model (as
    ``regressor.init_params`` restates it: the module path and the count
    of the parameter within its module folded into the stream's key), and
    for a layer's leaf flax's under a scan over the layers: the stream's
    key split into one a layer, and, because the scan's body is traced
    twice and the second trace's draws are kept, a module's ``k``-th
    parameter of ``n`` at count ``n + k``."""
    d, h, hkv, hd, t0, t1, rh, f, held, e_all, v = _sizes(cfg)
    g = h + hkv

    def normal(key, shape, *path):
        return jax.random.normal(fold_path(key, *path), shape, jnp.float32) \
            * INIT_STD

    def uniform(key, shape, bound, *path):
        return jax.random.uniform(fold_path(key, *path), shape, jnp.float32,
                                  -bound, bound)

    def layer(key):
        ones, zeros = (lambda n: jnp.ones((n,), jnp.float32),
                       lambda n: jnp.zeros((n,), jnp.float32))
        at = ("layers", "attention")
        moe = ("layers", "moe")
        router = moe + ("router",)
        out = {
            "in_norm": ones(d), "post_norm": ones(d),
            # A projection is a module with the one parameter: count 2.
            "cca.wq": normal(key, (d, h * hd), *at, "q_proj", 2),
            "cca.wk": normal(key, (d, hkv * hd), *at, "k_proj", 2),
            "cca.wv": normal(key, (d, hkv * hd // 2), *at, "v_proj", 2),
            "cca.wv_shift": normal(key, (d, hkv * hd // 2), *at,
                                   "v_shift_proj", 2),
            "cca.wo": normal(key, (h * hd, d), *at, "o_proj", 2),
            # The mixer's own five leaves in the order it makes them.
            "cca.conv0_w": uniform(key, (g * hd, t0), t0 ** -0.5, *at, 6),
            "cca.conv0_b": uniform(key, (g * hd,), t0 ** -0.5, *at, 7),
            "cca.conv1_w": uniform(key, (g, t1, hd, hd), (t1 * hd) ** -0.5,
                                   *at, 8),
            "cca.conv1_b": uniform(key, (g * hd,), (t1 * hd) ** -0.5, *at, 9),
            "cca.temperature": ones(hkv),
            # The expert layer's own three.
            "moe.w_gate": normal(key, (held, d, f), *moe, 4),
            "moe.w_up": normal(key, (held, d, f), *moe, 5),
            "moe.w_down": normal(key, (held, f, d), *moe, 6),
            # A matrix with a bias is the first parameter of two.
            "moe.router.down_w": normal(key, (d, rh), *router, "down", 3),
            "moe.router.down_b": zeros(rh),
            "moe.router.norm": ones(rh),
            "moe.router.out_w": normal(key, (rh, e_all), *router, "out", 2),
        }
        for j in range(ROUTER_DEPTH):
            out[f"moe.router.h{j}_w"] = normal(key, (rh, rh), *router,
                                               f"hidden_{j}", 3)
            out[f"moe.router.h{j}_b"] = zeros(rh)
        return out

    out = {"embed": normal(root, (v, d), 1),
           "final_norm": jnp.ones((d,), jnp.float32)}
    layers = int(cfg["num_hidden_layers"])
    for i, key in enumerate(jax.random.split(root, layers)):
        out.update({f"L{i}.{name}": leaf for name, leaf in layer(key).items()})
    return out


# ---------------------------------------------------------------------------
# Layers


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, quant(a), quant(b), precision=HIGHEST)


def previous(x, by: int):
    """``y[:, t] = x[:, t - by]``, nought before the sequence."""
    if by == 0:
        return x
    return jnp.concatenate(
        [jnp.zeros((x.shape[0], by) + x.shape[2:], x.dtype), x[:, :-by]], axis=1
    )


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


def causal_softmax_attention(q, k, v, scale: float, quant: Callable = _ID,
                             query_block: int = QUERY_BLOCK):
    """q [B, S, H, D] on k, v [B, S, Hkv, D], each key-value head serving
    H // Hkv query heads in a row; dense over blocks of query rows."""
    B, S, h, hd = q.shape
    hkv = k.shape[2]
    q = q.reshape(B, S, hkv, h // hkv, hd)
    rows = min(query_block, S)
    pad = -S % rows
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, rows, 1)
        scores = _mm("bqcgk,btck->bcgqt", qb, k, quant) * scale
        keep = (start + jnp.arange(rows))[:, None] >= jnp.arange(S)[None, :]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return _mm("bcgqt,btck->bqcgk", probs, v, quant)

    out = jax.lax.map(block, jnp.arange(0, S + pad, rows))
    return jnp.moveaxis(out, 0, 1).reshape(B, S + pad, h, hd)[:, :S]


def cca(p: dict, x, cfg: dict, quant: Callable = _ID,
        query_block: int = QUERY_BLOCK):
    """Compressed convolutional attention of the normed hidden state x
    [B, S, d]: the configuration file's ``what`` has the equations."""
    B, S, _ = x.shape
    _, h, hkv, hd, t0, t1, *_ = _sizes(cfg)
    group, g = h // hkv, h + hkv
    rope = cfg["rope_parameters"]["hybrid"]
    rotary_dim = int(hd * float(rope["partial_rotary_factor"]))
    q0 = _mm("bsd,de->bse", x, p["wq"], quant)
    k0 = _mm("bsd,de->bse", x, p["wk"], quant)
    # Two causal convolutions over positions with nothing between them:
    # a filter a channel, then a matrix a head on each of two positions.
    c = jnp.concatenate([q0, k0], -1)
    a = p["conv0_b"] + sum(
        previous(c, t0 - 1 - j) * p["conv0_w"][:, j] for j in range(t0)
    )
    a = a.reshape(B, S, g, hd)
    b = p["conv1_b"].reshape(g, hd) + sum(
        _mm("bsgd,gde->bsge", previous(a, t1 - 1 - j), p["conv1_w"][:, j], quant)
        for j in range(t1)
    )
    # The mean of the values before the convolutions, query with key.
    q0 = q0.reshape(B, S, hkv, group, hd)
    k0 = k0.reshape(B, S, hkv, hd)
    q = b[:, :, :h].reshape(q0.shape) + (q0 + k0[:, :, :, None]) / 2
    k = b[:, :, h:] + (jnp.mean(q0, 3) + k0) / 2
    q = q.reshape(B, S, h, hd)

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True))

    q = unit(q) * hd ** 0.5
    k = unit(k) * hd ** 0.5 * p["temperature"][:, None]
    theta = float(rope["rope_theta"])
    q = partial_rotary(q, rotary_dim, theta)
    k = partial_rotary(k, rotary_dim, theta)
    v = jnp.concatenate(
        [_mm("bsd,de->bse", x, p["wv"], quant),
         _mm("bsd,de->bse", previous(x, 1), p["wv_shift"], quant)], -1
    ).reshape(B, S, hkv, hd)
    out = causal_softmax_attention(q, k, v, hd ** -0.5, quant, query_block)
    return _mm("bse,ed->bsd", out.reshape(B, S, h * hd), p["wo"], quant)


def router_logits(p: dict, x, cfg: dict):
    """The router MLP on tokens x [T, d], float32 at ``highest`` whatever
    ``quant`` is: logits [T, all experts]."""
    def dense(r, name):
        return jnp.einsum("tr,rs->ts", r, p[name + "_w"], precision=HIGHEST)

    r = rms_norm(dense(x, "down") + p["down_b"], p["norm"],
                 float(cfg["rms_norm_eps"]))
    for j in range(ROUTER_DEPTH):
        r = jax.nn.gelu(dense(r, f"h{j}") + p[f"h{j}_b"], approximate=False)
    return dense(r, "out")


def route(p: dict, x, cfg: dict):
    """(weights [T, K], expert ids [T, K]) over all experts: the chosen
    experts' softmax probabilities as they are, not renormalised."""
    probs = jax.nn.softmax(router_logits(p, x, cfg), -1)
    return jax.lax.top_k(probs, int(cfg["num_experts_per_tok"]))


def _gated_mlp(x, w_gate, w_up, w_down, quant):
    hidden = jax.nn.silu(_mm("td,df->tf", x, w_gate, quant)) \
        * _mm("td,df->tf", x, w_up, quant)
    return _mm("tf,fd->td", hidden, w_down, quant)


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def moe(p: dict, x, cfg: dict, held=None, quant: Callable = _ID):
    """What the experts ``held`` = (first id, count; the configuration's
    by default) add for x [B, S, d]: a loop over them, each applied to
    every token and weighted by the router's weight where the token chose
    it, else by nought.  No shared expert."""
    B, S, d = x.shape
    t = x.reshape(B * S, d)
    top_p, top_e = route(_sub(p, "router."), t, cfg)
    first, count = (int(v) for v in (held or cfg["held_experts"]))

    @jax.checkpoint
    def one(e, w_gate, w_up, w_down):
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0.0), -1)
        return weight[:, None] * _gated_mlp(t, w_gate, w_up, w_down, quant)

    # The running sum is no input of the rematerialised part, or the
    # backward pass would keep one copy of it an expert.
    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(*xs), None), jnp.zeros_like(t),
        (first + jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]),
    )
    return y.reshape(B, S, d)


def layer(x, lp: dict, cfg: dict, quant: Callable = _ID):
    """One pre-norm block on x [B, S, d]; ``lp`` is the layer's leaves."""
    eps = float(cfg["rms_norm_eps"])
    h = rms_norm(x, lp["in_norm"], eps)
    x = x + cca(_sub(lp, "cca."), h, cfg, quant)
    h = rms_norm(x, lp["post_norm"], eps)
    return x + moe(_sub(lp, "moe."), h, cfg, quant=quant)


def hidden_states(params: dict, tokens, cfg: dict, quant: Callable = _ID):
    """The final norm's output [B, S, d] for int tokens [B, S]."""
    x = params["embed"][tokens]
    for i in range(int(cfg["num_hidden_layers"])):
        x = jax.checkpoint(
            lambda x, lp: layer(x, lp, cfg, quant)
        )(x, _sub(params, f"L{i}."))
    return rms_norm(x, params["final_norm"], float(cfg["rms_norm_eps"]))


def head_losses(x, table, targets, quant: Callable = _ID,
                position_block: int = LOSS_BLOCK):
    """-log softmax(x table^T)[target] at every position, [B, S]; the
    head and the softmax a block of positions at a time, so that the
    logits of a whole sequence are never held at once."""
    B, S, _ = x.shape
    rows = min(position_block, S)
    pad = -S % rows
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    t = jnp.pad(targets, ((0, 0), (0, pad)))

    @jax.checkpoint
    def block(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows, 1)
        tb = jax.lax.dynamic_slice_in_dim(t, start, rows, 1)
        logp = jax.nn.log_softmax(_mm("bsd,vd->bsv", xb, table, quant), -1)
        return -jnp.take_along_axis(logp, tb[..., None], -1)[..., 0]

    nll = jax.lax.map(block, jnp.arange(0, S + pad, rows))   # [n, B, rows]
    return jnp.moveaxis(nll, 0, 1).reshape(B, S + pad)[:, :S]


def forward(params: dict, tokens, cfg: dict, quant: Callable = _ID):
    """Logits [B, S, V] in float32 for int tokens [B, S]: the head is the
    embedding's transpose."""
    return _mm("bsd,vd->bsv", hidden_states(params, tokens, cfg, quant),
               params["embed"], quant)


def token_losses(params, tokens, targets, cfg, quant: Callable = _ID):
    """Next-token cross-entropy at every position, [B, S]."""
    return head_losses(hidden_states(params, tokens, cfg, quant),
                       params["embed"], targets, quant)


# ---------------------------------------------------------------------------
# Training: the interface ``gated_hybrid_lm.py`` gives its driver


class _Programs(NamedTuple):
    """The compiled pieces of a step and of an evaluation, shared by every
    layer and by every ``rows_used``."""

    lookup: Callable      # (table, tokens) -> x
    layer: Callable       # (x, lp) -> y
    layer_vjp: Callable   # (x, lp, dy) -> (dx, dlp)
    head: Callable        # (x, norm, table, targets, weights, n) -> loss, grads
    lookup_vjp: Callable  # (the head's d_table, tokens, dx) -> d_table
    nll: Callable         # (x, norm, table, targets) -> sum of rows' mean loss


_PROGRAMS: Dict[tuple, Callable] = {}


def _f32(shape=()):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _adam(cfg: dict, total_steps: int, ahead: bool) -> Callable:
    """(params, grads, opt, lr, wd) -> (params, opt): the program's Adam
    over every leaf, the state updated in place; compiled here and now
    where ``ahead``."""
    key = (json.dumps(cfg, sort_keys=True, default=str), total_steps, ahead)
    if key not in _PROGRAMS:
        adam = jax.jit(
            lambda params, grads, opt, lr, wd: adam_update(
                params, grads, opt, lr, wd, total_steps),
            donate_argnums=(0, 1, 2),
        )
        if ahead:
            params = {k: _f32(v) for k, v in parameter_shapes(cfg).items()}
            adam = adam.lower(params, params, jax.eval_shape(adam_init, params),
                              _f32(), _f32()).compile()
        _PROGRAMS[key] = adam
    return _PROGRAMS[key]


def _programs(cfg: dict, quant: Callable, rows: Optional[int] = None,
              seq_len: Optional[int] = None) -> _Programs:
    """The pieces of a step and of an evaluation, jitted; with ``rows`` and
    ``seq_len`` compiled here and now for blocks of that many rows of that
    length."""
    key = (json.dumps(cfg, sort_keys=True, default=str), quant, rows, seq_len)
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    eps = float(cfg["rms_norm_eps"])

    def one_layer(x, lp):
        return layer(x, lp, cfg, quant)

    def losses(x, norm, table, targets):
        return head_losses(rms_norm(x, norm, eps), table, targets, quant)

    def head_loss(x, norm, table, targets, weights, n):
        return jnp.sum(weights[:, None] * losses(x, norm, table, targets)) / n

    # What a call hands over for good is donated: at the timed sizes a
    # second copy of the state does not fit.
    run = _Programs(
        lookup=jax.jit(lambda table, tokens: table[tokens]),
        layer=jax.jit(one_layer),
        layer_vjp=jax.jit(
            lambda x, lp, dy: jax.vjp(one_layer, x, lp)[1](dy),
            donate_argnums=(0, 2),
        ),
        head=jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1, 2)),
                     donate_argnums=(0,)),
        lookup_vjp=jax.jit(
            lambda d_table, tokens, dx: d_table.at[tokens].add(dx),
            donate_argnums=(0, 2),
        ),
        nll=jax.jit(lambda x, norm, table, targets: jnp.sum(
            jnp.mean(losses(x, norm, table, targets), -1))),
    )
    if seq_len is not None:
        d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
        lp = {k: _f32(shape) for k, shape in layer_shapes(cfg).items()}
        tokens = jax.ShapeDtypeStruct((rows, seq_len), jnp.int32)
        x, table, norm = _f32((rows, seq_len, d)), _f32((v, d)), _f32((d,))
        lowered = [piece.lower(*shapes) for piece, shapes in zip(run, (
            (table, tokens), (x, lp), (x, lp, x),
            (x, norm, table, tokens, _f32((rows,)), _f32()),
            (table, tokens, x), (x, norm, table, tokens),
        ))]
        # The pieces are programs of their own: the compiler takes them
        # side by side.
        with ThreadPoolExecutor(max_workers=len(lowered)) as pool:
            run = _Programs(*pool.map(lambda piece: piece.compile(), lowered))
    _PROGRAMS[key] = run
    return run


def _layer_names(cfg: dict):
    return [f"L{i}." for i in range(int(cfg["num_hidden_layers"]))]


def _block_gradients(run: _Programs, cfg, params, xs, ys, weights, n):
    """(this block of rows' share of the loss, of every leaf's gradient):
    the layers forward, the head, then each layer's ``jax.vjp`` from the
    last to the first, the chain rule a layer at a time."""
    kept = [run.lookup(params["embed"], xs)]     # each layer's input
    for p in _layer_names(cfg):
        kept.append(run.layer(kept[-1], _sub(params, p)))
    loss, (dx, d_norm, d_table) = run.head(
        kept.pop(), params["final_norm"], params["embed"], ys, weights, n
    )
    grads = {"final_norm": d_norm}
    for p in reversed(_layer_names(cfg)):
        dx, d_layer = run.layer_vjp(kept.pop(), _sub(params, p), dx)
        grads.update({p + name: g for name, g in d_layer.items()})
    # The table is read twice: the head's gradient and the lookup's.
    grads["embed"] = run.lookup_vjp(d_table, xs, dx)
    return loss, grads


def make_step(cfg: dict, batch_size: int, block_rows: int, total_steps: int,
              quant: Callable = _ID, rows_used: Optional[int] = None,
              seq_len: Optional[int] = None):
    """One optimizer step on a batch of token rows, worked through in
    blocks of rows: mean cross-entropy over the rows used, Adam as the
    program's.  ``rows_used`` plants the fault "the last rows of the batch
    left out, the mean taken over the rest" when it is smaller than the
    batch.  The rows left out are still computed, with weight nought.
    With ``seq_len`` the step's programs are compiled here and now for
    rows of that length (a thread of the caller's can do that while the
    chip works for another), and not at their first call."""
    used = int(rows_used or batch_size)
    block_rows = min(block_rows, batch_size)
    assert batch_size % block_rows == 0, (batch_size, block_rows)
    run = _programs(cfg, quant, *((block_rows, seq_len) if seq_len else ()))
    adam = _adam(cfg, total_steps, seq_len is not None)
    weights = (jnp.arange(batch_size) < used).astype(jnp.float32)

    def step(params, opt, xb, yb, dkey, lr, wd):
        del dkey  # no dropout
        n = jnp.float32(used * xb.shape[1])
        loss, grads = 0.0, None
        for start in range(0, batch_size, block_rows):
            rows = slice(start, start + block_rows)
            part, g = _block_gradients(
                run, cfg, params, xb[rows], yb[rows], weights[rows], n
            )
            loss = loss + part
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        params, opt = adam(params, grads, opt, jnp.float32(lr),
                           jnp.float32(wd))
        return params, opt, loss

    return step


def make_eval(cfg: dict, block_rows: int, quant: Callable = _ID,
              seq_len: Optional[int] = None):
    """Mean cross-entropy over a split, in blocks of rows, with the step's
    own forward programs; compiled here and now with ``seq_len``, as
    ``make_step``."""
    run = _programs(cfg, quant, *((block_rows, seq_len) if seq_len else ()))

    def evaluate(params, x, y):
        total = 0.0
        for start in range(0, x.shape[0], block_rows):
            rows = slice(start, start + block_rows)
            h = run.lookup(params["embed"], x[rows])
            for p in _layer_names(cfg):
                h = run.layer(h, _sub(params, p))
            total = total + run.nll(h, params["final_norm"], params["embed"],
                                    y[rows])
        return total / x.shape[0]

    return evaluate
