"""Plain reference of the transformer regressor and of its training epoch.

Straight ``jax.numpy`` in float32 with every matrix product at ``highest``
precision: no kernels, no mixed precision, no vmap over trials.  It imports
nothing of the program and takes nothing the program made.  What it shares
with the program is the published architecture (post-LN encoder, sin/cos
positions, last-token pooling, 128-64-32-16-1 ReLU head), the seed, and
JAX's and flax's own random-number rules, so that a run from the same seed
starts from the same weights, draws the same batches and drops the same
activations:

* a parameter's or a dropout site's key is the module path and a call
  counter hashed and folded into the stream's key (flax's ``LazyRng``);
* weights are ``lecun_normal`` over the flattened (in, out) shape;
* an epoch's key splits into a permutation key and a dropout chain, the
  chain splits once a step (``make_epoch_fn``'s rule, restated here).

``quant`` is the control's hook: a function applied to both operands of
every matrix product (identity for the reference; a cast through fp8 or
bfloat16 for the precision below the configuration's).
"""

from __future__ import annotations

import hashlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
HEAD_WIDTHS = (128, 64, 32, 16, 1)


def program_seed(*parts) -> int:
    """The program's rule for a 31-bit seed from parts (its ``fold_seed``,
    restated): SHA-256 of the parts joined by "/", first four bytes little
    endian, sign bit cleared."""
    h = hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


def program_rng_impl(cfg: dict):
    """The generator behind the program's dropout and shuffling keys (its
    ``rng_impl`` rule, restated): the configuration's choice, and by default
    the hardware generator on a TPU and threefry elsewhere.  None is jax's
    default, threefry."""
    choice = cfg.get("rng_impl", "auto")
    if choice in (None, "auto"):
        return "rbg" if jax.default_backend() == "tpu" else None
    return None if choice == "threefry" else str(choice)


def fold_path(key, *path):
    """flax's rule for a key at a module path: SHA-1 of the path's strings
    and integers, first four bytes big-endian, folded into ``key``."""
    m = hashlib.sha1()
    for part in path:
        if isinstance(part, str):
            m.update(part.encode("utf-8"))
        else:
            m.update(part.to_bytes((part.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], "big"))
    )


def _dense_init(key, path, shape, flat_shape, bias_shape=None):
    """A dense layer as flax starts it: the kernel drawn over the flattened
    (in, out) shape from the path's first key, the bias nought."""
    kernel = jax.nn.initializers.lecun_normal()(
        fold_path(key, *path, 1), flat_shape, jnp.float32
    ).reshape(shape)
    return {"kernel": kernel,
            "bias": jnp.zeros(bias_shape or shape[-1:], jnp.float32)}


def init_params(cfg: dict, params_key, num_features: int) -> dict:
    """The weights ``model.init`` gives for ``params_key``."""
    d, h = int(cfg["d_model"]), int(cfg["num_heads"])
    hd, dff = d // h, int(cfg["dim_feedforward"])
    p = {"input_projection": _dense_init(
        params_key, ("input_projection",), (num_features, d), (num_features, d)
    )}
    for i in range(int(cfg["num_layers"])):
        name = f"layer_{i}"
        attn = {
            proj: _dense_init(
                params_key, (name, "attention", proj), (d, h, hd), (d, d),
                (h, hd),
            )
            for proj in ("query", "key", "value")
        }
        attn["out"] = _dense_init(
            params_key, (name, "attention", "out"), (h, hd, d), (d, d)
        )
        p[name] = {
            "attention": attn,
            "norm1": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
            "ff": {
                "Dense_0": _dense_init(
                    params_key, (name, "ff", "Dense_0"), (d, dff), (d, dff)
                ),
                "Dense_1": _dense_init(
                    params_key, (name, "ff", "Dense_1"), (dff, d), (dff, d)
                ),
            },
            "norm2": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        }
    head, width_in = {}, d
    for j, width in enumerate(HEAD_WIDTHS):
        head[f"Dense_{j}"] = _dense_init(
            params_key, ("head", f"Dense_{j}"), (width_in, width),
            (width_in, width),
        )
        width_in = width
    p["head"] = head
    return p


def sincos_table(length: int, d_model: int) -> np.ndarray:
    pos = np.arange(length, dtype=np.float32)[:, None]
    div = np.exp(
        np.arange(0, d_model, 2, dtype=np.float32) * (-np.log(10000.0) / d_model)
    )
    table = np.zeros((length, d_model), np.float32)
    table[:, 0::2] = np.sin(pos * div)
    table[:, 1::2] = np.cos(pos * div[: d_model // 2])
    return table


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def dropout_masks(cfg: dict, dropout_key, shape):
    """Every dropout site's keep-mask for one batch of ``shape`` [B, S, D],
    as flax draws them from the step's dropout key: one after the
    positions, and in each layer one after attention and one after the
    feed-forward.  None when the rate is nought."""
    rate = float(cfg.get("dropout", 0.1))
    if rate == 0.0:
        return None
    keep = 1.0 - rate
    layers = [f"layer_{i}" for i in range(int(cfg["num_layers"]))]

    def draw(*path):
        return jax.random.bernoulli(fold_path(dropout_key, *path, 1), keep, shape)

    return {
        "positions": draw("PositionalEncoding_0", "Dropout_0"),
        "attention": jnp.stack([draw(n, "attention", "Dropout_0") for n in layers]),
        "ff": jnp.stack([draw(n, "Dropout_0") for n in layers]),
    }


def slice_masks(masks, start, rows: int):
    """The masks of ``rows`` rows of the batch from ``start``."""
    if masks is None:
        return None
    return {
        "positions": jax.lax.dynamic_slice_in_dim(masks["positions"], start, rows, 0),
        "attention": jax.lax.dynamic_slice_in_dim(masks["attention"], start, rows, 1),
        "ff": jax.lax.dynamic_slice_in_dim(masks["ff"], start, rows, 1),
    }


def forward(params, x, cfg: dict, *, masks=None,
            quant: Callable = lambda a: a):
    """Predictions [rows, 1] for ``x`` [rows, S, F] in float32; ``masks``
    (see ``dropout_masks``) turns dropout on."""
    rate = float(cfg.get("dropout", 0.1))
    keep = 1.0 - rate
    d, h = int(cfg["d_model"]), int(cfg["num_heads"])
    hd = d // h
    scale = float(hd) ** -float(cfg.get("key_dim_scaling", 0.5))
    n_layers = int(cfg["num_layers"])

    def mm(spec, a, b):
        return jnp.einsum(spec, quant(a), quant(b), precision=HIGHEST)

    def dropout(a, mask):
        return a if mask is None else jnp.where(mask, a / keep, 0.0)

    x = x.astype(jnp.float32)
    p = params["input_projection"]
    x = mm("bsf,fd->bsd", x, p["kernel"]) + p["bias"]
    x = x + jnp.asarray(sincos_table(x.shape[1], d))[None]
    x = dropout(x, None if masks is None else masks["positions"])

    def layer(x, lp, mask_attn, mask_ff):
        a = lp["attention"]
        q = mm("bsd,dhk->bshk", x, a["query"]["kernel"]) + a["query"]["bias"]
        k = mm("bsd,dhk->bshk", x, a["key"]["kernel"]) + a["key"]["bias"]
        v = mm("bsd,dhk->bshk", x, a["value"]["kernel"]) + a["value"]["bias"]
        logits = mm("bqhk,bthk->bhqt", q, k) * scale
        probs = jax.nn.softmax(logits, axis=-1)
        ctx = mm("bhqt,bthk->bqhk", probs, v)
        out = mm("bqhk,hkd->bqd", ctx, a["out"]["kernel"]) + a["out"]["bias"]
        x = _layer_norm(x + dropout(out, mask_attn), lp["norm1"])
        f = lp["ff"]
        y = jax.nn.relu(mm("bsd,df->bsf", x, f["Dense_0"]["kernel"])
                        + f["Dense_0"]["bias"])
        y = mm("bsf,fd->bsd", y, f["Dense_1"]["kernel"]) + f["Dense_1"]["bias"]
        return _layer_norm(x + dropout(y, mask_ff), lp["norm2"])

    # The layers are alike, so they run as one scanned body (compiled once),
    # and one layer's internals live at a time in the backward pass: the
    # S x S attention weights in float32 are what fills the chip.
    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"layer_{i}"] for i in range(n_layers)],
    )
    if masks is None:
        body = lambda x, lp: (jax.checkpoint(
            lambda x, lp: layer(x, lp, None, None))(x, lp), None)
        x, _ = jax.lax.scan(body, x, stacked)
    else:
        body = lambda x, xs: (jax.checkpoint(layer)(x, *xs), None)
        x, _ = jax.lax.scan(
            body, x, (stacked, masks["attention"], masks["ff"])
        )
    x = x[:, -1, :]
    n_head = len(HEAD_WIDTHS)
    for j in range(n_head):
        p = params["head"][f"Dense_{j}"]
        x = mm("bd,de->be", x, p["kernel"]) + p["bias"]
        if j < n_head - 1:
            x = jax.nn.relu(x)
    return x


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def lr_shape(step, total_steps: int):
    """``warmup_linear_decay`` with no warm-up, peak 1: 1 - step / total."""
    frac = jnp.clip(step.astype(jnp.float32) / float(total_steps), 0.0, 1.0)
    return 1.0 - frac


def adam_update(params, grads, opt, lr, wd, total_steps: int):
    """The program's ``adam``: L2 decay added to the gradient, Adam's
    scaling, the schedule's shape at the step count, minus the rate."""
    g = jax.tree.map(lambda g, p: g + wd * p, grads, params)
    count = opt["count"] + 1
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, opt["mu"], g)
    nu = jax.tree.map(lambda n, g: ADAM_B2 * n + (1 - ADAM_B2) * g * g, opt["nu"], g)
    c = count.astype(jnp.float32)
    shape = lr_shape(opt["count"], total_steps)

    def step(p, m, n):
        m_hat = m / (1 - ADAM_B1 ** c)
        n_hat = n / (1 - ADAM_B2 ** c)
        return p - lr * shape * m_hat / (jnp.sqrt(n_hat) + ADAM_EPS)

    return jax.tree.map(step, params, mu, nu), {"mu": mu, "nu": nu, "count": count}


def make_step(cfg: dict, batch_size: int, block_rows: int, total_steps: int,
              quant: Callable = lambda a: a, rows_used: Optional[int] = None):
    """One optimizer step on a batch, worked through in blocks of rows.

    ``rows_used`` plants the fault "half of the batch left out, the mean
    taken over the rest" when it is smaller than the batch.
    """
    used = rows_used or batch_size
    block_rows = min(block_rows, used)
    assert used % block_rows == 0, (used, block_rows)

    def block_loss(params, xb, yb, masks):
        preds = forward(params, xb, cfg, masks=masks, quant=quant)
        return jnp.sum((preds - yb) ** 2) / (used * yb.shape[-1])

    grad_block = jax.value_and_grad(block_loss)

    @jax.jit
    def step(params, opt, xb, yb, dkey, lr, wd):
        # The whole batch's masks once a step; each block takes its rows.
        masks = dropout_masks(
            cfg, dkey, (batch_size, xb.shape[1], int(cfg["d_model"]))
        )

        def body(carry, start):
            loss, grads = carry
            xs = jax.lax.dynamic_slice_in_dim(xb, start, block_rows, 0)
            ys = jax.lax.dynamic_slice_in_dim(yb, start, block_rows, 0)
            l, g = grad_block(
                params, xs, ys, slice_masks(masks, start, block_rows)
            )
            return (loss + l, jax.tree.map(jnp.add, grads, g)), None

        zero = jax.tree.map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(
            body, (jnp.zeros(()), zero),
            jnp.arange(0, used, block_rows),
        )
        params, opt = adam_update(params, grads, opt, lr, wd, total_steps)
        return params, opt, loss

    return step


def run_epoch(step, params, opt, x_all, y_all, epoch_key, *, n_train: int,
              num_batches: int, batch_size: int, lr, wd, max_steps=None):
    """One epoch as the program runs it: the epoch key splits into a
    permutation key and a dropout chain; the chain splits once a step.
    Returns the per-step losses too."""
    perm_key, key = jax.random.split(epoch_key)
    perm = jax.random.permutation(perm_key, n_train)
    perm = perm[: num_batches * batch_size].reshape(num_batches, batch_size)
    losses = []
    for i in range(num_batches if max_steps is None else max_steps):
        key, dkey = jax.random.split(key)
        params, opt, loss = step(
            params, opt, x_all[perm[i]], y_all[perm[i]], dkey, lr, wd
        )
        losses.append(loss)
    return params, opt, jnp.stack(losses)


def make_eval(cfg: dict, block_rows: int, quant: Callable = lambda a: a):
    """Mean squared error over a split, in blocks of rows, dropout off."""

    @jax.jit
    def block(params, xb, yb):
        preds = forward(params, xb, cfg, quant=quant)
        return jnp.sum(jnp.mean((preds - yb) ** 2, axis=-1))

    def evaluate(params, x, y):
        n = x.shape[0]
        total = 0.0
        for start in range(0, n, block_rows):
            total = total + block(
                params, x[start:start + block_rows], y[start:start + block_rows]
            )
        return total / n

    return evaluate


def fp8(a):
    """The control's precision below bfloat16."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def bf16(a):
    """The control's precision below float32 at default precision."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)
