"""Plain reference of the served GPT (``family: fused_causal_lm``).

Imports nothing of the program. Two things live here:

``make_weights``  the weights of the cell, made on the device in ONE jitted
    call from the seed, in the types they are served in (bf16 matmul
    stacks and biases, float32 norms, a float32 embedding whose values are
    bf16-representable). The harness hands them to the program; the
    reference makes them again from the seed after the program is freed.
``logits``  the forward pass as the configuration file states it: token
    embedding, pre-LN blocks (LayerNorm, biased QKV, rotary positions in the
    half-rotation convention, causal softmax attention, biased output
    projection, LayerNorm, biased tanh-GELU FFN), final LayerNorm, head
    tied to the embedding. float32 throughout with ``Precision.HIGHEST``
    (a float32 dot on the TPU is otherwise bf16 passes), no cache, no
    batching, no kernels. Departure from Brown et al. 2020: rotary
    positions in place of learned ones, as the served model has them
    (``assumed`` in the configuration file).

``mode`` selects the control: ``"f32"`` is the reference; ``"int8"``
rounds every linear layer's weight (per output channel) and input (per
token) to symmetric int8 before the dot, the A8W8 arithmetic a later PR
would be tempted by; ``"fp8"`` does the same through float8_e4m3 with one
scale per tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
STACKED = ("ln1_scale", "ln1_bias", "qkv_weight", "qkv_bias", "out_weight",
           "out_bias", "ln2_scale", "ln2_bias", "ffn1_weight", "ffn1_bias",
           "ffn2_weight", "ffn2_bias")


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("vocab", "d", "layers", "dff"))
def _make(key, *, vocab, d, layers, dff):
    ks = jax.random.split(key, 16)
    bf = jnp.bfloat16

    def n(k, shape, std, dtype):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    w = {
        # float32 table of bf16-representable values: the program looks a
        # row up and casts it to bf16, and casts the transpose for its head
        "embed": n(ks[0], (vocab, d), 0.02, bf).astype(jnp.float32),
        "lnf_scale": 1.0 + n(ks[1], (d,), 0.1, jnp.float32),
        "lnf_bias": n(ks[2], (d,), 0.02, jnp.float32),
        "ln1_scale": 1.0 + n(ks[3], (layers, d), 0.1, jnp.float32),
        "ln1_bias": n(ks[4], (layers, d), 0.02, jnp.float32),
        "qkv_weight": n(ks[5], (layers, d, 3 * d), 0.02, bf),
        "qkv_bias": n(ks[6], (layers, 3 * d), 0.02, bf),
        "out_weight": n(ks[7], (layers, d, d), 0.02, bf),
        "out_bias": n(ks[8], (layers, d), 0.02, bf),
        "ln2_scale": 1.0 + n(ks[9], (layers, d), 0.1, jnp.float32),
        "ln2_bias": n(ks[10], (layers, d), 0.02, jnp.float32),
        "ffn1_weight": n(ks[11], (layers, d, dff), 0.02, bf),
        "ffn1_bias": n(ks[12], (layers, dff), 0.02, bf),
        "ffn2_weight": n(ks[13], (layers, dff, d), 0.02, bf),
        "ffn2_bias": n(ks[14], (layers, d), 0.02, bf),
    }
    return w


def make_weights(seed: int, cfg: dict) -> dict:
    return _make(seed_key(seed), vocab=int(cfg["vocab_size"]),
                 d=int(cfg["d_model"]), layers=int(cfg["n_layers"]),
                 dff=int(cfg["d_ff"]))


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _q_int8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-8) \
        / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _q_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, b, mode):
    """x [s, k] @ w [k, n] + b, in the arithmetic ``mode`` names."""
    w = w.astype(jnp.float32)
    if mode == "int8":
        x, w = _q_int8(x, -1), _q_int8(w, 0)
    elif mode == "fp8":
        x, w = _q_fp8(x), _q_fp8(w)
    y = jnp.dot(x, w, precision=HI)
    return y if b is None else y + b.astype(jnp.float32)


def _rope(x, cos, sin):
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit,
                   static_argnames=("heads", "eps", "theta", "mode"))
def logits(w, ids, *, heads, eps=1e-5, theta=10000.0, mode="f32"):
    """ids [s] int32 -> logits [s, vocab] float32. Position ``t`` holds the
    scores of the token that follows ``ids[:t + 1]``; padding after the
    real tokens cannot reach back through the causal mask."""
    s = ids.shape[0]
    d = w["embed"].shape[1]
    hd = d // heads
    x = w["embed"][ids]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        hn = _ln(x, lw["ln1_scale"], lw["ln1_bias"], eps)
        proj = _linear(hn, lw["qkv_weight"], lw["qkv_bias"], mode)
        q, k, v = jnp.split(proj.reshape(s, 3 * heads, hd),
                            [heads, 2 * heads], axis=1)
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / (hd ** 0.5)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                         precision=HI).reshape(s, d)
        x = x + _linear(att, lw["out_weight"], lw["out_bias"], mode)
        hn = _ln(x, lw["ln2_scale"], lw["ln2_bias"], eps)
        ff = jax.nn.gelu(_linear(hn, lw["ffn1_weight"], lw["ffn1_bias"],
                                 mode), approximate=True)
        return x + _linear(ff, lw["ffn2_weight"], lw["ffn2_bias"], mode), None

    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in STACKED})
    hn = _ln(x, w["lnf_scale"], w["lnf_bias"], eps)
    return _linear(hn, w["embed"].T, None, mode)


@jax.jit
def gaps(ref_logits, rows, tokens):
    """For each (row, token): how far the token's reference score lies
    below the reference's best at that row. 0 where the token IS the best."""
    picked = ref_logits[rows]
    return jnp.max(picked, -1) - picked[jnp.arange(rows.shape[0]), tokens]


@jax.jit
def argmax_rows(lg, rows):
    return jnp.argmax(lg[rows], -1).astype(jnp.int32)
