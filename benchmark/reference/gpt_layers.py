"""Plain reference of the trained GPT (``training.family: gpt_layers``).

Imports nothing of the program. The model is Brown et al. 2020's decoder as
the repo's training path builds it: token and learned position embeddings,
pre-LN blocks (LayerNorm, biased QKV laid out [3, heads, head_dim], causal
softmax attention, biased projection, LayerNorm, biased erf-GELU FFN), a
final LayerNorm and an untied head without bias; the loss is the mean
cross-entropy over every token.

Everything is float32 with ``Precision.HIGHEST``. What the configuration
STATES about storage is kept, because it is the recipe and not an accident:
matrix and bias parameters live in bf16 and are written back with stochastic
rounding (no float32 master copy), Adam's moments live in bf16, the norms'
parameters in float32. So parameters are upcast on use, and the update rounds
as the recipe says, with a random stream of the reference's own.

The backward pass runs layer by layer (``jax.vjp`` of one block at a time,
its parameters updated as soon as their gradient exists, which is sound:
nothing below a layer reads its parameters), so that 1.3 B parameters with
their moments fit beside the activations of one batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BLOCK = ("ln1.weight", "ln1.bias", "qkv.weight", "qkv.bias", "proj.weight",
         "proj.bias", "ln2.weight", "ln2.bias", "fc1.weight", "fc1.bias",
         "fc2.weight", "fc2.bias")


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit,
                   static_argnames=("vocab", "d", "layers", "dff", "seq"))
def _make(key, *, vocab, d, layers, dff, seq):
    bf = jnp.bfloat16
    shapes = {"ln1.weight": (d,), "ln1.bias": (d,), "qkv.weight": (d, 3 * d),
              "qkv.bias": (3 * d,), "proj.weight": (d, d), "proj.bias": (d,),
              "ln2.weight": (d,), "ln2.bias": (d,), "fc1.weight": (d, dff),
              "fc1.bias": (dff,), "fc2.weight": (dff, d), "fc2.bias": (d,)}

    def leaf(k, name, shape):
        x = jax.random.normal(k, shape, jnp.float32)
        if name.endswith("weight") and len(shape) == 1:     # a norm's scale
            return 1.0 + 0.1 * x
        if len(shape) == 1 and name.split(".")[-2] in ("ln1", "ln2", "norm"):
            return 0.02 * x                                 # a norm's bias
        return (0.02 * x).astype(bf)

    ks = jax.random.split(key, 5 + layers)
    w = {"embed.weight": leaf(ks[0], "embed.weight", (vocab, d)),
         "pos.weight": leaf(ks[1], "pos.weight", (seq, d)),
         "norm.weight": leaf(ks[2], "norm.weight", (d,)),
         "norm.bias": leaf(ks[3], "norm.bias", (d,)),
         "head.weight": leaf(ks[4], "head.weight", (d, vocab))}
    for i in range(layers):
        kk = jax.random.split(ks[5 + i], len(BLOCK))
        for k, n in zip(kk, BLOCK):
            w[f"blocks.{i}.{n}"] = leaf(k, n, shapes[n])
    return w


def make_weights(seed: int, cfg: dict, seq: int) -> dict:
    """name -> array, in the type the recipe stores it in."""
    return _make(seed_key(seed), vocab=int(cfg["vocab_size"]),
                 d=int(cfg["d_model"]), layers=int(cfg["n_layers"]),
                 dff=int(cfg["d_ff"]), seq=int(seq))


def batch(seed: int, step: int, batch_size: int, seq: int, vocab: int):
    """The feed of step ``step`` (0-based): token ids and next-token labels,
    uniform from the seed; every row differs."""
    rng = np.random.RandomState((int(seed) * 1000003 + step) % (2 ** 32))
    ids = rng.randint(0, vocab, (batch_size, seq)).astype(np.int64)
    labels = rng.randint(0, vocab, (batch_size, seq)).astype(np.int64)
    return ids, labels


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _q(x):
    """Through float8_e4m3 and back, with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _mm_fp8(x, w):
    """The control's matmul: every operand of the forward dot and of both
    backward dots goes through fp8."""
    return jnp.dot(_q(x), _q(w), precision=HI)


def _mm_fp8_fwd(x, w):
    return _mm_fp8(x, w), (x, w)


def _mm_fp8_bwd(res, dy):
    x, w = res
    dyq, k = _q(dy), x.shape[-1]
    dx = jnp.dot(dyq, _q(w).T, precision=HI)
    dw = jnp.dot(_q(x).reshape(-1, k).T, dyq.reshape(-1, dy.shape[-1]),
                 precision=HI)
    return dx, dw


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(x, w, mode):
    w = w.astype(jnp.float32)
    return _mm_fp8(x, w) if mode == "fp8" else jnp.dot(x, w, precision=HI)


def block(x, p, *, heads, eps, mode):
    """One pre-LN block over x [b, s, d]; ``p`` holds the twelve leaves."""
    b, s, d = x.shape
    hd = d // heads
    f = {k: v.astype(jnp.float32) for k, v in p.items()}
    h = _ln(x, f["ln1.weight"], f["ln1.bias"], eps)
    qkv = (_mm(h, p["qkv.weight"], mode) + f["qkv.bias"]) \
        .reshape(b, s, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / (hd ** 0.5)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                     precision=HI).reshape(b, s, d)
    x = x + _mm(att, p["proj.weight"], mode) + f["proj.bias"]
    h = _ln(x, f["ln2.weight"], f["ln2.bias"], eps)
    ff = jax.nn.gelu(_mm(h, p["fc1.weight"], mode) + f["fc1.bias"],
                     approximate=False)
    return x + _mm(ff, p["fc2.weight"], mode) + f["fc2.bias"]


def head_loss(x, p, labels, *, eps, mode):
    """Final LayerNorm, head, mean cross-entropy over every token."""
    h = _ln(x, p["norm.weight"], p["norm.bias"], eps)
    logits = _mm(h, p["head.weight"], mode)
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - picked)


def _sr_bf16(x, key):
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    noise = jax.random.bits(key, x.shape, jnp.uint16).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(((bits + noise) >> 16)
                                        .astype(jnp.uint16), jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2, 3))
def adamw(p, g, m, v, t, key, *, opt):
    """One AdamW update of one leaf as the recipe states it. ``opt`` is a
    tuple (lr, beta1, beta2, eps, weight_decay, stochastic_rounding)."""
    lr, b1, b2, eps, wd, sr = opt
    p32 = p.astype(jnp.float32) * (1.0 - lr * wd)
    m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g
    v32 = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
    mhat = m32 / (1 - b1 ** t)
    vhat = v32 / (1 - b2 ** t)
    new = p32 - lr * mhat / (jnp.sqrt(vhat) + eps)
    if p.dtype == jnp.bfloat16:
        new = _sr_bf16(new, key) if sr else new.astype(jnp.bfloat16)
    return new, m32.astype(m.dtype), v32.astype(v.dtype)


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


class Trainer:
    """The reference's training state and its step, layer by layer."""

    def __init__(self, seed, cfg, tr, mode="f32", frozen=False):
        self.cfg, self.tr, self.mode, self.frozen = cfg, tr, mode, frozen
        self.layers = int(cfg["n_layers"])
        self.heads = int(cfg["n_heads"])
        self.eps = float(cfg.get("layer_norm_eps", 1e-5))
        o = tr["optimizer"]
        self.opt = (float(o["lr"]), float(o["beta1"]), float(o["beta2"]),
                    float(o["epsilon"]), float(o["weight_decay"]),
                    bool(o["stochastic_rounding"]))
        md = jnp.bfloat16 if o["moment_dtype"] == "bfloat16" else jnp.float32
        self.w = make_weights(seed, cfg, tr["seq"])
        self.m = {k: jnp.zeros(a.shape, md) for k, a in self.w.items()}
        self.v = {k: jnp.zeros(a.shape, md) for k, a in self.w.items()}
        self.t = 0
        self.grad_vecs = {}             # first gradient of each 1-D leaf
        self.key = jax.random.fold_in(seed_key(seed), 977)
        kw = dict(heads=self.heads, eps=self.eps, mode=mode)
        self._fwd = jax.jit(functools.partial(block, **kw))

        def bwd(x, p, dy):
            _, pull = jax.vjp(functools.partial(block, **kw), x, p)
            return pull(dy)

        self._bwd = jax.jit(bwd)
        self._head = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, eps=self.eps, mode=mode),
            argnums=(0, 1)))

    def _layer(self, i):
        return {n: self.w[f"blocks.{i}.{n}"].astype(jnp.float32)
                for n in BLOCK}

    def _apply(self, name, g, grad_norms):
        if grad_norms is not None:
            grad_norms[name] = float(_norm(g))
            if g.ndim == 1:
                self.grad_vecs[name] = np.asarray(g, np.float32)
        if self.frozen:                 # a planted fault: nothing moves
            return
        self.key, k = jax.random.split(self.key)
        self.w[name], self.m[name], self.v[name] = adamw(
            self.w[name], g.astype(jnp.float32), self.m[name], self.v[name],
            float(self.t), k, opt=self.opt)

    def step(self, ids, labels, want_grad_norms=False):
        """One step on one batch; returns (loss, {leaf: gradient norm} or
        None)."""
        self.t += 1
        norms = {} if want_grad_norms else None
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        b, s = ids.shape
        x = self.w["embed.weight"].astype(jnp.float32)[ids] \
            + self.w["pos.weight"].astype(jnp.float32)[jnp.arange(s)][None]
        xs = []
        for i in range(self.layers):
            xs.append(x)
            x = self._fwd(x, self._layer(i))
        hp = {n: self.w[n].astype(jnp.float32)
              for n in ("norm.weight", "norm.bias", "head.weight")}
        loss, (dx, dhp) = self._head(x, hp, labels)
        for n, g in dhp.items():
            self._apply(n, g, norms)
        for i in reversed(range(self.layers)):
            dx, dp = self._bwd(xs.pop(), self._layer(i), dx)
            for n, g in dp.items():
                self._apply(f"blocks.{i}.{n}", g, norms)
        d = dx.shape[-1]
        g_embed = jnp.zeros(self.w["embed.weight"].shape, jnp.float32) \
            .at[ids.reshape(-1)].add(dx.reshape(-1, d))
        self._apply("embed.weight", g_embed, norms)
        self._apply("pos.weight", jnp.sum(dx, 0), norms)
        return float(loss), norms

    def changes(self, seed):
        """({leaf: norm of (parameters now - parameters at the start)},
        {1-D leaf: that difference itself})."""
        w0 = make_weights(seed, self.cfg, self.tr["seq"])
        norms, vecs = {}, {}
        for k, a in self.w.items():
            diff = a.astype(jnp.float32) - w0[k].astype(jnp.float32)
            norms[k] = float(_norm(diff))
            if a.ndim == 1:
                vecs[k] = np.asarray(diff)
        return norms, vecs
