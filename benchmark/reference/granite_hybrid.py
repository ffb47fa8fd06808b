"""Plain reference of ``family: granite_hybrid`` (HF ``GraniteMoeHybrid``:
granite-4.0-h-small). Imports nothing of the program.

The equations, as ``transformers``' ``modeling_granitemoehybrid.py`` states
them, float32 throughout with ``Precision.HIGHEST``, no cache, no batching,
no kernels:

    h = embedding_multiplier * E[ids]
    per layer:  h = h + r * Mixer(RMSNorm(h))
                x = RMSNorm(h);  h = h + r * (MoE(x) + SharedMLP(x))
    logits = RMSNorm(h) E^T / logits_scaling            (tied, eps 1e-5)

Mamba-2 mixer (d_inner = heads x head_dim, one B/C group):
    [z | xBC | dt] = x W_in                         (no bias)
    xBC = silu(conv1d_causal(xBC; k taps, depthwise, bias))
    [u | B | C] = xBC;   dt = softplus(dt + dt_bias);   A = -exp(A_log)
    per head:  S_t = exp(dt_t A) S_{t-1} + dt_t u_t B_t^T;  y_t = S_t C_t + D u_t
    y = RMSNorm(y * silu(z)) over all of d_inner;   out = y W_out
  the recurrence is a plain ``lax.scan`` over tokens.
Attention: q, k, v without bias, NO positional encoding, grouped-query,
    softmax_causal(q k^T * attention_multiplier) v, then W_o.
MoE: l = x W_r (float32); the top_k largest; gates = softmax over those
    logits; e(x) = W2_e (silu(a) * b), [a | b] = x W1_e; sum of gate x
    expert. SharedMLP: the same gated form, every token.

Departures, each stated in the configuration's file: only the experts
``experts_held`` names are computed (a pick of another expert adds
nothing: the chip's share of a two-chip layer, alike in the program);
the vocabulary is the held slice; depth is the first ``num_hidden_layers``
of ``layer_types``; weights are random from the seed.

The float32 weights of the cell are 19 GB, so ``make_weights`` returns the
RECIPE (seed and sizes) and every layer's weights are made from the seed
inside the jitted function that uses them; the experts one at a time inside
a scan. ``layer_weights`` / ``expert_bank`` hand the same values to the
program in its serving types.

``mode``: ``"f32"`` the reference; ``"fp8"`` rounds every linear layer's
weight and input through float8_e4m3 with one scale a tensor: the
control that a lower precision than the configuration states must fail.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BF = jnp.bfloat16


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


class Dims(NamedTuple):
    """Sizes of one configuration: hashable, a static jit argument."""
    d: int
    vocab: int
    layers: int
    kinds: tuple
    m_heads: int
    m_hd: int
    m_state: int
    m_conv: int
    a_heads: int
    a_kv: int
    a_hd: int
    a_scale: float
    experts: int
    top_k: int
    f: int
    fs: int
    held_first: int
    held: int
    eps: float
    emb_mult: float
    res_mult: float
    logit_div: float

    @property
    def d_inner(self):
        return self.m_heads * self.m_hd

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.m_state


def dims(cfg: dict) -> Dims:
    n = int(cfg["num_hidden_layers"])
    first, held = cfg.get("experts_held", (0, cfg["num_local_experts"]))
    return Dims(
        d=int(cfg["hidden_size"]), vocab=int(cfg["vocab_size"]), layers=n,
        kinds=tuple(cfg["layer_types"][:n]),
        m_heads=int(cfg["mamba_n_heads"]), m_hd=int(cfg["mamba_d_head"]),
        m_state=int(cfg["mamba_d_state"]), m_conv=int(cfg["mamba_d_conv"]),
        a_heads=int(cfg["num_attention_heads"]),
        a_kv=int(cfg["num_key_value_heads"]),
        a_hd=int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]),
        a_scale=float(cfg["attention_multiplier"]),
        experts=int(cfg["router_width"]),
        top_k=int(cfg["num_experts_per_tok"]),
        f=int(cfg["intermediate_size"]),
        fs=int(cfg["shared_intermediate_size"]),
        held_first=int(first), held=int(held),
        eps=float(cfg["rms_norm_eps"]),
        emb_mult=float(cfg["embedding_multiplier"]),
        res_mult=float(cfg["residual_multiplier"]),
        logit_div=float(cfg["logits_scaling"]))


def make_weights(seed: int, cfg: dict) -> dict:
    """The recipe: weights are made from it where they are used."""
    return {"seed": int(seed), "dims": dims(cfg)}


# ------------------------------------------------------- the weights

def _n(key, shape, std=0.02):
    """Normal values that bf16 holds exactly, as float32."""
    return (jax.random.normal(key, shape, jnp.float32) * std) \
        .astype(BF).astype(jnp.float32)


def _scale(key, shape):
    return 1.0 + jax.random.normal(key, shape, jnp.float32) * 0.1


def embedding(key, D: Dims):
    """Normal 0.02 / embedding_multiplier: the SCALED embedding that enters
    the stack has the matrices' 0.02. At 0.02 itself the tied head would
    score the token just read at 12 |E_t|^2 and a model of random weights
    would echo its input whatever its layers compute: every comparison of
    served tokens would pass, the control's too."""
    return _n(jax.random.fold_in(key, 1_000_001), (D.vocab, D.d),
              0.02 / D.emb_mult)


def final_norm(key, D: Dims):
    return _scale(jax.random.fold_in(key, 1_000_002), (D.d,))


def layer_key(key, layer: int):
    return jax.random.fold_in(key, layer)


def mixer_weights(key, D: Dims, kind: str) -> dict:
    """One layer's mixer (and its norm). Mamba-2's own initialisation for
    the recurrence: ``A_log`` = log of uniform 1..16, ``dt_bias`` the
    inverse softplus of log-uniform 0.001..0.1, ``D`` ones; conv taps and
    bias uniform +-1/sqrt(taps) (torch's Conv1d default)."""
    ks = jax.random.split(jax.random.fold_in(key, 11), 8)
    if kind == "attention":
        qkv = (D.a_heads + 2 * D.a_kv) * D.a_hd
        return {"norm": _scale(ks[0], (D.d,)),
                "qkv": _n(ks[1], (D.d, qkv)),
                "out": _n(ks[2], (D.a_heads * D.a_hd, D.d))}
    di, cd, H = D.d_inner, D.conv_dim, D.m_heads
    lim = 1.0 / math.sqrt(D.m_conv)
    dt = jnp.exp(jax.random.uniform(ks[5], (H,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {"norm": _scale(ks[0], (D.d,)),
            "in": _n(ks[1], (D.d, di + cd + H)),
            "conv_w": jax.random.uniform(ks[2], (D.m_conv, cd), jnp.float32,
                                         -lim, lim),
            "conv_b": jax.random.uniform(ks[3], (cd,), jnp.float32,
                                         -lim, lim),
            "A_log": jnp.log(jax.random.uniform(ks[4], (H,), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((H,), jnp.float32),
            "gnorm": _scale(ks[6], (di,)),
            "out": _n(ks[7], (di, D.d))}


def ffn_weights(key, D: Dims) -> dict:
    """One layer's FFN outside its experts: norm, router, shared MLP."""
    ks = jax.random.split(jax.random.fold_in(key, 12), 4)
    return {"norm": _scale(ks[0], (D.d,)),
            "router": _n(ks[1], (D.d, D.experts)),
            "s_w1": _n(ks[2], (D.d, 2 * D.fs)),
            "s_w2": _n(ks[3], (D.fs, D.d))}


def expert_weights(key, D: Dims, e):
    """Expert ``e`` (its number in the whole bank) of one layer:
    ``(w1 [d, 2f] = [a | b], w2 [f, d])``."""
    k = jax.random.fold_in(jax.random.fold_in(key, 13), e)
    k1, k2 = jax.random.split(k)
    return _n(k1, (D.d, 2 * D.f)), _n(k2, (D.f, D.d))


def expert_bank(key, D: Dims):
    """The held experts of one layer, stacked: for the program."""
    ids = D.held_first + jnp.arange(D.held)
    return jax.vmap(lambda e: expert_weights(key, D, e))(ids)


# ------------------------------------------------------- arithmetic

def _q_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, mode):
    if mode == "fp8":
        x, w = _q_fp8(x), _q_fp8(w)
    return jnp.dot(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def mamba_mixer(x, w, D: Dims, mode):
    """x [s, d] (already normed) -> [s, d]."""
    s = x.shape[0]
    di, cd, H, P, N = D.d_inner, D.conv_dim, D.m_heads, D.m_hd, D.m_state
    zxbcdt = _linear(x, w["in"], mode)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di: di + cd], zxbcdt[:, di + cd:]
    k = D.m_conv
    pad = jnp.concatenate([jnp.zeros((k - 1, cd), jnp.float32), xbc], 0)
    conv = w["conv_b"][None, :] + sum(
        w["conv_w"][j][None, :] * pad[j: j + s] for j in range(k))
    xbc = _silu(conv)
    u = xbc[:, :di].reshape(s, H, P)
    Bm, Cm = xbc[:, di: di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"][None, :])
    A = -jnp.exp(w["A_log"])

    def step(S, inp):                        # S [H, P, N]
        ut, dtt, bt, ct = inp
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * ut)[:, :, None] * bt[None, None, :]
        y = jnp.einsum("hpn,n->hp", S, ct, precision=HI) \
            + w["D"][:, None] * ut
        return S, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (u, dt, Bm, Cm))
    y = _rms(y.reshape(s, di) * _silu(z), w["gnorm"], D.eps)
    return _linear(y, w["out"], mode)


def attention_mixer(x, w, D: Dims, mode):
    s = x.shape[0]
    nq, nkv, hd = D.a_heads, D.a_kv, D.a_hd
    g = nq // nkv
    proj = _linear(x, w["qkv"], mode).reshape(s, nq + 2 * nkv, hd)
    q, k, v = proj[:, :nq], proj[:, nq: nq + nkv], proj[:, nq + nkv:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(inp):                          # one kv head, its g queries
        qg, kg, vg = inp                     # [s, g, hd], [s, hd], [s, hd]
        sc = jnp.einsum("qgd,kd->gqk", qg, kg, precision=HI) * D.a_scale
        sc = jnp.where(causal[None], sc, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(sc, -1), vg,
                          precision=HI)

    out = jax.lax.map(group, (jnp.moveaxis(q.reshape(s, nkv, g, hd), 1, 0),
                              jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    att = jnp.moveaxis(out, 0, 1).reshape(s, nq * hd)
    return _linear(att, w["out"], mode)


def route(x, router, D: Dims, mode):
    """(gates [s, k], idx [s, k]): softmax over the chosen logits."""
    val, idx = jax.lax.top_k(_linear(x, router, mode), D.top_k)
    return jax.nn.softmax(val, -1), idx


def gated(x, w1, w2, mode):
    f = w2.shape[0]
    ab = _linear(x, w1, mode)
    return _linear(_silu(ab[:, :f]) * ab[:, f:], w2, mode)


def moe(x, key, fw, D: Dims, mode, first=None, count=None):
    """Routed experts ``first .. first+count-1`` (default: the held ones)
    plus nothing else: the shared MLP is the caller's."""
    first = D.held_first if first is None else first
    count = D.held if count is None else count
    gates, idx = route(x, fw["router"], D, mode)

    def one(acc, e):
        w1, w2 = expert_weights(key, D, e)
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + g * gated(x, w1, w2, mode), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), first + jnp.arange(count))
    return out


@functools.partial(jax.jit, static_argnames=("D", "kind", "mode"))
def _layer(h, key, *, D, kind, mode):
    mw = mixer_weights(key, D, kind)
    x = _rms(h, mw["norm"], D.eps)
    mix = attention_mixer if kind == "attention" else mamba_mixer
    h = h + D.res_mult * mix(x, mw, D, mode)
    fw = ffn_weights(key, D)
    x = _rms(h, fw["norm"], D.eps)
    y = moe(x, key, fw, D, mode) + gated(x, fw["s_w1"], fw["s_w2"], mode)
    return h + D.res_mult * y


@functools.partial(jax.jit, static_argnames=("D",))
def _embed(key, ids, *, D):
    return D.emb_mult * embedding(key, D)[ids]


@functools.partial(jax.jit, static_argnames=("D", "mode"))
def _head(key, h, *, D, mode):
    hn = _rms(h, final_norm(key, D), D.eps)
    return _linear(hn, embedding(key, D).T, mode) / D.logit_div


def logits(w, ids, *, heads=None, mode="f32"):
    """ids [s] int32 -> logits [s, vocab] float32; ``heads`` is in the
    recipe already and only taken for the driver's sake. One layer's
    weights are alive at a time."""
    D = w["dims"]
    key = seed_key(w["seed"])
    h = _embed(key, ids, D=D)
    for l, kind in enumerate(D.kinds):
        h = _layer(h, layer_key(key, l), D=D, kind=kind, mode=mode)
    return _head(key, h, D=D, mode=mode)


@jax.jit
def gaps(ref_logits, rows, tokens):
    """For each (row, token): how far the token's reference score lies
    below the reference's best at that row. 0 where the token IS the best."""
    picked = ref_logits[rows]
    return jnp.max(picked, -1) - picked[jnp.arange(rows.shape[0]), tokens]


@jax.jit
def argmax_rows(lg, rows):
    return jnp.argmax(lg[rows], -1).astype(jnp.int32)
