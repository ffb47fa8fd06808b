"""Plain reference of ``family: mistral4_mla`` (``model_type mistral4``:
Mistral-Small-4-119B-2603, the language model alone). Imports nothing of
the program.

The equations, float32 throughout with ``Precision.HIGHEST``, no cache, no
batching, no kernels; attention in query blocks so that 33k tokens fit.
With ``x = RMSNorm(h)`` (eps 1e-6, no biases), H heads:

    c_q = RMSNorm(x W_dq);   q = c_q W_uq -> H x (q_nope | q_rope)
    [c_kv | k_r] = x W_dkv;  c = RMSNorm(c_kv);  k_r ONE rotary head for all
    [k_nope | v] = c W_ukv                       H x (nope + v)
    s_h(i,j) = a(i) scale (q_nope_h(i).k_nope_h(j) + R(i)q_rope_h(i).R(j)k_r(j)),
               j <= i;   o_h = softmax_j(s_h) v_h;   h = h + concat_h(o_h) W_o
    x2 = RMSNorm(h);  top-k of the router's float32 logits, gates = softmax
    over the chosen;  h = h + sum_k g_k Expert_k(x2) + Shared(x2)
    Expert / Shared: (silu(x W_g) * (x W_u)) W_d   ([W_g | W_u] stored as w1)
    logits = RMSNorm(h_L) W_head^T               (untied head)

``R``: YaRN rotary over the rope dimensions on ADJACENT pairs
(``rope_interleave``): per pair the frequency blends ``theta^(-2i/dim)``
and that over ``factor`` by the linear ramp between the two correction
dimensions (``beta_fast`` / ``beta_slow``, floor / ceil as ``transformers``
truncates them); the table's own factor is mscale / mscale_all_dim = 1.
``scale = qk_head_dim^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``.
``a(i) = 1 + beta ln(1 + floor(i / original_max_position_embeddings))``.

``assumed`` (each stated in the configuration's file): softmax over the
chosen logits as the gates (no ``scoring_func`` in the config;
``norm_topk_prob`` true); the ``m^2`` in ``scale`` and the table's factor 1
(DeepSeek-V3's attention, whose key set this is); ``a(i)`` in the form
above (Llama-4's ``ln(floor((i + 1) / 8192) + 1) beta + 1`` differs from it
at multiples of 8192 alone); the shared expert ``n_shared_experts x
moe_intermediate_size`` wide; text only.

Departures, each in the configuration's file: only the experts
``experts_held`` names are computed (a pick of another expert adds nothing:
the chip's share of a four-chip layer, alike in the program); the vocabulary
is the held slice; depth is the first ``num_hidden_layers``; weights are
random from the seed.

``make_weights`` returns the RECIPE (seed and sizes): every layer's weights
are made from the seed inside the jitted function that uses them, the
experts one at a time inside a scan. ``logits`` returns the final NORMED
hidden rows and the recipe, not ``[s, vocab]`` scores (33k x 32,768 float32
are 4.4 GB): ``gaps`` / ``argmax_rows`` apply the head to the rows asked for.

``mode``: ``"f32"`` the reference; ``"fp8"`` rounds every linear layer's
weight and input through float8_e4m3 with one scale a tensor (the control
that a lower precision than the configuration states must fail); a name of
``FAULTS`` (``calibrate.py gaps --modes``) is float32 with that ONE
departure from the equations: what a program with that fault would serve.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BF = jnp.bfloat16
Q_BLOCK = 1024

FAULTS = ("no_yarn_blend", "no_mscale", "no_query_temperature",
          "no_latent_norm")


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


class Dims(NamedTuple):
    """Sizes of one configuration: hashable, a static jit argument."""
    d: int
    vocab: int
    layers: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    experts: int
    top_k: int
    f: int
    fs: int
    held_first: int
    held: int
    eps: float
    theta: float
    factor: float
    orig: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all: float
    temp_beta: float

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.mscale_all * math.log(self.factor) + 1.0 \
            if self.factor > 1 else 1.0
        return (self.nope + self.rope) ** -0.5 * m * m


def dims(cfg: dict) -> Dims:
    rp = cfg["rope_parameters"]
    first, held = cfg.get("experts_held", (0, cfg["n_routed_experts"]))
    f = int(cfg["moe_intermediate_size"])
    return Dims(
        d=int(cfg["hidden_size"]), vocab=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]),
        experts=int(cfg["router_width"]),
        top_k=int(cfg["num_experts_per_tok"]), f=f,
        fs=int(cfg["n_shared_experts"]) * f,
        held_first=int(first), held=int(held),
        eps=float(cfg["rms_norm_eps"]), theta=float(rp["rope_theta"]),
        factor=float(rp["factor"]),
        orig=int(rp["original_max_position_embeddings"]),
        beta_fast=float(rp["beta_fast"]), beta_slow=float(rp["beta_slow"]),
        mscale=float(rp["mscale"]), mscale_all=float(rp["mscale_all_dim"]),
        temp_beta=float(rp["llama_4_scaling_beta"]))


def make_weights(seed: int, cfg: dict) -> dict:
    """The recipe: weights are made from it where they are used."""
    return {"seed": int(seed), "dims": dims(cfg)}


# ------------------------------------------------------- the weights
#
# Standard deviations are chosen so that every part of the layer shows in
# the logits AND greedy decoding does not collapse (configuration file,
# ``assumed.weights``; PERF.md 6, PR 37). With unit-RMS inputs q has std
# 2.8, k_nope and k_r 0.57 each, so a score (64 + 64 products, times
# ``scale`` 0.195) has std near 3.5 below position 8192: over 4k-33k keys a
# softmax of std 1 is diffuse, every query returns the SAME mean of the
# values, that common vector passes W_o into the stream of every row and
# into the next layer's values, and after two layers every row's hidden
# state points one way: a random model then repeats ONE token whatever it
# reads (first chip runs of PR 37: 1 distinct token of 192, the fp8 control
# reads 0). v has std 1 and W_o 1.28 / sqrt(H v) (0.02 at the published
# widths): attention adds 0.3-0.6 to a stream of 1.4-2.2; the embedding has
# std 1. A routed expert's W_d has std 0.008 where the shared expert's has
# 0.02: the router's top-4 is a DISCRETE choice that bf16 inputs flip now
# and then against the float32 reference, and at 0.02 those flips were the
# program's whole reading (0.7-1.4 against an fp8 control of 2.3).

def _n(key, shape, std=0.02):
    """Normal values that bf16 holds exactly, as float32."""
    return (jax.random.normal(key, shape, jnp.float32) * std) \
        .astype(BF).astype(jnp.float32)


def _scale(key, shape):
    return 1.0 + jax.random.normal(key, shape, jnp.float32) * 0.1


def embedding(key, D: Dims):
    return _n(jax.random.fold_in(key, 1_000_001), (D.vocab, D.d), 1.0)


def head(key, D: Dims):
    """The untied output head ``[vocab, d]``."""
    return _n(jax.random.fold_in(key, 1_000_003), (D.vocab, D.d))


def final_norm(key, D: Dims):
    return _scale(jax.random.fold_in(key, 1_000_002), (D.d,))


def layer_key(key, layer: int):
    return jax.random.fold_in(key, layer)


def attention_weights(key, D: Dims) -> dict:
    """One layer's latent attention: ``dq [d, q_rank]``, ``uq [q_rank, H x
    (nope + rope)]`` (a head's columns: nope then rope), ``dkv [d, kv_rank
    + rope]``, ``ukv [kv_rank, H x (nope + v)]`` (a head's columns: k_nope
    then v), ``o [H x v, d]`` and the three norm scales."""
    ks = jax.random.split(jax.random.fold_in(key, 11), 10)
    H = D.heads
    ukv = jnp.concatenate(
        [_n(ks[6], (D.kv_rank, H, D.nope), 0.57 / math.sqrt(D.kv_rank)),
         _n(ks[7], (D.kv_rank, H, D.v), 1.0 / math.sqrt(D.kv_rank))], -1)
    return {"norm": _scale(ks[0], (D.d,)),
            "dq": _n(ks[1], (D.d, D.q_rank)),
            "q_norm": _scale(ks[2], (D.q_rank,)),
            "uq": _n(ks[3], (D.q_rank, H * (D.nope + D.rope)),
                     2.8 / math.sqrt(D.q_rank)),
            "dkv": jnp.concatenate(
                [_n(ks[4], (D.d, D.kv_rank)),
                 _n(ks[5], (D.d, D.rope), 0.57 / math.sqrt(D.d))], -1),
            "kv_norm": _scale(ks[8], (D.kv_rank,)),
            "ukv": ukv.reshape(D.kv_rank, H * (D.nope + D.v)),
            "o": _n(ks[9], (H * D.v, D.d), 1.28 / math.sqrt(H * D.v))}


def ffn_weights(key, D: Dims) -> dict:
    """One layer's FFN outside its routed experts: norm, router, shared."""
    ks = jax.random.split(jax.random.fold_in(key, 12), 4)
    return {"norm": _scale(ks[0], (D.d,)),
            "router": _n(ks[1], (D.d, D.experts)),
            "s_w1": _n(ks[2], (D.d, 2 * D.fs)),
            "s_w2": _n(ks[3], (D.fs, D.d))}


def expert_weights(key, D: Dims, e):
    """Expert ``e`` (its number in the whole bank) of one layer:
    ``(w1 [d, 2f] = [W_g | W_u], w2 [f, d] = W_d)``."""
    k = jax.random.fold_in(jax.random.fold_in(key, 13), e)
    k1, k2 = jax.random.split(k)
    return _n(k1, (D.d, 2 * D.f)), _n(k2, (D.f, D.d), 0.008)


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


def yarn_inv_freq(D: Dims, blend: bool = True):
    """Per-pair frequencies ``[rope / 2]``: the YaRN blend, or (``blend``
    false: a fault) the plain ``theta^(-2i/dim)``."""
    dim = D.rope
    extra = D.theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not blend or D.factor <= 1:
        return extra

    def correction(rot):
        return dim * math.log(D.orig / (rot * 2 * math.pi)) \
            / (2 * math.log(D.theta))

    low = max(math.floor(correction(D.beta_fast)), 0)
    high = min(math.ceil(correction(D.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / D.factor * ramp + extra * (1.0 - ramp)


def rope_interleaved(x, pos, inv_freq):
    """Rotate adjacent pairs ``(x[2i], x[2i+1])`` of the last axis by
    ``pos * inv_freq[i]``; ``pos`` broadcasts over x's leading axes."""
    ang = pos[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * cos - xo * sin, xo * cos + xe * sin],
                     -1).reshape(x.shape)


def query_temperature(pos, D: Dims):
    return 1.0 + D.temp_beta * jnp.log1p(
        jnp.floor(pos.astype(jnp.float32) / D.orig))


def attention_inputs(x, w, D: Dims, mode, fault=None):
    """x [s, d] (normed) -> ``(q [s, H, nope + rope]`` rotated, scaled and
    tempered, ``c [s, kv_rank]`` the normed latent, ``k_r [s, rope]``
    rotated): what the cache holds is ``c`` and ``k_r``."""
    s = x.shape[0]
    H = D.heads
    pos = jnp.arange(s, dtype=jnp.int32)
    freq = yarn_inv_freq(D, blend=fault != "no_yarn_blend")
    cq = _rms(_linear(x, w["dq"], mode), w["q_norm"], D.eps)
    q = _linear(cq, w["uq"], mode).reshape(s, H, D.nope + D.rope)
    q = jnp.concatenate(
        [q[..., :D.nope],
         rope_interleaved(q[..., D.nope:], pos[:, None], freq)], -1)
    scale = D.softmax_scale if fault != "no_mscale" \
        else (D.nope + D.rope) ** -0.5
    if fault != "no_query_temperature":
        scale = scale * query_temperature(pos, D)[:, None, None]
    ckv = _linear(x, w["dkv"], mode)
    c = ckv[:, :D.kv_rank]
    if fault != "no_latent_norm":
        c = _rms(c, w["kv_norm"], D.eps)
    return q * scale, c, rope_interleaved(ckv[:, D.kv_rank:], pos, freq)


def latent_attention(x, w, D: Dims, mode, fault=None):
    """x [s, d] (normed) -> [s, d]: the expanded form, a head at a time, in
    blocks of ``Q_BLOCK`` queries."""
    s = x.shape[0]
    H = D.heads
    q, c, k_r = attention_inputs(x, w, D, mode, fault)
    kv = _linear(c, w["ukv"], mode).reshape(s, H, D.nope + D.v)
    k = jnp.concatenate(
        [kv[..., :D.nope], jnp.broadcast_to(k_r[:, None], (s, H, D.rope))],
        -1)
    v = kv[..., D.nope:]
    qb = min(Q_BLOCK, s)
    pad = -(-s // qb) * qb - s
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    kpos = jnp.arange(s, dtype=jnp.int32)

    def head_fn(inp):                        # one head
        qh, kh, vh = inp                     # [s+pad, n+r], [s, n+r], [s, v]

        def block(i):
            qi = jax.lax.dynamic_slice_in_dim(qh, i * qb, qb, 0)
            sc = jnp.einsum("qd,kd->qk", qi, kh, precision=HI)
            qpos = i * qb + jnp.arange(qb, dtype=jnp.int32)
            sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
            return jnp.einsum("qk,kd->qd", jax.nn.softmax(sc, -1), vh,
                              precision=HI)

        return jax.lax.map(block, jnp.arange((s + pad) // qb)) \
            .reshape(s + pad, D.v)[:s]

    out = jax.lax.map(head_fn, (jnp.moveaxis(qp, 1, 0),
                                jnp.moveaxis(k, 1, 0),
                                jnp.moveaxis(v, 1, 0)))
    att = jnp.moveaxis(out, 0, 1).reshape(s, H * D.v)
    return _linear(att, w["o"], mode)


def latent_attention_absorbed(x, w, D: Dims, mode="f32"):
    """The absorbed form the program may use, equal to the above: scores
    against the latent row itself, ``q~_h = q_nope_h W_uk_h^T``, ``o_h =
    (sum_j p_j c(j)) W_uv_h``. Whole [s, s] scores: small s only (tests)."""
    s = x.shape[0]
    H = D.heads
    q, c, k_r = attention_inputs(x, w, D, mode)
    ukv = w["ukv"].reshape(D.kv_rank, H, D.nope + D.v)
    qa = jnp.einsum("shn,rhn->shr", q[..., :D.nope], ukv[..., :D.nope],
                    precision=HI)
    sc = jnp.einsum("shr,kr->hsk", qa, c, precision=HI) \
        + jnp.einsum("shr,kr->hsk", q[..., D.nope:], k_r, precision=HI)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
    oc = jnp.einsum("hsk,kr->shr", jax.nn.softmax(sc, -1), c, precision=HI)
    att = jnp.einsum("shr,rhv->shv", oc, ukv[..., D.nope:], precision=HI)
    return _linear(att.reshape(s, H * D.v), w["o"], mode)


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
    and nothing else: the shared expert is the caller's."""
    first = D.held_first if first is None else first
    count = D.held if count is None else count
    gates, idx = route(x, fw["router"], D, mode)

    def one(acc, e):
        w1, w2 = expert_weights(key, D, e)
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + g * gated(x, w1, w2, mode), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), first + jnp.arange(count))
    return out


@functools.partial(jax.jit, static_argnames=("D", "mode", "fault"))
def _layer(h, key, *, D, mode, fault=None):
    aw = attention_weights(key, D)
    h = h + latent_attention(_rms(h, aw["norm"], D.eps), aw, D, mode, fault)
    fw = ffn_weights(key, D)
    x = _rms(h, fw["norm"], D.eps)
    return h + moe(x, key, fw, D, mode) \
        + gated(x, fw["s_w1"], fw["s_w2"], mode)


@functools.partial(jax.jit, static_argnames=("D",))
def _embed(key, ids, *, D):
    return embedding(key, D)[ids]


class Scores(NamedTuple):
    """What ``logits`` hands to ``gaps`` / ``argmax_rows``: the final
    normed hidden rows ``[s, d]`` and the recipe of the head."""
    hidden: jax.Array
    seed: int
    dims: Dims
    mode: str

    def rows(self, rows):
        """float32 logits ``[len(rows), vocab]`` of the rows asked for."""
        return _head_rows(seed_key(self.seed), self.hidden[rows],
                          D=self.dims, mode=self.mode)


@functools.partial(jax.jit, static_argnames=("D", "mode"))
def _head_rows(key, hn, *, D, mode):
    return _linear(hn, head(key, D).T, mode)


@functools.partial(jax.jit, static_argnames=("D",))
def _final(key, h, *, D):
    return _rms(h, final_norm(key, D), D.eps)


def logits(w, ids, *, heads=None, mode="f32", fault=None) -> Scores:
    """ids [s] int32 -> the scores of every row, the head not yet applied
    (``Scores.rows``). ``heads`` is in the recipe already and only taken for
    the driver's sake. One layer's weights are alive at a time."""
    D = w["dims"]
    key = seed_key(w["seed"])
    if mode in FAULTS:
        mode, fault = "f32", mode
    h = _embed(key, ids, D=D)
    for l in range(D.layers):
        h = _layer(h, layer_key(key, l), D=D, mode=mode, fault=fault)
    return Scores(_final(key, h, D=D), w["seed"], D, mode)


def gaps(scores: Scores, rows, tokens):
    """For each (row, token): how far the token's reference score lies
    below the reference's best at that row. 0 where the token IS the best."""
    picked = scores.rows(rows)
    return jnp.max(picked, -1) - picked[jnp.arange(rows.shape[0]), tokens]


def argmax_rows(scores: Scores, rows):
    return jnp.argmax(scores.rows(rows), -1).astype(jnp.int32)
