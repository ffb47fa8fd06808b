"""Plain reference of ``family: mimo_v2_flash`` (``model_type
mimo_v2_flash``: MiMo-V2-Flash, the language model alone). Imports nothing
of the program.

The equations, float32 throughout with ``Precision.HIGHEST``, no cache, no
batching, no kernels; attention in query blocks so that 34k tokens fit.
With ``x = RMSNorm(h)`` (eps ``layernorm_epsilon``, no biases), layer ``l``
of kind ``hybrid_layer_pattern[l]`` (0 full, 1 window):

    q = x W_q -> H heads of dk;  k = x W_k, v = x W_v -> G heads (dk, dv)
    q, k: rotate-half rotary over each head's first int(dk x
          partial_rotary_factor) dims (theta: rope_theta in full layers,
          swa_rope_theta in window layers); the other dims carry no position
    z_h(i,j) = q_h(i).k_g(h)(j) / sqrt(dk), j <= i (window: i - W < j <= i)
    full:    o_h = softmax_j(z_h) (s v_g(h))
    window:  o_h = sum_j e^(z_j - m) s v_g(h)(j) / (e^(s_h - m)
                   + sum_j e^(z_j - m)),   s_h the head's sink logit
    h = h + concat_h(o_h) W_o,   s = attention_value_scale
    x2 = RMSNorm(h);  moe_layer_freq[l] = 0: h = h + Dense(x2), else
    p = sigmoid(x2 W_r) (float32, all E), chosen = top-k of p + b,
    g = p[chosen] / sum p[chosen];  h = h + sum_k g_k Expert_k(x2)
    Dense / Expert: (silu(x W_g) * (x W_u)) W_d   ([W_g | W_u] stored as w1)
    logits = RMSNorm(h_L) W_head^T               (untied head)

``assumed`` (each stated in the configuration's file): the sink joins the
denominator and adds no value; the window includes the query's own row;
rotate-half pairing over the leading rotary dims; the value scale
multiplies V; ``b`` is noaux_tc's per-expert selection bias (its values
are drawn from the seed); RMSNorm; text only; the MTP modules left out.

Departures, each in the configuration's file: only the experts
``experts_held`` names are computed (a pick of another expert adds
nothing: the chip's share of a sixteen-chip layer, alike in the program);
the vocabulary is the held slice; depth is the first ``num_hidden_layers``
of the published layer list; weights are random from the seed.

``make_weights`` returns the RECIPE (seed and sizes): every layer's weights
are made from the seed inside the jitted function that uses them, the
experts one at a time inside a scan. ``logits`` returns the final NORMED
hidden rows and the recipe (``Scores``): ``gaps`` / ``argmax_rows`` apply
the head to the rows asked for.

``mode``: ``"f32"`` the reference; ``"fp8"`` rounds every linear layer's
weight and input through float8_e4m3 with one scale a tensor (the control
that a lower precision than the configuration states must fail); a name of
``FAULTS`` (``calibrate.py gaps --modes``) is float32 with that ONE
departure from the equations: what a program with that fault would serve.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BF = jnp.bfloat16
Q_BLOCK = 1024
ROW_BLOCK = 4096
#: a sequence longer than this is padded to a multiple of it (rows past
#: its end change none before them): few shapes to compile over a check
ROW_BUCKET = 4096

FAULTS = ("no_sink", "no_window", "window_off_by_one",
          "full_theta_in_window", "rope_all_dims", "no_value_scale",
          "bias_in_gates", "no_bias_in_choice", "no_experts")


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
    kv_full: int
    kv_window: int
    dk: int
    dv: int
    rot: int
    theta_full: float
    theta_window: float
    window: int
    value_scale: float
    sink_full: bool
    sink_window: bool
    d_ff: int
    experts: int
    top_k: int
    f: int
    held_first: int
    held: int
    eps: float
    kinds: Tuple[int, ...]            # 0 full, 1 window, a layer
    moe: Tuple[int, ...]              # 0 dense, 1 experts, a layer


def dims(cfg: dict) -> Dims:
    L = int(cfg["num_hidden_layers"])
    first, held = cfg.get("experts_held", (0, cfg["n_routed_experts"]))
    dk = int(cfg["head_dim"])
    return Dims(
        d=int(cfg["hidden_size"]), vocab=int(cfg["vocab_size"]), layers=L,
        heads=int(cfg["num_attention_heads"]),
        kv_full=int(cfg["num_key_value_heads"]),
        kv_window=int(cfg["swa_num_key_value_heads"]), dk=dk,
        dv=int(cfg["v_head_dim"]),
        rot=int(dk * float(cfg["partial_rotary_factor"])),
        theta_full=float(cfg["rope_theta"]),
        theta_window=float(cfg["swa_rope_theta"]),
        window=int(cfg["sliding_window"]),
        value_scale=float(cfg["attention_value_scale"]),
        sink_full=bool(cfg["add_full_attention_sink_bias"]),
        sink_window=bool(cfg["add_swa_attention_sink_bias"]),
        d_ff=int(cfg["intermediate_size"]),
        experts=int(cfg["router_width"]),
        top_k=int(cfg["num_experts_per_tok"]),
        f=int(cfg["moe_intermediate_size"]),
        held_first=int(first), held=int(held),
        eps=float(cfg["layernorm_epsilon"]),
        kinds=tuple(int(k) for k in cfg["hybrid_layer_pattern"][:L]),
        moe=tuple(int(k) for k in cfg["moe_layer_freq"][:L]))


def make_weights(seed: int, cfg: dict) -> dict:
    """The recipe: weights are made from it where they are used."""
    return {"seed": int(seed), "dims": dims(cfg)}


# ------------------------------------------------------- the weights
#
# Standard deviations are chosen so that every part of the layer shows in
# the logits AND greedy decoding does not collapse (configuration file,
# ``assumed.weights``; PERF.md section 6: a softmax of std-1 scores over
# thousands of keys returns one mean of the values to every query, and a
# random model then repeats ONE token). With unit-RMS inputs a full layer's
# q has std 2.8 and k 1.25, a score (times dk^-0.5) std 3.5; a window
# layer's scores have std 1.5 over at most 128 keys and its sink logits
# 5 + N(0, 1), so that the sink takes about a quarter of a softmax's mass
# deep in a sequence. v has std 1, W_o 1.28 / sqrt(H dv); the embedding
# std 1; the dense MLP, the router and the experts' [W_g | W_u] 0.02, the
# experts' W_d 0.008 (top-8 is a discrete choice that bf16 inputs flip now
# and then against float32); the selection bias N(0, 0.1).

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


def attention_weights(key, D: Dims, window: bool) -> dict:
    """One layer's attention: ``q [d, H dk]``, ``k [d, G dk]``, ``v [d, G
    dv]``, ``o [H dv, d]``, the norm scale and (window layers with a sink)
    ``sink [H]``."""
    ks = jax.random.split(jax.random.fold_in(key, 11), 6)
    H, G = D.heads, (D.kv_window if window else D.kv_full)
    qs, kstd = (1.5, 1.0) if window else (2.8, 1.25)
    r = math.sqrt(D.d)
    w = {"norm": _scale(ks[0], (D.d,)),
         "q": _n(ks[1], (D.d, H * D.dk), qs / r),
         "k": _n(ks[2], (D.d, G * D.dk), kstd / r),
         "v": _n(ks[3], (D.d, G * D.dv), 1.0 / r),
         "o": _n(ks[4], (H * D.dv, D.d), 1.28 / math.sqrt(H * D.dv))}
    if (D.sink_window if window else D.sink_full):
        w["sink"] = 5.0 + jax.random.normal(ks[5], (H,), jnp.float32)
    return w


def ffn_weights(key, D: Dims, dense: bool) -> dict:
    """One layer's FFN outside its routed experts: the norm and either the
    dense gated MLP or the router with its selection bias."""
    ks = jax.random.split(jax.random.fold_in(key, 12), 4)
    if dense:
        return {"norm": _scale(ks[0], (D.d,)),
                "w1": _n(ks[1], (D.d, 2 * D.d_ff)),
                "w2": _n(ks[2], (D.d_ff, D.d))}
    return {"norm": _scale(ks[0], (D.d,)),
            "router": _n(ks[1], (D.d, D.experts)),
            "bias": jax.random.normal(ks[3], (D.experts,), jnp.float32)
            * 0.1}


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


def rope(x, pos, dim: int, theta: float):
    """Rotate-half rotary over the first ``dim`` lanes of the last axis;
    ``pos`` broadcasts over x's leading axes."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2: dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dim:]], -1)


def attention(x, w, D: Dims, window: bool, mode, fault=None):
    """x [s, d] (normed) -> [s, d]: a head at a time, in blocks of
    ``Q_BLOCK`` queries (a window layer's block sees the ``window`` keys
    before it and its own)."""
    s = x.shape[0]
    H = D.heads
    G = D.kv_window if window else D.kv_full
    pos = jnp.arange(s, dtype=jnp.int32)
    theta = D.theta_window if window and fault != "full_theta_in_window" \
        else D.theta_full
    rot = D.dk if fault == "rope_all_dims" else D.rot
    q = rope(_linear(x, w["q"], mode).reshape(s, H, D.dk), pos[:, None],
             rot, theta) * D.dk ** -0.5
    k = rope(_linear(x, w["k"], mode).reshape(s, G, D.dk), pos[:, None],
             rot, theta)
    v = _linear(x, w["v"], mode).reshape(s, G, D.dv)
    if fault != "no_value_scale":
        v = v * D.value_scale
    sink = w.get("sink") if fault != "no_sink" else None
    span = None
    if window and fault != "no_window":
        span = D.window + (1 if fault == "window_off_by_one" else 0)
    qb = min(Q_BLOCK, s)
    pad = -(-s // qb) * qb - s
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    # a window block's keys: the ``span`` positions before it and its own
    reach = qb + span if span is not None else s
    lead = span if span is not None else 0
    kpad = jnp.pad(k, ((lead, pad + qb), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((lead, pad + qb), (0, 0), (0, 0)))

    def head_fn(kh, vh, inp):                # one head
        qh, sh = inp

        def block(i):
            qi = jax.lax.dynamic_slice_in_dim(qh, i * qb, qb, 0)
            first = i * qb if span is not None else 0
            ki = jax.lax.dynamic_slice_in_dim(kh, first, reach, 0)
            vi = jax.lax.dynamic_slice_in_dim(vh, first, reach, 0)
            kpos = first - lead + jnp.arange(reach, dtype=jnp.int32)
            qpos = i * qb + jnp.arange(qb, dtype=jnp.int32)
            vis = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
            if span is not None:
                vis &= kpos[None, :] > qpos[:, None] - span
            sc = jnp.where(vis, jnp.einsum("qd,kd->qk", qi, ki,
                                           precision=HI), -jnp.inf)
            m = jnp.max(sc, -1, keepdims=True)
            if sink is not None:
                m = jnp.maximum(m, sh)
            e = jnp.exp(sc - m)
            den = jnp.sum(e, -1, keepdims=True)
            if sink is not None:
                den = den + jnp.exp(sh - m)
            return jnp.einsum("qk,kd->qd", e, vi, precision=HI) / den

        return jax.lax.map(block, jnp.arange((s + pad) // qb)) \
            .reshape(s + pad, D.dv)[:s]

    def group_fn(inp):                       # one kv head and its queries
        qg, kh, vh, sg = inp
        return jax.lax.map(functools.partial(head_fn, kh, vh), (qg, sg))

    hpg = H // G
    sinks = sink if sink is not None else jnp.zeros((H,), jnp.float32)
    out = jax.lax.map(group_fn, (
        jnp.moveaxis(qp, 1, 0).reshape(G, hpg, s + pad, D.dk),
        jnp.moveaxis(kpad, 1, 0), jnp.moveaxis(vpad, 1, 0),
        sinks.reshape(G, hpg))).reshape(H, s, D.dv)
    att = jnp.moveaxis(out, 0, 1).reshape(s, H * D.dv)
    return _linear(att, w["o"], mode)


def route(x, fw, D: Dims, mode, fault=None):
    """(gates [s, k], idx [s, k]): sigmoid scores over all experts, the
    top-k of scores + bias, the chosen scores renormalised."""
    p = jax.nn.sigmoid(_linear(x, fw["router"], mode))
    b = fw["bias"] if fault != "no_bias_in_choice" else 0.0
    _, idx = jax.lax.top_k(p + b, D.top_k)
    g = jnp.take_along_axis(p + fw["bias"] if fault == "bias_in_gates"
                            else p, idx, -1)
    return g / jnp.sum(g, -1, keepdims=True), idx


def gated(x, w1, w2, mode):
    """``(silu(x W_g) * (x W_u)) W_d`` in blocks of ``ROW_BLOCK`` rows."""
    f = w2.shape[0]

    def rows(xb):
        ab = _linear(xb, w1, mode)
        return _linear(_silu(ab[:, :f]) * ab[:, f:], w2, mode)

    s = x.shape[0]
    if s <= ROW_BLOCK:
        return rows(x)
    pad = -(-s // ROW_BLOCK) * ROW_BLOCK - s
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, x.shape[1])
    return jax.lax.map(rows, xb).reshape(-1, w2.shape[1])[:s]


def moe(x, key, fw, D: Dims, mode, first=None, count=None, fault=None):
    """Routed experts ``first .. first+count-1`` (default: the held ones)
    and nothing else (none: the ``no_experts`` fault)."""
    if fault == "no_experts":
        return jnp.zeros_like(x)
    first = D.held_first if first is None else first
    count = D.held if count is None else count
    gates, idx = route(x, fw, D, mode, fault)

    def one(acc, e):
        w1, w2 = expert_weights(key, D, e)
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + g * gated(x, w1, w2, mode), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), first + jnp.arange(count))
    return out


@functools.partial(jax.jit,
                   static_argnames=("D", "window", "dense", "mode", "fault"))
def _layer(h, key, *, D, window, dense, mode, fault=None):
    aw = attention_weights(key, D, window)
    h = h + attention(_rms(h, aw["norm"], D.eps), aw, D, window, mode,
                      fault)
    fw = ffn_weights(key, D, dense)
    x = _rms(h, fw["norm"], D.eps)
    if dense:
        return h + gated(x, fw["w1"], fw["w2"], mode)
    return h + moe(x, key, fw, D, mode, fault=fault)


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
    s = ids.shape[0]
    pad = -(-s // ROW_BUCKET) * ROW_BUCKET - s if s > ROW_BUCKET else 0
    h = _embed(key, jnp.pad(ids, (0, pad)), D=D)
    for l in range(D.layers):
        h = _layer(h, layer_key(key, l), D=D, window=D.kinds[l] == 1,
                   dense=D.moe[l] == 0, mode=mode, fault=fault)
    return Scores(_final(key, h, D=D)[:s], w["seed"], D, mode)


def gaps(scores: Scores, rows, tokens):
    """For each (row, token): how far the token's reference score lies
    below the reference's best at that row. 0 where the token IS the best."""
    picked = scores.rows(rows)
    return jnp.max(picked, -1) - picked[jnp.arange(rows.shape[0]), tokens]


def argmax_rows(scores: Scores, rows):
    return jnp.argmax(scores.rows(rows), -1).astype(jnp.int32)
