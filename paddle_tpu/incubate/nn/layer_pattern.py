"""The description a served stack is built from: a period of layer kinds,
the widths of each kind, the block's norm / gating / multipliers, and
which experts this chip holds.

One description, two stacks. ``FusedMultiTransformer`` (pre-LN
LayerNorm, biased GELU FFN, rotary GQA, one kind) reports itself as the
one-kind pattern (``LayerPattern.uniform_attention``);
``HybridStack`` (``incubate/nn/hybrid_stack.py``) is BUILT from a
pattern whose layers mix the four kinds below. The serving engines
read only the description: which layers need pages and of what shape
(``n_paged`` / ``paged_kind`` / ``pool_row_width``), which slot-indexed
state has to live beside the pool (``slot_state``: ``recurrent`` or
``window_ring``), and the widths of each.

The kinds, and the cache group each has:

``"attention"``         rotary or NoPE GQA; K and V per kv-head in the
    paged pool ``PagedKV`` (two arrays ``[layers x pages, n_kv, page,
    head_dim]``), or, where V heads are narrower than K heads
    (``AttentionSpec.v_head_dim``), ``PackedKV``: ONE array ``[layers x
    pages, page, row]``, a row every kv head's V then every kv head's K.
``"window_attention"``  GQA over the last ``window`` positions with an
    optional per-head sink logit; no pages: a slot-indexed ring of
    ``window`` rows a layer (``WindowRing``) beside the pool.
``"latent_attention"``  multi-head latent attention (queries through a
    low rank, keys and values expanded from ONE latent row a token, one
    rotary head shared by all query heads, YaRN frequencies, a
    position-dependent query temperature); the paged latent pool
    ``LatentKV`` (ONE array ``[layers x pages, page, row]``, a row the
    normed latent followed by the rotated rope key, padded to whole
    lane tiles).
``"mamba"``             Mamba-2 mixer; no pages, the slot-indexed
    ``RecurrentState``.

One engine owns ONE paged pool and ONE slot-indexed state, so a pattern
holds ``"attention"`` or ``"latent_attention"`` layers, not both, and
``"mamba"`` or ``"window_attention"`` layers, not both.

The layers are ``lead`` (kinds before the first period) followed by
``period`` repeated ``n_periods`` times, cut to ``depth`` layers where
that is set. A weight stack a kind, a cache group a kind: layer ``i``
is kind ``kinds()[i]`` and the ``kind_index(i)``-th entry of that kind's
stacks, of the paged pool's layer fold (both paged kinds), of the
recurrent state's leading axis (mamba) or of the window ring's (window
attention). The first ``dense_lead`` layers take a dense gated FFN of
width ``d_ff``; every other layer takes the routed experts, the
``moe_index(i)``-th entry of their stacks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

__all__ = ["AttentionSpec", "LatentAttentionSpec", "YarnSpec",
           "MambaSpec", "MoESpec", "LayerPattern", "RecurrentSpec",
           "WindowRingSpec", "ATTENTION", "LATENT", "MAMBA", "WINDOW",
           "SLOT_RECURRENT", "SLOT_RING"]

ATTENTION = "attention"
LATENT = "latent_attention"
MAMBA = "mamba"
WINDOW = "window_attention"
KINDS = (ATTENTION, LATENT, MAMBA, WINDOW)
ROUTINGS = ("softmax_topk", "sigmoid_bias")
#: what a slot keeps beside the pool (``LayerPattern.slot_state``)
SLOT_RECURRENT = "recurrent"
SLOT_RING = "window_ring"


def _lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane tiles."""
    return -(-n // 128) * 128


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    #: softmax scale; None = 1/sqrt(head_dim)
    scale: Optional[float] = None
    #: rotary base; None = no positional encoding (NoPE)
    rope_theta: Optional[float] = 10000.0
    #: V head width where it differs from the q / k heads' (None: the same)
    v_head_dim: Optional[int] = None
    #: leading dims of a q / k head that rotate (rotate-half pairing over
    #: them); None = all of ``head_dim``
    rope_dim: Optional[int] = None
    #: query ``i`` sees keys ``i - window < j <= i`` (None: all ``j <= i``)
    window: Optional[int] = None
    #: a learnt per-head logit that joins every softmax's denominator and
    #: adds no value
    sink: bool = False
    #: what the attended values are multiplied by
    value_scale: float = 1.0

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5 if self.scale is None \
            else float(self.scale)

    @property
    def v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def rot_dim(self) -> int:
        return self.rope_dim or self.head_dim

    @property
    def packed(self) -> bool:
        """K and V of a token live in ONE row (``PackedKV``): V heads of
        their own width cannot share ``PagedKV``'s one ``head_dim``."""
        return self.v_head_dim is not None

    @property
    def row_width(self) -> int:
        """Values a packed row STORES: every kv head's V, then every kv
        head's K, padded to whole lane tiles."""
        return _lanes(self.num_kv_heads * (self.head_dim + self.v_dim))

    @property
    def k_lanes(self) -> int:
        """A K head as a window ring stores it: whole lane tiles."""
        return _lanes(self.head_dim)


@dataclasses.dataclass(frozen=True)
class YarnSpec:
    """YaRN scaling of a rotary table: per pair the frequency blends
    ``theta^(-2i/dim)`` and that over ``factor`` by a linear ramp
    between the correction dimensions of ``beta_fast`` and ``beta_slow``
    rotations over ``original_max_position`` positions (floor / ceil)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, m: float) -> float:
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    @property
    def table_factor(self) -> float:
        """What cos and sin are multiplied by."""
        return self._mscale(self.factor, self.mscale) \
            / self._mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_factor(self) -> float:
        """What the softmax scale is multiplied by (``m^2``)."""
        return self._mscale(self.factor, self.mscale_all_dim) ** 2


@dataclasses.dataclass(frozen=True)
class LatentAttentionSpec:
    """Multi-head latent attention. Queries ``x W_dq`` (``q_lora_rank``)
    normed then up to ``num_heads x (nope + rope)``; ``x W_dkv`` gives
    the latent (``kv_lora_rank``, normed) and one rope key;
    ``[k_nope | v]`` of a head are the latent times ``W_ukv``. The cache
    row of a token and layer is the normed latent and the rotated rope
    key."""
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    #: rotary pairs are ADJACENT lanes (2i, 2i + 1)
    yarn: Optional[YarnSpec] = None
    #: query temperature ``1 + beta ln(1 + floor(pos / period))``; None = 1
    temperature_beta: Optional[float] = None
    temperature_period: int = 8192

    @property
    def softmax_scale(self) -> float:
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        return s * (self.yarn.softmax_factor if self.yarn else 1.0)

    @property
    def row_used(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """Values a cache row STORES: ``row_used`` padded to whole
        128-lane tiles (the chip's tiled layout stores a 320-wide minor
        dimension as 384 either way; the pad is explicit so that the
        kernels' blocks are lane-aligned and the bytes are counted)."""
        return -(-self.row_used // 128) * 128


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    """Mamba-2 mixer widths (``d_inner = num_heads * head_dim``)."""
    num_heads: int
    head_dim: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk_size: int = 256

    @property
    def d_inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def in_proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.num_heads


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """Routed experts of one FFN: the router scores ``num_experts``;
    ``routing`` ``"softmax_topk"`` takes the ``top_k`` largest logits and
    the softmax over them as gates, ``"sigmoid_bias"`` takes the top_k of
    ``sigmoid(logits) + bias`` (a per-expert selection bias) and the
    chosen sigmoid scores, renormalised, times ``scaling`` as gates;
    ``experts_held = (first, count)`` names the contiguous slice of
    experts this chip stores and computes (a pick that names another
    expert adds nothing here)."""
    num_experts: int
    top_k: int
    expert_dim: int
    shared_dim: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    routing: str = "softmax_topk"
    scaling: float = 1.0

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)


@dataclasses.dataclass(frozen=True)
class RecurrentSpec:
    """Shapes of the slot-indexed state the recurrent layers keep beside
    the paged pool: ``ssm [layers, slots, d_state, d_inner]`` float32
    and the conv tail ``[layers, slots, d_conv - 1, conv_dim]``."""
    layers: int
    d_state: int
    d_inner: int
    conv_rows: int
    conv_dim: int

    def bytes_per_slot(self, conv_itemsize: int = 2) -> int:
        return self.layers * (self.d_state * self.d_inner * 4
                              + self.conv_rows * self.conv_dim
                              * conv_itemsize)


@dataclasses.dataclass(frozen=True)
class WindowRingSpec:
    """Shapes of the slot-indexed ring the window layers keep beside the
    paged pool: ``k [layers, slots, heads, rows, k_width]`` and ``v
    [layers, slots, heads, rows, v_width]``; position ``p`` of a slot's
    sequence lives in row ``p % rows``."""
    layers: int
    heads: int
    rows: int
    k_width: int
    v_width: int

    def bytes_per_slot(self, itemsize: int = 2) -> int:
        return self.layers * self.heads * self.rows \
            * (self.k_width + self.v_width) * itemsize


@dataclasses.dataclass(frozen=True)
class LayerPattern:
    d_model: int
    period: Tuple[str, ...]
    n_periods: int
    attention: Optional[AttentionSpec] = None
    mamba: Optional[MambaSpec] = None
    latent: Optional[LatentAttentionSpec] = None
    #: the output head is the embedding itself (False: a matrix of its own)
    tie_embeddings: bool = True
    #: dense FFN width (``moe`` None) — gated halves it into a | b
    d_ff: int = 0
    moe: Optional[MoESpec] = None
    norm: str = "layernorm"            # | "rmsnorm"
    gated: bool = False                # SiLU-gated FFN / experts
    bias: bool = True
    activation: str = "gelu"
    epsilon: float = 1e-5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0        # logits are DIVIDED by it
    #: the window_attention kind's widths, window and sink
    window_attention: Optional[AttentionSpec] = None
    #: kinds of the layers before the first period
    lead: Tuple[str, ...] = ()
    #: layers served (None: all of lead + period x n_periods)
    depth: Optional[int] = None
    #: the first layers whose FFN is dense (``d_ff``) instead of experts
    dense_lead: int = 0

    def __post_init__(self):
        bad = [k for k in tuple(self.lead) + tuple(self.period)
               if k not in KINDS]
        if bad or not self.period:
            raise ValueError(f"LayerPattern: unknown layer kinds {bad} "
                             f"(known: {', '.join(map(repr, KINDS))})")
        full = len(self.lead) + len(self.period) * self.n_periods
        if self.depth is not None and not 0 < self.depth <= full:
            raise ValueError(f"LayerPattern: depth {self.depth} outside "
                             f"1..{full}")
        kinds = set(self.kinds())
        if ATTENTION in kinds and self.attention is None:
            raise ValueError("LayerPattern: attention layers need an "
                             "AttentionSpec")
        if LATENT in kinds and self.latent is None:
            raise ValueError("LayerPattern: latent_attention layers need "
                             "a LatentAttentionSpec")
        if LATENT in kinds and ATTENTION in kinds:
            raise ValueError(
                "LayerPattern: attention and latent_attention layers in "
                "one pattern would need two paged pools; an engine owns "
                "one")
        if MAMBA in kinds and self.mamba is None:
            raise ValueError("LayerPattern: mamba layers need a "
                             "MambaSpec")
        if WINDOW in kinds:
            sw = self.window_attention
            if sw is None or not sw.window:
                raise ValueError("LayerPattern: window_attention layers "
                                 "need an AttentionSpec with a window")
            if MAMBA in kinds or LATENT in kinds:
                raise ValueError(
                    "LayerPattern: window_attention layers beside mamba "
                    "or latent_attention layers would need two slot "
                    "states or a latent pool beside the window ring; an "
                    "engine owns one of each kind of cache")
        if self.dense_lead and not (0 < self.dense_lead <= self.num_layers
                                    and self.d_ff > 0):
            raise ValueError("LayerPattern: dense_lead layers need d_ff "
                             "and at most num_layers of them")
        if self.moe is not None and self.moe.routing not in ROUTINGS:
            raise ValueError(f"LayerPattern: routing {self.moe.routing!r} "
                             f"(known: {', '.join(ROUTINGS)})")

    # ---------------------------------------------------- the layers

    @property
    def num_layers(self) -> int:
        return len(self.kinds())

    def kinds(self) -> Tuple[str, ...]:
        kinds = tuple(self.lead) + tuple(self.period) * self.n_periods
        return kinds if self.depth is None else kinds[:self.depth]

    def count(self, kind: str) -> int:
        return self.kinds().count(kind)

    def kind_index(self, layer: int) -> int:
        """Index of ``layer`` inside its own kind's stacks."""
        kinds = self.kinds()
        return sum(1 for k in kinds[:layer] if k == kinds[layer])

    def moe_index(self, layer: int) -> int:
        """Index of an expert layer inside the experts' stacks."""
        return layer - self.dense_lead

    @property
    def n_moe(self) -> int:
        return self.num_layers - self.dense_lead

    @property
    def n_window(self) -> int:
        return self.count(WINDOW)

    @property
    def n_attention(self) -> int:
        return self.count(ATTENTION)

    @property
    def n_latent(self) -> int:
        return self.count(LATENT)

    @property
    def n_mamba(self) -> int:
        return self.count(MAMBA)

    @property
    def paged_kind(self) -> Optional[str]:
        """The kind whose layers keep their history in pages, or None."""
        if self.n_latent:
            return LATENT
        return ATTENTION if self.n_attention else None

    @property
    def n_paged(self) -> int:
        return self.n_latent or self.n_attention

    @property
    def pool_row_width(self) -> Optional[int]:
        """Width of the pool's ONE array of rows (a latent pool, a pool
        of packed K|V rows), or None for ``PagedKV``'s K and V sides."""
        if self.n_latent:
            return self.latent.row_width
        if self.n_attention and self.attention.packed:
            return self.attention.row_width
        return None

    @property
    def slot_state(self) -> Optional[str]:
        """What a slot keeps beside the pool, the one answer the stack,
        its programs and the engines read: ``SLOT_RECURRENT`` (the mamba
        layers' ``RecurrentState``, zeroed at a sequence's first chunk),
        ``SLOT_RING`` (the window layers' ``WindowRing``, whose rows the
        sequence's positions keep apart) or None."""
        if self.n_mamba:
            return SLOT_RECURRENT
        return SLOT_RING if self.n_window else None

    @property
    def window_ring(self) -> Optional[WindowRingSpec]:
        """What the cache manager holds a slot for the window layers, or
        None for a pattern without any."""
        if not self.n_window:
            return None
        sw = self.window_attention
        return WindowRingSpec(self.n_window, sw.num_kv_heads, sw.window,
                              sw.k_lanes, sw.v_dim)

    @property
    def recurrent(self) -> Optional[RecurrentSpec]:
        """What the cache manager has to hold a slot, or None for a
        pattern whose every layer keeps its history in pages."""
        if not self.n_mamba:
            return None
        m = self.mamba
        return RecurrentSpec(self.n_mamba, m.d_state, m.d_inner,
                             m.d_conv - 1, m.conv_dim)

    @classmethod
    def uniform_attention(cls, d_model, num_layers, num_heads,
                          num_kv_heads, head_dim, d_ff, *,
                          rope_theta=10000.0, epsilon=1e-5,
                          activation="gelu", moe=None):
        """The one-kind pattern: what ``FusedMultiTransformer`` serves."""
        return cls(d_model=d_model, period=(ATTENTION,),
                   n_periods=num_layers,
                   attention=AttentionSpec(num_heads, num_kv_heads,
                                           head_dim, None, rope_theta),
                   d_ff=d_ff, moe=moe, norm="layernorm", gated=False,
                   bias=True, activation=activation, epsilon=epsilon)
