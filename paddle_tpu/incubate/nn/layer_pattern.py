"""The description a served stack is built from: a period of layer kinds,
the widths of each kind, the block's norm / gating / multipliers, and
which experts this chip holds.

One description, two stacks. ``FusedMultiTransformer`` (pre-LN
LayerNorm, biased GELU FFN, rotary GQA, one kind) reports itself as the
one-kind pattern (``LayerPattern.uniform_attention``);
``HybridStack`` (``incubate/nn/hybrid_stack.py``) is BUILT from a
pattern whose period mixes the three kinds below. The serving engines
read only the description: which layers need pages and of what shape
(``n_paged`` / ``paged_kind``), whether slot-indexed recurrent state
has to live beside the pool (``recurrent``), and the widths of each.

The kinds, and the cache group each has:

``"attention"``         rotary or NoPE GQA; K and V per kv-head in the
    paged pool ``PagedKV`` (two arrays ``[layers x pages, n_kv, page,
    head_dim]``).
``"latent_attention"``  multi-head latent attention (queries through a
    low rank, keys and values expanded from ONE latent row a token, one
    rotary head shared by all query heads, YaRN frequencies, a
    position-dependent query temperature); the paged latent pool
    ``LatentKV`` (ONE array ``[layers x pages, page, row]``, a row the
    normed latent followed by the rotated rope key, padded to whole
    lane tiles).
``"mamba"``             Mamba-2 mixer; no pages, the slot-indexed
    ``RecurrentState``.

One engine owns ONE paged pool, so a pattern holds ``"attention"`` or
``"latent_attention"`` layers, not both.

A weight stack a kind, a cache group a kind: layer ``i`` of the model
is kind ``period[i % len(period)]`` and the ``kind_index(i)``-th entry
of that kind's stacks, of the paged pool's layer fold (both attention
kinds) or of the recurrent state's leading axis (mamba).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

__all__ = ["AttentionSpec", "LatentAttentionSpec", "YarnSpec",
           "MambaSpec", "MoESpec", "LayerPattern", "RecurrentSpec",
           "ATTENTION", "LATENT", "MAMBA"]

ATTENTION = "attention"
LATENT = "latent_attention"
MAMBA = "mamba"
KINDS = (ATTENTION, LATENT, MAMBA)


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    #: softmax scale; None = 1/sqrt(head_dim)
    scale: Optional[float] = None
    #: rotary base; None = no positional encoding (NoPE)
    rope_theta: Optional[float] = 10000.0

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5 if self.scale is None \
            else float(self.scale)


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
    """Routed experts of one FFN: the router scores ``num_experts``,
    the ``top_k`` largest are taken and the gates are the softmax over
    the chosen logits; ``experts_held = (first, count)`` names the
    contiguous slice of experts this chip stores and computes (a pick
    that names another expert adds nothing here)."""
    num_experts: int
    top_k: int
    expert_dim: int
    shared_dim: int = 0
    experts_held: Optional[Tuple[int, int]] = None

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

    def __post_init__(self):
        bad = [k for k in self.period if k not in KINDS]
        if bad or not self.period:
            raise ValueError(f"LayerPattern: unknown layer kinds {bad} "
                             f"(known: {', '.join(map(repr, KINDS))})")
        if ATTENTION in self.period and self.attention is None:
            raise ValueError("LayerPattern: attention layers need an "
                             "AttentionSpec")
        if LATENT in self.period and self.latent is None:
            raise ValueError("LayerPattern: latent_attention layers need "
                             "a LatentAttentionSpec")
        if LATENT in self.period and ATTENTION in self.period:
            raise ValueError(
                "LayerPattern: attention and latent_attention layers in "
                "one pattern would need two paged pools; an engine owns "
                "one")
        if MAMBA in self.period and self.mamba is None:
            raise ValueError("LayerPattern: mamba layers need a "
                             "MambaSpec")

    # ---------------------------------------------------- the layers

    @property
    def num_layers(self) -> int:
        return len(self.period) * self.n_periods

    def kinds(self) -> Tuple[str, ...]:
        return tuple(self.period) * self.n_periods

    def count(self, kind: str) -> int:
        return self.period.count(kind) * self.n_periods

    def kind_index(self, layer: int) -> int:
        """Index of ``layer`` inside its own kind's stacks."""
        kinds = self.kinds()
        return sum(1 for k in kinds[:layer] if k == kinds[layer])

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
