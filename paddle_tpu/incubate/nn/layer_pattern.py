"""The description a served stack is built from: a period of layer kinds,
the widths of each kind, the block's norm / gating / multipliers, and
which experts this chip holds.

One description, two stacks. ``FusedMultiTransformer`` (pre-LN
LayerNorm, biased GELU FFN, rotary GQA, one kind) reports itself as the
one-kind pattern (``LayerPattern.uniform_attention``);
``HybridStack`` (``incubate/nn/hybrid_stack.py``) is BUILT from a
pattern whose period mixes ``"mamba"`` and ``"attention"`` layers. The
serving engines read only the description: how many attention layers
need pages (``n_attention``), whether slot-indexed recurrent state has
to live beside the pool (``recurrent``), and the widths of both.

A weight stack a kind, a cache group a kind: layer ``i`` of the model
is kind ``period[i % len(period)]`` and the ``kind_index(i)``-th entry
of that kind's stacks, of the paged pool's layer fold (attention) or of
the recurrent state's leading axis (mamba).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["AttentionSpec", "MambaSpec", "MoESpec", "LayerPattern",
           "RecurrentSpec", "ATTENTION", "MAMBA"]

ATTENTION = "attention"
MAMBA = "mamba"


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
        bad = [k for k in self.period if k not in (ATTENTION, MAMBA)]
        if bad or not self.period:
            raise ValueError(f"LayerPattern: unknown layer kinds {bad} "
                             f"(known: {ATTENTION!r}, {MAMBA!r})")
        if ATTENTION in self.period and self.attention is None:
            raise ValueError("LayerPattern: attention layers need an "
                             "AttentionSpec")
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
    def n_mamba(self) -> int:
        return self.count(MAMBA)

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
