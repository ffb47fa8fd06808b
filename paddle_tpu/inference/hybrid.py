"""A causal LM over ``HybridStack`` and the two programs that serve it.

``HybridCausalLM`` is ``FusedCausalLM``'s sibling: token embedding
(times ``embedding_multiplier``), the pattern-built stack, a final
RMSNorm, the head tied to the embedding or (``tie_embeddings`` false in
the pattern) a matrix of its own (logits divided by
``logits_scaling``). It goes behind the SAME engines
(``ContinuousBatchingEngine`` / ``serving.ServingEngine``): the engines
ask the model for its program family (``_gen_cls``) and otherwise treat
it alike — scheduler, page manager, block tables, the
admit / plan / run / emit framing of a step.

``HybridPrograms`` is that family: the chunked-prefill and decode-chunk
programs with one more donated operand, the slot-indexed
``RecurrentState`` or, for a pattern with window layers, ``WindowRing``
(None where the pattern has neither), which both return rebound; the
pool's two operands hold ``PagedKV``'s K and V sides or the ONE pool of
rows (``LatentKV``, ``PackedKV``) and None. A pattern with window
layers has two rotary tables: the full layers' and the window layers'.
Their XLA module names
are fixed here (``PREFILL_PROGRAM_NAME`` / ``DECODE_PROGRAM_NAME``), not
taken from whatever the Python methods happen to be called, so a trace
reader's pattern survives a rename.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..incubate.nn.fused_transformer import PagedKV, rope_table
from ..incubate.nn.hybrid_stack import HybridStack, RecurrentState
from ..incubate.nn.layer_pattern import (LATENT, SLOT_RECURRENT,
                                         SLOT_RING, LayerPattern)
from ..nn.functional.mixed_attention import PackedKV
from ..nn.functional.mla_attention import LatentKV, yarn_rope_table
from ..nn.layer_base import Layer
from ..profiler import roofline as _roofline
from .engine import GenerationEngine

__all__ = ["HybridCausalLM", "HybridPrograms", "PREFILL_PROGRAM_NAME",
           "DECODE_PROGRAM_NAME"]

#: names of the jitted functions, hence of the XLA modules in a device
#: trace (``jit_<name>(...)``)
PREFILL_PROGRAM_NAME = "pt_hybrid_prefill_chunk"
DECODE_PROGRAM_NAME = "pt_hybrid_decode_chunk"


def _named(fn, name):
    """``fn`` under a fixed ``__name__`` (jit names the module after it)."""
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        return fn(*a, **kw)

    wrapper.__name__ = wrapper.__qualname__ = name
    return wrapper


class HybridPrograms(GenerationEngine):
    """Program family of a pattern-built model (the engines hold one as
    ``_gen``). Same operand order as ``GenerationEngine``'s programs with
    the recurrent state after the pool's two sides."""

    #: the engines hand these programs the recurrent state (or None)
    #: after the pool and read pick counts beside their first result
    pattern_built = True

    def _init_serving_state(self, kv_dtype, quant=None, mesh=None,
                            mp_degree=None, ep_degree=None):
        if quant is not None or mesh is not None or mp_degree \
                or ep_degree:
            raise NotImplementedError(
                "pattern-built stacks are served on one chip in their "
                "weights' dtype: no quant=, mesh=, mp_degree= or "
                "ep_degree= yet (the experts_held slice of the pattern "
                "is how a chip is told its share of the experts)")
        st = self.model.stack
        self._tp = None
        self._a8w8 = False
        self._cdtype = st.e_w1._data.dtype
        self._kv_dtype = kv_dtype or self._cdtype
        # the head ``[vocab, d]`` is contracted over d_model: a
        # transposed copy would cost the table's bytes again; tied, it is
        # the embedding itself
        head = getattr(self.model, "head", None)
        self._head_t = (head if head is not None
                        else self.model.embed)._data
        self._decode_tag = "decode.hybrid"
        self._decode_k_jit = {}
        pat = st.pattern
        self._latent = pat.paged_kind == LATENT
        att = pat.attention
        self._rows_cls = LatentKV if self._latent else (
            PackedKV if pat.pool_row_width is not None else None)
        if self._latent:
            lt = pat.latent
            self._cos, self._sin = yarn_rope_table(
                self.max_length + 1, lt.qk_rope_head_dim, lt.rope_theta,
                lt.yarn)
        elif att is not None and att.rope_theta is not None:
            self._cos, self._sin = rope_table(
                self.max_length + 1, att.rot_dim, att.rope_theta)
        else:
            self._cos = self._sin = None
        sw = pat.window_attention if pat.n_window else None
        # the window layers' own table, or None
        self._rope_w = rope_table(self.max_length + 1, sw.rot_dim,
                                  sw.rope_theta) \
            if sw is not None and sw.rope_theta is not None else None

    def _cache(self, ck, cv):
        return self._rows_cls(ck) if self._rows_cls is not None \
            else PagedKV(ck, cv)

    def _sides(self, cache):
        return (cache.rows, None) if self._rows_cls is not None \
            else (cache.k, cache.v)

    def _window_kw(self, **kw):
        """The phases' window-layer arguments, none without such layers."""
        return dict(kw, rope_w=self._rope_w) \
            if self.model.stack.pattern.n_window else {}

    def _weights(self):
        return self.model.stack._stack()

    def _embed(self):
        return self.model.embed._data

    def _lnf(self):
        return self.model.norm_scale._data, None

    def _get_decode_k(self, k: int, sample_cfg=None,
                      adaptered: bool = False):
        if adaptered or sample_cfg is not None:
            raise NotImplementedError(
                "pattern-built stacks decode greedily without adapters")
        if k not in self._decode_k_jit:
            fn = _named(functools.partial(self._decode_k_fn, k=k),
                        DECODE_PROGRAM_NAME)
            self._decode_k_jit[k] = _roofline.AotProgram(
                self._decode_rung(k),
                jax.jit(fn, donate_argnums=(7, 8, 9)))
        return self._decode_k_jit[k]

    def _get_chunk_prefill(self, rung: str):
        fn = _named(self._chunk_prefill_fn, PREFILL_PROGRAM_NAME)
        return _roofline.AotProgram(
            rung, jax.jit(fn, donate_argnums=(8, 9, 10)))

    # -------------------------------------------------- pure programs

    def _logits(self, h, head, norm_s, _unused=None):
        p = self.model.stack.pattern
        hl = HybridStack._rms(h, norm_s, p.epsilon).astype(head.dtype)
        lg = jax.lax.dot_general(hl, head, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return lg / p.logits_scaling

    def _embed_rows(self, embed, ids):
        p = self.model.stack.pattern
        return (embed[ids].astype(jnp.float32)
                * p.embedding_multiplier).astype(self._cdtype)

    def _chunk_prefill_fn(self, weights, embed, head, norm_s, _nb, ids,
                          start, chunk_len, ck, cv, rs, tables, slot,
                          fresh):
        """One prefill chunk of the sequence parked on ``slot``: its
        recurrent state is read from the slot (zeros where ``fresh``: the
        first chunk after an admission, whatever the slot held before),
        carried through the chunk's valid rows and written back. Returns
        ``((token [1], counts), ck, cv, rs)``: the greedy pick at the
        last valid row (meaningful on a prompt's final chunk)."""
        st = self.model.stack
        kind = st.pattern.slot_state
        s = slot[0]
        state = None
        if kind == SLOT_RING:
            # the ring is read and written at the slot's rows inside the
            # layers: positions, not a reset, keep a new sequence off the
            # last occupant's rows
            state = rs
        elif kind == SLOT_RECURRENT:
            ssm = jax.lax.dynamic_index_in_dim(rs.ssm, s, 1, False)
            conv = jax.lax.dynamic_index_in_dim(rs.conv, s, 1, False)
            state = (jnp.where(fresh[0], jnp.zeros_like(ssm), ssm),
                     jnp.where(fresh[0], jnp.zeros_like(conv), conv))
        x = self._embed_rows(embed, ids)
        h, cache, state, counts = st.prefill_chunk_raw(
            weights, x, self._cache(ck, cv), state, tables, start,
            chunk_len, self._cos, self._sin, **self._window_kw(slot=slot))
        if kind == SLOT_RING:
            rs = state
        elif kind == SLOT_RECURRENT:
            rs = RecurrentState(
                jax.lax.dynamic_update_index_in_dim(rs.ssm, state[0], s, 1),
                jax.lax.dynamic_update_index_in_dim(rs.conv, state[1], s,
                                                    1))
        hl = h[jnp.arange(h.shape[0]), chunk_len - 1]
        tok = self._argmax(self._logits(hl, head, norm_s))
        return (tok, counts), *self._sides(cache), rs

    def _decode_k_fn(self, weights, embed, head, norm_s, _nb, tok,
                     seq_lens, ck, cv, rs, tables, active, *, k):
        """K greedy decode steps as one program; ``active [slots]`` marks
        the rows that decode (the others keep pool and state). Returns
        ``((tokens [slots, k], counts), ck, cv, rs)``."""
        st = self.model.stack

        def step(carry, _):
            tok, lens, ck, cv, rs, counts = carry
            x = self._embed_rows(embed, tok)
            h, cache, rs, c = st.decode_raw(
                weights, x, self._cache(ck, cv), rs, tables, lens, active,
                self._cos, self._sin, **self._window_kw())
            nxt = self._argmax(self._logits(h, head, norm_s))
            return (nxt, lens + 1, *self._sides(cache), rs,
                    counts + c), nxt

        init = (tok, seq_lens, ck, cv, rs, jnp.zeros((4,), jnp.int32))
        (_, _, ck, cv, rs, counts), toks = jax.lax.scan(
            step, init, None, length=k)
        return (jnp.swapaxes(toks, 0, 1), counts), ck, cv, rs

    def generate(self, *a, **kw):
        raise NotImplementedError(
            "pattern-built stacks are served through "
            "ContinuousBatchingEngine / serving.ServingEngine")


class HybridCausalLM(Layer):
    """Token embedding + ``HybridStack`` + final RMSNorm + the head (the
    embedding itself, or ``head [vocab, d]`` where the pattern says
    ``tie_embeddings=False``). ``draw=False`` starts every drawn
    parameter as zeros (``HybridStack``)."""

    #: the program family the engines build for this model
    _gen_cls = HybridPrograms

    def __init__(self, vocab_size: int, pattern: LayerPattern,
                 dtype=jnp.float32, draw: bool = True):
        super().__init__()
        from ..core.generator import default_generator
        from ..core.tensor import Parameter

        self.vocab_size = vocab_size
        from ..incubate.nn.hybrid_stack import _draw

        def table():
            shape, dt = (vocab_size, pattern.d_model), jnp.dtype(dtype)
            if not draw:
                return jnp.zeros(shape, dt)
            return _draw(default_generator().next_key(), shape, dt, 0.02)

        self.embed = Parameter(table())
        if not pattern.tie_embeddings:
            self.head = Parameter(table())
        self.stack = HybridStack(pattern, dtype=dtype, draw=draw)
        self.norm_scale = Parameter(
            jnp.ones((pattern.d_model,), jnp.float32))
