"""Generation engine: compiled prefill + paged-KV decode loop.

TPU-native equivalent of the reference's fused-decode serving spine
(reference: paddle/fluid/operators/fused/fused_multi_transformer_op.cu
driving AnalysisPredictor-run programs, with paged KV via
block_multi_head_attention_kernel.cu). Here both phases are single XLA
programs: prefill(x[b,s]) and decode_step(token[b]) are jit-compiled
once per shape with the cache donated, so steady-state decode is one
device program per token with zero host round-trips in the stack.
"""
from __future__ import annotations

import itertools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..incubate.nn.fused_transformer import (
    FusedMultiTransformer, PagedKV, rope_table)
from ..incubate.nn.layer_pattern import LATENT
from ..nn.layer_base import Layer
from ..profiler import RecordEvent
from ..profiler import roofline as _roofline
from ..profiler import stats as _stats
from .kv_cache import BlockKVCacheManager, gather_rows, restore_scatter_jit

__all__ = ["FusedCausalLM", "GenerationEngine",
           "ContinuousBatchingEngine", "GenRequest",
           "DEFAULT_DECODE_CHUNK", "RecurrentStateUnsupported",
           "LatentPoolUnsupported"]


class RecurrentStateUnsupported(NotImplementedError):
    """A mechanism that snapshots, shares or replays PAGES was asked of a
    model whose recurrent layers keep slot-indexed state beside the pool
    (prefix reuse, speculative verify, slot migration, host-tier spill):
    carrying pages alone would be silently wrong, so the engine refuses.
    Counted in ``serving.recurrent.refusals``."""


def _refuse_recurrent(what: str):
    _stats.inc("serving.recurrent.refusals")
    raise RecurrentStateUnsupported(
        f"{what} moves K/V pages only; this model's recurrent layers "
        "keep slot-indexed state that it would leave behind")



class LatentPoolUnsupported(NotImplementedError):
    """A path that moves, shares or re-types K/V pages was asked of a model
    whose attention layers keep ONE latent row a token in the pool
    (``LatentKV``): prefix reuse, host-tier spill, slot export / import,
    page streaming, speculative verify, the int8 pool. None of them has
    been shown on latent pages, so each refuses instead of guessing.
    Counted in ``serving.latent.refusals``."""


def _refuse_latent(what: str):
    _stats.inc("serving.latent.refusals")
    raise LatentPoolUnsupported(
        f"{what} is written for K and V pages side by side; this model's "
        "pool holds one latent row a token and nothing here has been "
        "shown to move or share those")

#: auto-picked decode scan-chunk: 128 measured best on the 1.3B bench
#: geometry (chunk 64 -> 128: +7% tok/s, bench_profile.json r5 — one
#: scan program covers the whole generation, so chunk-boundary pool
#: relayout + the per-chunk host sync amortize). Callers pass an
#: explicit ``decode_chunk`` to override (small chunks keep
#: continuous-batching admit latency low on interactive traffic).
DEFAULT_DECODE_CHUNK = 128


def _resolve_decode_chunk(decode_chunk) -> int:
    if decode_chunk is None:
        return DEFAULT_DECODE_CHUNK
    return max(int(decode_chunk), 1)


def _round_pool_pages(n: int, page_size: int) -> int:
    """Round a pool size up so a stream-attention chunk size divides it
    — the chunk DMA then never crosses the layer-region boundary.

    The rounding quantum is the FULL chunk (stream_chunk_pages, 1024
    tokens) capped at the next power of two >= n: without the cap, tiny
    pools at small page sizes inflate drastically (page_size=4: 25
    requested pages -> 256, ~10x HBM). With it, the pool stays within
    2x of the request and remains a power-of-two multiple that
    _pick_chunk_pages can divide exactly (the kernels then run with a
    proportionally smaller chunk — fine for pools this small). The
    engines expose the final rounded size via the
    ``inference.pool_pages`` stats gauge."""
    from ..nn.functional.paged_attention import stream_chunk_pages

    chunk = stream_chunk_pages(page_size)
    next_pow2 = 1
    while next_pow2 < n:
        next_pow2 *= 2
    quantum = min(chunk, next_pow2)
    return -(-n // quantum) * quantum


class FusedCausalLM(Layer):
    """Minimal GPT-style causal LM over FusedMultiTransformer:
    token embedding (tied lm head) + stack + final LN."""

    def __init__(self, vocab_size, embed_dim, num_heads, dim_feedforward,
                 num_layers, num_kv_heads=None, max_position=32768,
                 rope_theta=10000.0, moe_num_experts=None, moe_top_k=2):
        super().__init__()
        from ..core.tensor import Parameter

        from ..core.generator import default_generator

        self.vocab_size = vocab_size
        self.embed = Parameter(
            jax.random.normal(default_generator().next_key(),
                              (vocab_size, embed_dim), jnp.float32) * 0.02)
        self.stack = FusedMultiTransformer(
            embed_dim, num_heads, dim_feedforward, num_layers,
            num_kv_heads=num_kv_heads, max_position=max_position,
            rope_theta=rope_theta, moe_num_experts=moe_num_experts,
            moe_top_k=moe_top_k)
        self.lnf_scale = Parameter(jnp.ones((embed_dim,), jnp.float32))
        self.lnf_bias = Parameter(jnp.zeros((embed_dim,), jnp.float32))

    def _final(self, h):
        h = FusedMultiTransformer._ln(
            h, self.lnf_scale._data, self.lnf_bias._data,
            self.stack.epsilon)
        return h @ self.embed._data.T

    def forward(self, ids):
        """Plain full-sequence forward (training/eval parity path):
        logits [b, s, vocab]. No cache involved."""
        ids_d = ids._data if isinstance(ids, Tensor) else jnp.asarray(ids)
        x = self.embed._data[ids_d]
        cos_t, sin_t = rope_table(self.stack.max_position,
                                  self.stack.head_dim,
                                  self.stack.rope_theta)
        h, _ = self.stack.prefill_raw(
            self.stack._stack(), x, None, None, cos_t, sin_t)
        return Tensor(self._final(h))


class GenerationEngine:
    """Continuous single-batch generation over a FusedCausalLM.

    generate(): prefill the prompt (one compiled program), then a
    compiled decode step per token. The decode program takes and returns
    the paged cache with donated buffers — the cache never leaves HBM.
    """

    def __init__(self, model: FusedCausalLM, page_size: int = 16,
                 max_length: int = 1024, num_pages: Optional[int] = None,
                 decode_chunk: Optional[int] = None, kv_dtype=None,
                 quant: Optional[str] = None, mesh=None,
                 mp_degree: Optional[int] = None,
                 ep_degree: Optional[int] = None):
        self.model = model
        st = model.stack
        self.max_length = max_length
        self.page_size = page_size
        self.decode_chunk = _resolve_decode_chunk(decode_chunk)
        self._cos, self._sin = rope_table(st.max_position, st.head_dim,
                                          st.rope_theta)
        self._init_serving_state(kv_dtype, quant, mesh=mesh,
                                 mp_degree=mp_degree,
                                 ep_degree=ep_degree)
        self._num_pages = num_pages
        self._mgr = None

    def _init_serving_state(self, kv_dtype, quant=None, mesh=None,
                            mp_degree=None, ep_degree=None):
        """Serving dtype discipline + compiled-program holders (shared
        with ContinuousBatchingEngine): the COMPUTE dtype follows the
        stack weights (cast them bf16 for the bandwidth-bound serving
        path; fp32 stacks keep exact dense parity; int8 = weight-only
        quantized → compute bf16), the KV pool follows kv_dtype
        (default: same as compute), and the lm head is a PRE-TRANSPOSED
        [d, vocab] copy in compute dtype with fp32 accumulation in the
        logits dot.

        ``quant``: None | "int8" (weight-only) | "a8w8" (weight-only
        int8 PLUS per-token dynamic int8 activations into int8 x int8
        matmuls). Both quantize the model's stack IN PLACE when it is
        not already int8.

        ``mesh`` / ``mp_degree``: tensor-parallel serving over an
        ``mp`` mesh axis (distributed/tp.py). The stacked weights are
        sharded AT LOAD — column/row slices per chip, the QKV columns
        rearranged so attention heads partition with them — the KV
        pool shards by kv-head, and every decode/prefill program runs
        under shard_map with exactly one psum per column→row
        projection pair. Rungs report with an ``,mp=N`` suffix and
        ``dist.mp_degree`` lands in telemetry."""
        if quant not in (None, "int8", "a8w8"):
            raise ValueError(
                f"quant={quant!r}: expected None, 'int8' or 'a8w8'")
        st = self.model.stack
        from ..distributed.tp import TPContext

        self._tp = TPContext.create(
            st.num_heads, st.num_kv_heads, st.head_dim,
            mp_degree=mp_degree, mesh=mesh, ep_degree=ep_degree)
        if self._tp is not None and self._tp.ep > 1 \
                and not st.moe_num_experts:
            raise ValueError(
                "ep_degree shards the MoE expert bank — the stack has "
                "no experts (pass moe_num_experts to the model, or "
                "use mp_degree for dense tensor parallelism)")
        if quant is not None and \
                st.qkv_weight._data.dtype != jnp.int8:
            st.quantize_weight_only_int8()
        self._a8w8 = quant == "a8w8"
        wd = st.qkv_weight._data.dtype
        self._cdtype = jnp.bfloat16 if wd == jnp.int8 else wd
        self._kv_dtype = kv_dtype or self._cdtype
        self._head_t = jnp.array(self.model.embed._data.T) \
            .astype(self._cdtype)
        if self._tp is not None:
            # shard-at-load: per-chip column/row weight slices; the
            # replicated operands (embed, lm head, final LN) are
            # device_put once so no per-call host transfer (and no
            # mixing of single-device-committed arrays into the
            # mesh-sharded programs)
            tp = self._tp
            self._tp_weights = tp.shard_stack(st._stack())
            self._head_t = tp.replicate(self._head_t)
            self._embed_tp = tp.replicate(self.model.embed._data)
            self._lnf_tp = (tp.replicate(self.model.lnf_scale._data),
                            tp.replicate(self.model.lnf_bias._data))
            _stats.set_gauge("dist.mp_degree", tp.mp)
            if tp.ep > 1:
                _stats.set_gauge("dist.ep_degree", tp.ep)
        # roofline rung names follow the layer loop the stack says it
        # runs (``decode_loop``: the one place that decides), so the
        # serving modes' achieved-bandwidth rows never mix: MoE and A8W8
        # under their own keys, the grouped weight-stream loop under
        # ``decode.<dtype>_grouped``
        if st.decode_loop(a8w8=self._a8w8) == "grouped":
            wname = ("int8" if wd == jnp.int8 else
                     "bf16" if self._cdtype == jnp.bfloat16 else "f32")
            self._decode_tag = f"decode.{wname}_grouped"
        else:
            self._decode_tag = ("decode.moe" if st.moe_num_experts
                                else "decode.a8w8")
        # one jitted prefill; decode programs are per-chunk-size (k=1
        # is the single-token step); cache operands are donated. Both
        # dispatch through the explicit-AOT wrapper so each program's
        # XLA cost model (flops, bytes accessed — the decode step's
        # weight+KV traffic) feeds the roofline telemetry
        # (profiler/roofline.py) instead of a hand-derived byte count.
        self._prefill = _roofline.AotProgram(
            ("prefill.a8w8" if self._a8w8 else "prefill")
            + self._mp_suffix(),
            jax.jit(self._prefill_fn, donate_argnums=(7, 8)))
        self._decode_k_jit = {}

    def _dist_coords(self) -> str:
        """``mp=N`` / ``ep=N`` rung coordinates under tensor/expert
        parallelism (README metric conventions)."""
        if self._tp is None:
            return ""
        parts = []
        if self._tp.mp > 1:
            parts.append(f"mp={self._tp.mp}")
        if self._tp.ep > 1:
            parts.append(f"ep={self._tp.ep}")
        return ",".join(parts)

    def _mp_suffix(self) -> str:
        """``[mp=N]``/``[ep=N]`` rung suffix under tensor/expert
        parallelism (composes as ``[k=*,mp=N]`` on decode)."""
        c = self._dist_coords()
        return f"[{c}]" if c else ""

    def _decode_rung(self, k: int, adaptered: bool = False) -> str:
        """Roofline rung name of the k-step decode program —
        ``decode.bf16_grouped[k=8,mp=2]``-shaped under TP. The
        adaptered variant (multi-LoRA delta path) is its own rung:
        it runs the per-projection f32 loop, not the grouped tail."""
        c = self._dist_coords()
        tag = "decode.lora" if adaptered else self._decode_tag
        return f"{tag}[k={k}{',' + c if c else ''}]"

    def _weights(self):
        """The decode/prefill weight-stack operand: the shard-at-load
        TP stacks when a mesh is configured, the model's plain stacked
        dict otherwise (fresh dict of the same arrays — cheap)."""
        return self._tp_weights if self._tp is not None \
            else self.model.stack._stack()

    def _embed(self):
        return self._embed_tp if self._tp is not None \
            else self.model.embed._data

    def _lnf(self):
        if self._tp is not None:
            return self._lnf_tp
        return (self.model.lnf_scale._data, self.model.lnf_bias._data)

    def _get_decode_k(self, k: int, sample_cfg=None,
                      adaptered: bool = False):
        """One compiled program per (chunk size, greedy-vs-sample,
        top_k, adaptered); temperature/top_p flow in as traced
        scalars so per-request values never recompile. ``adaptered``
        adds the multi-LoRA delta operands (slot map + weight banks)
        as TRACED arrays: adapter membership and hot load/unload
        never retrace — the compiled-program count is independent of
        the adapter set (at most 2 programs per chunk size)."""
        key = (k, sample_cfg, adaptered)
        if key not in self._decode_k_jit:
            import functools

            self._decode_k_jit[key] = _roofline.AotProgram(
                self._decode_rung(k, adaptered),
                jax.jit(functools.partial(self._decode_k_fn, k=k,
                                          sample_cfg=sample_cfg),
                        donate_argnums=(7, 8)))
        return self._decode_k_jit[key]

    def _count_a8w8(self, steps: int):
        """Python-side ``quant.*`` accounting for executed A8W8 work
        (inside the traced programs the quant ops run once per compile,
        so the dispatch layer counts per EXECUTED step: 4 matmuls per
        layer per step, each preceded by one dynamic act-quant)."""
        if self._a8w8:
            n = 4 * self.model.stack.num_layers * steps
            _stats.inc("quant.act_quant_calls", n)
            _stats.inc("quant.a8w8_matmuls", n)

    # ---------- pure programs ----------

    def _logits(self, h, head_t, lnf_s, lnf_b):
        """LM head: final LN + pre-transposed [d, vocab] matmul with
        fp32 accumulation (argmax/sampling happen on fp32 logits)."""
        hl = FusedMultiTransformer._ln(
            h, lnf_s, lnf_b, self.model.stack.epsilon) \
            .astype(head_t.dtype)
        return jax.lax.dot_general(
            hl, head_t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _prefill_fn(self, weights, embed, head_t, lnf_s, lnf_b, ids,
                    seq_lens, cache_k, cache_v, tables):
        """Prompt pass over a right-padded batch: ``seq_lens[b]`` are the
        real prompt lengths (the reference's per-request seq_lens input,
        block_multi_head_attention_kernel.cu). Logits are gathered at
        each sequence's own last real position; pad-position KV is
        causal-dead and later overwritten by decode writes."""
        st = self.model.stack
        x = embed[ids].astype(self._cdtype)
        h, cache = st.prefill_raw(
            weights, x, PagedKV(cache_k, cache_v), tables,
            self._cos, self._sin, a8w8=self._a8w8, tp=self._tp)
        hl = h[jnp.arange(h.shape[0]), seq_lens - 1]
        logits = self._logits(hl, head_t, lnf_s, lnf_b)
        return logits, cache.k, cache.v

    @staticmethod
    def _argmax(logits):
        """Greedy pick as three lane-friendly passes (max, equality,
        min-index). XLA lowers ``jnp.argmax``'s variadic reduce poorly
        on TPU — measured 1.4ms/step over [32, 51200] f32 (50x the
        bandwidth roofline) vs ~0.1ms for this form (decode ablation
        r5, engine_noargmax knockout)."""
        m = jnp.max(logits, axis=-1, keepdims=True)
        idx = jnp.arange(logits.shape[-1], dtype=jnp.int32)
        cand = jnp.where(logits == m, idx[None, :],
                         jnp.int32(logits.shape[-1]))
        picked = jnp.min(cand, axis=-1).astype(jnp.int32)
        # all-NaN row: NaN != NaN leaves no candidate — return 0 like
        # jnp.argmax rather than an out-of-range id the embedding would
        # silently clamp
        return jnp.where(picked >= logits.shape[-1], 0, picked)

    @staticmethod
    def _pick_token(logits, key, sample_cfg):
        """Greedy argmax, or temperature/top-k/top-p sampling (the
        reference's top_p_sampling serving op, ops.yaml).

        sample_cfg is (temperature, top_k, top_p) with temperature and
        top_p as TRACED scalars — per-request values don't recompile the
        decode program; only top_k (a shape-determining slice) and the
        sampling on/off switch are static."""
        if sample_cfg is None:
            return GenerationEngine._argmax(logits)
        temperature, top_k, top_p = sample_cfg
        logits = logits / jnp.maximum(jnp.asarray(temperature,
                                                  logits.dtype), 1e-6)
        neg = jnp.asarray(-1e30, logits.dtype)
        if top_k and top_k > 0 and top_k < logits.shape[-1]:
            kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
            logits = jnp.where(logits < kth, neg, logits)
        # top_p traced: the mask arithmetic below is a no-op at
        # top_p >= 1.0, so one compiled program serves every value
        sorted_l = jnp.flip(jnp.sort(logits, axis=-1), -1)
        probs = jax.nn.softmax(sorted_l, -1)
        cum = jnp.cumsum(probs, -1)
        keep_sorted = (cum - probs) < jnp.asarray(top_p, probs.dtype)
        thresh = jnp.min(jnp.where(keep_sorted, sorted_l, jnp.inf),
                         -1, keepdims=True)
        logits = jnp.where(logits >= thresh, logits, neg)
        return jax.random.categorical(key, logits, axis=-1) \
            .astype(jnp.int32)

    def _decode_k_fn(self, weights, embed, head_t, lnf_s, lnf_b, tok,
                     seq_lens, cache_k, cache_v, tables, key=None,
                     sample_params=None, adapter_slots=None,
                     adapter_banks=None, *, k, sample_cfg=None):
        """K decode steps as ONE XLA program: the picked token feeds back
        into the next step inside lax.scan, so the host syncs once per
        chunk instead of once per token (the per-token dispatch
        round-trip is host time the chip spends idle). Greedy by default; sample_cfg=(static top_k,) +
        sample_params=(temperature, top_p) traced arrays switch to
        ancestral sampling with a per-step folded key."""
        st = self.model.stack
        if key is None:
            key = jax.random.PRNGKey(0)
        cfg = None
        if sample_cfg is not None:
            (top_k,) = sample_cfg
            temperature, top_p = sample_params
            cfg = (temperature, top_k, top_p)
        adapters = None
        if adapter_banks is not None:
            # multi-LoRA delta operands (ISSUE 18): the per-row bank
            # slot map plus the [L, S, ...] A/B banks, all traced —
            # the stack sorts rows by slot and issues ONE ragged
            # grouped delta launch per target projection per step
            adapters = dict(adapter_banks)
            adapters["slots"] = adapter_slots

        def step(carry, i):
            tok, lens, ck, cv = carry
            x = embed[tok].astype(self._cdtype)
            h, cache = st.decode_raw(
                weights, x, PagedKV(ck, cv), tables, lens,
                self._cos, self._sin, a8w8=self._a8w8, tp=self._tp,
                adapters=adapters)
            logits = self._logits(h, head_t, lnf_s, lnf_b)
            nxt = self._pick_token(logits, jax.random.fold_in(key, i),
                                   cfg)
            return (nxt, lens + 1, cache.k, cache.v), nxt

        (tok, seq_lens, ck, cv), toks = jax.lax.scan(
            step, (tok, seq_lens, cache_k, cache_v), jnp.arange(k))
        return jnp.swapaxes(toks, 0, 1), ck, cv  # [b, k]

    # ---------- serving API ----------

    @staticmethod
    def _pad_prompts(input_ids, seq_lens=None):
        """Normalize prompts to (padded [b, s] int array, lens [b]).
        Accepts a rectangular array (all rows real unless seq_lens
        given) or a ragged list of 1-D sequences (right-padded here)."""
        if isinstance(input_ids, Tensor):
            input_ids = np.asarray(input_ids._data)
        if isinstance(input_ids, (list, tuple)) and not np.isscalar(
                input_ids[0]):
            rows = [np.asarray(r).reshape(-1) for r in input_ids]
            lens = np.array([len(r) for r in rows], np.int32)
            s = int(lens.max())
            ids = np.zeros((len(rows), s), rows[0].dtype)
            for i, r in enumerate(rows):
                ids[i, : len(r)] = r
            return ids, lens
        ids = np.asarray(input_ids)
        if seq_lens is None:
            lens = np.full((ids.shape[0],), ids.shape[1], np.int32)
        else:
            lens = np.asarray(seq_lens, np.int32)
        return ids, lens

    def _grow_tables(self, seq_ids, lens, extra, pages_per_seq):
        """On-demand paging: extend each sequence's pages to cover
        ``lens + extra`` tokens; returns the (constant-shape) table."""
        for i, sid in enumerate(seq_ids):
            need = min(self._mgr.pages_needed(int(lens[i]) + extra),
                       pages_per_seq)
            have = len(self._mgr._owned.get(sid, ()))
            if need > have:
                self._mgr.grow(sid, need - have)
        return self._mgr.block_tables(seq_ids, pages_per_seq)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None, seq_lens=None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0):
        """Greedy decode with per-sequence prompt lengths.

        input_ids: [b, s] array (optionally with ``seq_lens`` marking
        real lengths) or a ragged list of 1-D prompts. Returns
        np.ndarray [b, max(s_i) + max_new_tokens]; row i holds its
        prompt then its generated tokens at columns
        lens[i]..lens[i]+max_new_tokens-1 (tail beyond that is pad/EOS)."""
        ids, lens = self._pad_prompts(input_ids, seq_lens)
        b, s = ids.shape
        if max_new_tokens <= 0:
            return ids.copy()
        st = self.model.stack
        if int(lens.max()) + max_new_tokens > self.max_length:
            raise ValueError(
                f"prompt ({int(lens.max())}) + max_new_tokens "
                f"({max_new_tokens}) exceeds engine max_length "
                f"({self.max_length}); raise max_length (positions past "
                "the page table would silently clamp)")
        # block-table WIDTH always covers max_length (constant shapes →
        # no recompiles), but pages are allocated on demand as sequences
        # grow — short generations leave the pool free for others
        pages_per_seq = -(-self.max_length // self.page_size)
        # +1 for the reserved scratch page 0, whether the pool size is
        # defaulted or caller-specified (a caller's num_pages means
        # usable capacity); rounded up so the stream-attention kernel
        # gets whole chunks (see _round_pool_pages)
        requested = (self._num_pages or b * pages_per_seq) + 1
        self._mgr = BlockKVCacheManager(
            st.num_layers, st.num_kv_heads, st.head_dim, self.page_size,
            num_pages=_round_pool_pages(requested, self.page_size),
            dtype=self._kv_dtype, reserve_scratch=True,
            mp_degree=self._tp.mp if self._tp else 1,
            mesh=self._tp.mesh if self._tp else None)
        _stats.set_gauge("inference.pool_pages_requested", requested)
        _stats.set_gauge("inference.pool_pages", self._mgr.num_pages)
        for i in range(b):
            self._mgr.allocate(i, int(lens[i]))
        tables = self._mgr.block_tables(range(b), pages_per_seq)
        cache = self._mgr.fresh_cache()

        weights = self._weights()
        embed = self._embed()
        lnf_s, lnf_b = self._lnf()

        _stats.inc("inference.prefills")
        self._count_a8w8(1)
        logits, ck, cv = self._prefill(
            weights, embed, self._head_t, lnf_s, lnf_b, jnp.asarray(ids),
            jnp.asarray(lens), cache.k, cache.v, tables)

        from ..core.generator import next_rng_key

        # static part: (top_k,) — temperature/top_p stay traced; greedy
        # decoding must not consume the global RNG stream at all
        static_cfg = (int(top_k),) if do_sample else None
        params = (jnp.asarray(float(temperature), jnp.float32),
                  jnp.asarray(float(top_p), jnp.float32)) \
            if do_sample else None
        pick_cfg = (params[0], int(top_k), params[1]) if do_sample \
            else None

        width = s + max_new_tokens
        out = np.zeros((b, width), ids.dtype)
        out[:, :s] = ids
        finished = np.zeros((b,), bool)

        # first generated token: prefill logits at each row's own last
        # real position
        tok_np = np.asarray(self._pick_token(
            logits, next_rng_key() if do_sample else None,
            pick_cfg)).astype(ids.dtype)
        if eos_token_id is not None:
            finished |= tok_np == eos_token_id
        out[np.arange(b), lens] = tok_np
        emitted = 1

        # remaining tokens in scan-chunks: one device program + ONE host
        # sync per chunk instead of per token
        while emitted < max_new_tokens and not (
                eos_token_id is not None and finished.all()):
            k = min(self.decode_chunk, max_new_tokens - emitted)
            # feed each row's last generated token at its own position
            cur = lens + emitted - 1         # per-seq position just fed
            tables = self._grow_tables(range(b), lens + emitted, k,
                                       pages_per_seq)
            _stats.inc("inference.decode_steps", k)
            self._count_a8w8(k)
            _stats.set_gauge("inference.kv_pages_in_use",
                             self._mgr.num_pages - self._mgr.free_pages)
            if self._tp is not None:
                # re-stamped per chunk: benches reset the registry
                # after warmup, and the TP degree must survive into
                # the measured telemetry block
                _stats.set_gauge("dist.mp_degree", self._tp.mp)
            import time as _time

            t0 = _time.perf_counter()
            toks, ck, cv = self._get_decode_k(k, static_cfg)(
                weights, embed, self._head_t, lnf_s, lnf_b,
                jnp.asarray(out[np.arange(b), cur].astype(np.int32)),
                jnp.asarray(cur, dtype=jnp.int32), ck, cv, tables,
                next_rng_key() if do_sample else None, params)
            toks_np = np.asarray(toks)
            # honest wall time: the np.asarray fetch synced the chunk,
            # so this roofline reflects executed work, not dispatch
            _roofline.analyze(self._decode_rung(k),
                              _time.perf_counter() - t0)
            for j in range(k):
                col = toks_np[:, j].astype(ids.dtype)
                if eos_token_id is not None:
                    col = np.where(finished, eos_token_id, col)
                    finished |= col == eos_token_id
                out[np.arange(b), lens + emitted] = col
                emitted += 1
        if eos_token_id is not None:
            for i in range(b):
                if finished[i]:
                    e = int(lens[i]) + emitted
                    out[i, e:] = eos_token_id
        for i in range(b):
            self._mgr.free(i)
        return out


class GenRequest:
    """One serving request (continuous batching unit)."""

    # id allocation must be thread-safe: the serving frontend
    # (paddle_tpu/serving) submits from arbitrary threads. next() on a
    # shared itertools.count is atomic under CPython (single bytecode
    # dispatch into C) — no lock, no duplicate ids.
    _next_id = itertools.count()

    def __init__(self, prompt, max_new_tokens=32, eos_token_id=None):
        self.id = next(GenRequest._next_id)
        self.prompt = np.asarray(prompt).reshape(-1).astype(np.int32)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.generated: list = []
        self.done = False
        # times the admission loop passed this request over for a later
        # one that fit (skip-ahead head-of-line fix; bounded by the
        # engine's starvation_bound)
        self._admit_skips = 0

    @property
    def output(self):
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])


class ContinuousBatchingEngine:
    """Continuous-batching serving loop over a FusedCausalLM.

    TPU-native counterpart of the reference's serving frontend around
    block_multi_head_attention (reference:
    paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu —
    per-request seq_lens + block tables): a fixed pool of ``max_batch``
    decode slots shares one paged KV pool; finished sequences free their
    pages and waiting requests are admitted mid-stream (their prompt is
    prefilled into the shared cache), so decode shapes stay constant and
    nothing recompiles as traffic churns.

    ``speculative=`` (ISSUE 12, inference/speculative.py): replace the
    decode chunk with draft+verify rounds — k drafted tokens verified
    in ONE streamed pass, amortizing the per-token weight stream by
    the accept length, with greedy parity guaranteed whatever the
    drafter proposes.

    Usage::

        eng = ContinuousBatchingEngine(model, max_batch=4)
        eng.submit([1, 2, 3], max_new_tokens=16)
        finished = eng.run()          # or step() repeatedly
    """

    def __init__(self, model: FusedCausalLM, max_batch: int = 4,
                 page_size: int = 16, max_length: int = 1024,
                 num_pages: Optional[int] = None,
                 decode_chunk: Optional[int] = None,
                 prompt_bucket: int = 16, kv_dtype=None,
                 quant: Optional[str] = None, admit_window: int = 8,
                 starvation_bound: int = 16, mesh=None,
                 mp_degree: Optional[int] = None,
                 ep_degree: Optional[int] = None, speculative=None,
                 spec_k: Optional[int] = None):
        self.model = model
        self.max_batch = int(max_batch)
        self.max_length = int(max_length)
        self.page_size = int(page_size)
        self.decode_chunk = _resolve_decode_chunk(decode_chunk)
        self.prompt_bucket = max(int(prompt_bucket), 1)
        # admission skip-ahead: when the queue head's pages don't fit,
        # up to admit_window later requests are tried instead of
        # head-of-line blocking; a head skipped starvation_bound times
        # pins the queue until it fits (bounded unfairness)
        self.admit_window = max(int(admit_window), 1)
        self.starvation_bound = max(int(starvation_bound), 1)
        gen_cls = getattr(model, "_gen_cls", GenerationEngine)
        self._gen = gen_cls.__new__(gen_cls)  # share
        self._gen.model = model
        self._gen.max_length = self.max_length
        self._gen.page_size = self.page_size
        self._gen.decode_chunk = self.decode_chunk
        self._gen._init_serving_state(kv_dtype, quant, mesh=mesh,
                                      mp_degree=mp_degree,
                                      ep_degree=ep_degree)
        st = model.stack
        # the stack's description: a cache group a layer kind — pages
        # for the attention layers only, slot-indexed state for the
        # recurrent ones (none in the one-kind pattern)
        pattern = st.pattern
        recurrent = pattern.recurrent
        att = pattern.attention
        if pattern.paged_kind is None:
            raise NotImplementedError(
                "a pattern without attention or latent_attention layers "
                "has no paged pool: the engines page every sequence")
        # a pattern-built model's programs take the recurrent state (or
        # None) after the pool and return pick counts beside their result
        self._pattern_built = getattr(gen_cls, "pattern_built", False)
        self._latent = pattern.paged_kind == LATENT
        if self._latent and (kv_dtype == "int8" or kv_dtype == jnp.int8):
            _refuse_latent("the int8 cache-KV pool")
        self._pages_per_seq = -(-self.max_length // self.page_size)
        requested = (num_pages or self.max_batch * self._pages_per_seq) + 1
        tp = self._gen._tp
        heads, width = (1, pattern.latent.row_width) if self._latent \
            else (att.num_kv_heads, att.head_dim)
        self._mgr = BlockKVCacheManager(
            pattern.n_paged, heads, width, self.page_size,
            num_pages=_round_pool_pages(requested, self.page_size),
            dtype=self._gen._kv_dtype, reserve_scratch=True,
            mp_degree=tp.mp if tp else 1,
            mesh=tp.mesh if tp else None,
            recurrent=recurrent, slots=self.max_batch,
            latent=self._latent)
        _stats.set_gauge("serving.pool_pages_requested", requested)
        _stats.set_gauge("serving.pool_pages", self._mgr.num_pages)
        cache = self._mgr.fresh_cache()
        # the pool's two operands: K and V sides, or the one latent pool
        # and None
        self._ck, self._cv = (cache.rows, None) if self._latent \
            else (cache.k, cache.v)
        # None on a model without recurrent layers
        self._rs = self._mgr.fresh_recurrent_state(self._gen._cdtype)
        if not self._pattern_built:
            self._cos, self._sin = rope_table(
                st.max_position, st.head_dim, st.rope_theta)
            self._gen._cos, self._gen._sin = self._cos, self._sin
        elif recurrent is not None:
            _stats.set_gauge("serving.recurrent.state_bytes",
                             recurrent.bytes_per_slot() * self.max_batch)
        self._gen._mgr = self._mgr

        self.waiting: list = []
        self.finished: list = []
        # serving flight-recorder hook (serving/journal.py): the
        # serving frontend installs its ring journal here so engine-
        # level finish events land on the same per-request timeline;
        # None (the base engine) keeps every hook a no-op
        self._journal = None
        # fault-injection registry (serving/faults.py): the serving
        # frontend installs its injector here so the ``decode.step``
        # site fires once per decode chunk; None = one attribute test
        self._faults = None
        # usage ledger hook (serving/accounting.py): the serving
        # frontend installs its UsageLedger here so engine-level
        # token accounting (wasted chunk tails, spec accepts) charges
        # the owning request; None = one attribute test
        self._usage = None
        # the clock that bounds a step's plan / run / emit phases and
        # the stamps of the last step that reached its program call,
        # (run start, run end), None where none did; the serving
        # frontend installs its clock seam and reads the stamps into
        # the ``serve.step.{plan,run,emit}_ms`` histograms
        self._now = time.monotonic
        self._run_ts = None
        # slot state
        self._slots: list = [None] * self.max_batch   # GenRequest or None
        self._lens = np.zeros((self.max_batch,), np.int64)
        self._last_tok = np.zeros((self.max_batch,), np.int64)
        # speculative decoding (inference/speculative.py): when set,
        # step() runs one draft+verify round in place of the decode
        # chunk — the weight stack streams once per ACCEPTED WINDOW
        # instead of once per token. ``speculative`` accepts True
        # (FLAGS_spec_drafter), "self" (Medusa-style self-drafting
        # heads), a Drafter instance, or a small FusedCausalLM draft
        # model; ``spec_k`` defaults to FLAGS_spec_k.
        self._spec = None
        if speculative and recurrent is not None:
            _refuse_recurrent("speculative verify (rejected drafts roll "
                              "the page table back)")
        if speculative and self._latent:
            _refuse_latent("speculative verify")
        if speculative:
            from .speculative import build_speculative_decoder

            self._spec = build_speculative_decoder(
                self, speculative, spec_k)

    # ---------------- public API ----------------

    def submit(self, prompt, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None) -> int:
        req = GenRequest(prompt, max_new_tokens, eos_token_id)
        if len(req.prompt) + req.max_new_tokens > self.max_length:
            raise ValueError("request exceeds engine max_length")
        self.waiting.append(req)
        return req.id

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slots)

    def step(self):
        """Admit waiting requests into free slots, then run ONE decode
        chunk — or, with ``speculative=`` set, one draft+verify round —
        for the active batch. Returns requests finished this step."""
        self._admit()
        if self.num_active == 0:
            return []
        if self._spec is not None:
            return self._spec_step()
        k = self.decode_chunk
        with RecordEvent("serve.plan"):
            plan = self._plan_decode(k)
        if plan is None:
            return []
        active, program, lead, tail = plan
        t_run0 = self._now()
        with RecordEvent("serve.run", program=program.name):
            t0 = time.perf_counter()
            toks_np = self._fetch(self._run_program(program, lead, tail))
        self._run_ts = (t_run0, self._now())
        # synced by the fetch above — an honest per-chunk roofline
        _roofline.analyze(program.name, time.perf_counter() - t0)
        with RecordEvent("serve.emit"):
            return self._emit_decode(toks_np, active, k)

    def _run_program(self, program, lead, tail):
        """``program(*lead, <cache>, *tail)``: the cache (the pool's two
        sides and, on a model with recurrent layers, the slot-indexed
        state) is donated and rebound; returns the program's first
        result."""
        if not self._pattern_built:
            out, self._ck, self._cv = program(
                *lead, self._ck, self._cv, *tail)
        else:
            out, self._ck, self._cv, self._rs = program(
                *lead, self._ck, self._cv, self._rs, *tail)
        return out

    def _fetch(self, out):
        """A program's first result on the host. A pattern-built model
        returns its expert layers' pick counts beside it (a prefill
        chunk also its grouped GEMMs' work units): both come in the one
        fetch and the counts go to the ``serving.moe.*`` counters."""
        if not self._pattern_built:
            return np.asarray(out)
        first, counts = jax.device_get(out)
        for name, n in zip(("picks", "picks_here", "experts_hit",
                            "experts_held", "units_walked", "units_live"),
                           counts):
            _stats.inc("serving.moe." + name, int(n))
        return first

    def _plan_decode(self, k: int):
        """The host's work before a decode chunk's program call: page
        growth (which may evict or preempt), block tables, operands.
        Returns (active slots, the program, operands before the pool,
        operands after it), or None where no slot is left to decode."""
        active = [i for i, r in enumerate(self._slots) if r is not None]
        fi = self._faults
        if fi is not None and active:
            # the decode.step fault site fires BEFORE the grow loop so
            # scheduled pool squeezes exhaust the free list the grows
            # are about to hit — the REAL recovery paths (eviction,
            # preemption-by-recompute) engage on genuine pool state
            fi.fire("decode.step")
        # pages grow on demand, clamped to what the request can still
        # emit — a near-max_length prompt must not over-allocate past
        # the fixed block-table width
        for i in active:
            req = self._slots[i]
            if req is None:
                continue  # preempted by an earlier slot's grow
            remaining = req.max_new_tokens - len(req.generated)
            need = self._mgr.pages_needed(
                int(self._lens[i]) + min(k, max(remaining, 0)))
            need = min(need, self._pages_per_seq)
            have = len(self._mgr._owned.get(("slot", i), ()))
            if need > have and \
                    not self._grow_decode_slot(i, need - have):
                continue  # slot preempted (serving override)
        active = [i for i in active if self._slots[i] is not None]
        if not active:
            return None
        tables = self._mgr.block_tables(
            [("slot", i) for i in range(self.max_batch)],
            self._pages_per_seq, allow_missing=True)
        _stats.inc("serving.decode_steps", k)
        self._gen._count_a8w8(k)
        _stats.set_gauge("serving.kv_pages_in_use",
                         self._mgr.num_pages - self._mgr.free_pages)
        _stats.set_gauge("serving.active_slots", len(active))
        if self._gen._tp is not None:
            # survives post-warmup stats.reset() in the benches
            _stats.set_gauge("dist.mp_degree", self._gen._tp.mp)

        cur = np.where([r is not None for r in self._slots],
                       self._lens - 1, 0).astype(np.int64)
        if not isinstance(self._ck, tuple):
            # what the decode kernel walks in this chunk, a step and
            # attention layer: the pages the tables name at the step's
            # lengths (every row advances a token a step), beside the
            # pages of the layer's region (the int8 pool's kernel still
            # walks its whole region and counts nothing)
            named = np.minimum(
                -(-(cur[:, None] + np.arange(k)[None, :])
                  // self.page_size), self._pages_per_seq)
            layers = self._mgr.num_layers
            _stats.inc("serving.kv.pages_walked",
                       int(named.sum()) * layers)
            _stats.inc("serving.kv.pages_region",
                       self._mgr.num_pages * layers * k)
            if self._latent:
                # latent rows the decode kernel reads in this chunk, a
                # step and layer: every cached token of every decoding
                # row, once for all heads
                rows = (cur[:, None] + np.arange(k)[None, :])[
                    [r is not None for r in self._slots]]
                _stats.inc("serving.mla.rows_read",
                           int(rows.sum()) * layers)
        lnf_s, lnf_b = self._gen._lnf()
        a_slots, a_banks = self._adapter_operands(active)
        adaptered = a_banks is not None
        extra = (None, None, a_slots, a_banks) if adaptered else ()
        if adaptered:
            # one ragged grouped delta launch per target projection
            # per executed decode step (4 projections x L layers x k)
            _stats.inc("lora.grouped_launches",
                       4 * self.model.stack.num_layers * k)
        lead = (self._gen._weights(), self._gen._embed(),
                self._gen._head_t, lnf_s, lnf_b,
                jnp.asarray(self._last_tok, jnp.int32),
                jnp.asarray(cur, jnp.int32))
        if self._pattern_built:
            # rows that decode; the others (idle slots, slots whose
            # prompt is still prefilling) keep pool and state
            extra = (jnp.asarray([r is not None for r in self._slots]),)
        return (active, self._gen._get_decode_k(k, adaptered=adaptered),
                lead, (tables, *extra))

    def _emit_decode(self, toks_np, active, k: int):
        """Fetched tokens -> requests: validation, ``on_token``
        callbacks, finish hooks, page release. Returns the requests
        that finished."""
        # overridable token filter: runs BEFORE any request mutates,
        # so a validation raise (serving corruption detection) leaves
        # every slot exactly as it was and a chunk re-run is clean
        toks_np = self._postprocess_tokens(toks_np, active)

        done_now = []
        for i in active:
            req = self._slots[i]
            cb = getattr(req, "on_token", None)
            consumed = 0
            for j in range(k):
                if req.done:
                    break
                t = int(toks_np[i, j])
                req.generated.append(t)
                consumed += 1
                if cb is not None:
                    cb(req, t)
                if (req.eos_token_id is not None
                        and t == req.eos_token_id) or \
                        len(req.generated) >= req.max_new_tokens:
                    req.done = True
            if req.done:
                # tokens the chunk decoded PAST req.done are executed-
                # but-discarded device work: the decode_chunk tuning
                # signal (big chunks amortize dispatch, small chunks
                # waste less tail work on eos/max_new finishes)
                _stats.inc("serving.wasted_decode_tokens", k - consumed)
                u = self._usage
                if u is not None and k > consumed:
                    # the tail belongs to the FINISHER — the request
                    # whose eos/max_new ended the chunk early
                    u.add_tokens(req, wasted=k - consumed)
                self._finish_hook(req, i)
                self._release(i)
                done_now.append(req)
            else:
                self._lens[i] += k
                self._last_tok[i] = int(toks_np[i, k - 1])
        self.finished.extend(done_now)
        return done_now

    def _spec_step(self):
        """One SPECULATIVE round in place of the decode chunk: the
        drafter proposes k tokens per active slot, ONE streamed verify
        pass (``prefill_chunk_raw`` over the paged pool) scores every
        window, and the fused accept-prefix emits the accepted drafts
        plus the bonus token — greedy-parity by construction, and a
        rejection costs only a page-table truncation
        (inference/speculative.py)."""
        k = self._spec.k
        active = [i for i, r in enumerate(self._slots) if r is not None]
        fi = self._faults
        if fi is not None and active:
            # same decode.step fault site as the chunk path, fired
            # BEFORE the grows so pool squeezes hit the real recovery
            fi.fire("decode.step")
        # per-slot window, clamped so the verify never writes past what
        # the request can still emit (which also bounds it to the page
        # table: cached + remaining <= max_length by the submit check)
        win = np.zeros((self.max_batch,), np.int64)
        for i in active:
            req = self._slots[i]
            if req is None:
                continue  # preempted by an earlier slot's grow
            remaining = req.max_new_tokens - len(req.generated)
            w = max(1, min(k + 1, remaining,
                           self.max_length - (int(self._lens[i]) - 1)))
            win[i] = w
            need = min(self._mgr.pages_needed(
                int(self._lens[i]) - 1 + w), self._pages_per_seq)
            have = len(self._mgr._owned.get(("slot", i), ()))
            if need > have and \
                    not self._grow_decode_slot(i, need - have):
                continue  # slot preempted (serving override)
        active = [i for i in active if self._slots[i] is not None]
        if not active:
            return []
        # the round drafts, verifies and emits in one piece: all of it
        # is booked as the step's run phase
        t_run0 = self._now()
        with RecordEvent("serve.run", program=self._spec._rung()):
            out = self._spec.run_round(self, active, win)
        self._run_ts = (t_run0, self._now())
        return out

    def run(self):
        """Drain: step until every submitted request finishes."""
        while self.waiting or self.num_active:
            self.step()
        return self.finished

    # ------------- slot migration (fleet drain, ISSUE 14) -------------

    @staticmethod
    def _pad_pow2(a: np.ndarray, axis: int = 0) -> np.ndarray:
        """Pad ``a`` along ``axis`` to the next power-of-two length
        (min 8) by repeating its last entry, so the KV gather/scatter
        row shapes BUCKET instead of recompiling per page count — a
        per-count XLA compile in the serving hot path wedges a
        replica's stepping thread long enough to trip the fleet
        health checker into hedging its queue away. Duplicate scatter
        indices carry the duplicated (identical) values, so the
        padded writes are no-ops; padded gather rows are sliced off
        by the caller."""
        n = a.shape[axis]
        b = max(8, 1 << max(0, (n - 1).bit_length()))
        if n == 0 or b == n:
            return a
        idx = [slice(None)] * a.ndim
        idx[axis] = slice(n - 1, n)
        pad = np.repeat(a[tuple(idx)], b - n, axis=axis)
        return np.concatenate([a, pad], axis=axis)

    def _needs_kv_pages(self, what: str):
        """Refuse ``what`` by type where K/V pages are not the whole of a
        sequence's cached state (recurrent layers) or the pool holds no K/V
        pages at all (a latent pool)."""
        if self._rs is not None:
            _refuse_recurrent(what)
        if self._latent:
            _refuse_latent(what)

    def can_migrate(self) -> bool:
        """Page-granular KV export/import is supported for plain
        (unsharded, non-int8) pools; int8 cache-KV carries scale
        planes and TP pools shard by kv-head — both fall back to the
        preemption-by-recompute path on a fleet drain."""
        return not isinstance(self._ck, tuple) \
            and self._mgr._mesh is None and self._rs is None \
            and not self._latent

    def export_slot(self, i: int) -> dict:
        """Export decode slot ``i``'s live state for page-granular
        migration to a peer engine: the request, its sequence
        position, and the slot's KV pages gathered out of the pool
        (one contiguous blob per K/V, layer-major — see
        ``BlockKVCacheManager.phys_rows``). Pages are NOT freed here;
        the caller releases the slot only after the import lands, so
        a failed migration leaves this engine untouched."""
        self._needs_kv_pages("slot export")
        if not self.can_migrate():
            raise NotImplementedError(
                "KV-page migration needs a plain pool (no int8 "
                "cache-KV, no TP kv-head sharding) — use the "
                "recompute resume path instead")
        req = self._slots[i]
        if req is None:
            raise KeyError(f"slot {i} is not decoding")
        pages = list(self._mgr._owned[("slot", i)])
        rows_np = self._mgr.phys_rows(pages)
        nr = len(rows_np)
        rows = jnp.asarray(self._pad_pow2(rows_np))
        return {"req": req, "len": int(self._lens[i]),
                "last_tok": int(self._last_tok[i]),
                "n_pages": len(pages),
                "k": np.asarray(gather_rows(self._ck, rows))[:nr],
                "v": np.asarray(gather_rows(self._cv, rows))[:nr]}

    def import_slot(self, i: int, blob: dict) -> bool:
        """Adopt an exported decode slot into free slot ``i``: allocate
        exactly ``n_pages`` fresh pages, scatter the K/V blob into
        them, and re-home the request mid-decode — its next token
        comes out byte-identical because the cached KV (and the
        replicated weights) are byte-identical. False when the slot is
        occupied or the pool can't cover the pages (the caller falls
        back to recompute)."""
        self._needs_kv_pages("slot import")
        if not self.can_migrate():
            raise NotImplementedError(
                "KV-page migration needs a plain pool (no int8 "
                "cache-KV, no TP kv-head sharding)")
        n = int(blob["n_pages"])
        if not self._slot_free(i) or n > self._mgr.free_pages \
                or n > self._pages_per_seq:
            return False

        pages = self._mgr.allocate(("slot", i), n * self.page_size)
        rows = jnp.asarray(self._pad_pow2(self._mgr.phys_rows(pages)))
        self._ck = restore_scatter_jit(
            self._ck, rows, jnp.asarray(self._pad_pow2(blob["k"])))
        self._cv = restore_scatter_jit(
            self._cv, rows, jnp.asarray(self._pad_pow2(blob["v"])))
        self._slots[i] = blob["req"]
        self._lens[i] = int(blob["len"])
        self._last_tok[i] = int(blob["last_tok"])
        return True

    # ------- async page streaming (decode-concurrent migration) -------
    #
    # Decode appends only: a page whose positions all sit below the
    # slot's current length never mutates again, so COMPLETE pages can
    # stream to the destination in batches with NO lock on the source
    # (reads snapshot the functional pool arrays) and only a short
    # per-batch critical section on the destination (the scatter swaps
    # its pool arrays). The join copies the mutable tail + metadata
    # under both step locks — byte-identical tokens preserved because
    # every streamed page is byte-identical by construction.

    def safe_page_count(self, i: int) -> int:
        """Pages of slot ``i`` that are complete (every position below
        the current length) and therefore immutable under further
        decode steps — the lock-free streamable prefix."""
        return min(int(self._lens[i]) // self.page_size,
                   len(self._mgr._owned.get(("slot", i), ())))

    def export_pages(self, i: int, lo: int, hi: int) -> dict:
        """Gather logical pages ``[lo, hi)`` of decoding slot ``i`` to
        host memory. Lock-free for complete pages: the pool arrays are
        functional (decode steps REPLACE them), so a snapshot reference
        carries byte-identical rows for any already-complete page."""
        self._needs_kv_pages("page streaming (export_pages)")
        if not self.can_migrate():
            raise NotImplementedError(
                "KV-page migration needs a plain pool (no int8 "
                "cache-KV, no TP kv-head sharding)")
        pages = list(self._mgr._owned[("slot", i)])[lo:hi]
        ck, cv = self._ck, self._cv
        rows_np = self._mgr.phys_rows(pages)
        nr = len(rows_np)
        rows = jnp.asarray(self._pad_pow2(rows_np))
        return {"lo": lo, "hi": hi,
                "k": np.asarray(gather_rows(ck, rows))[:nr],
                "v": np.asarray(gather_rows(cv, rows))[:nr]}

    def import_begin(self, n_pages: int):
        """Reserve ``n_pages`` for an in-flight migration WITHOUT
        claiming a decode slot (admission keeps running; the slot is
        picked at ``import_finish``). Returns an opaque ticket, or
        None when the pool can't cover the reservation. Call under
        this engine's step lock."""
        self._needs_kv_pages("page streaming (import_begin)")
        if not self.can_migrate():
            raise NotImplementedError(
                "KV-page migration needs a plain pool (no int8 "
                "cache-KV, no TP kv-head sharding)")
        if n_pages > self._mgr.free_pages or n_pages > self._pages_per_seq:
            return None
        self._mig_seq = getattr(self, "_mig_seq", 0) + 1
        key = ("migrate", self._mig_seq)
        self._mgr.allocate(key, n_pages * self.page_size)
        return {"key": key, "n_pages": n_pages}

    def import_pages(self, ticket, batch: dict):
        """Scatter one streamed page batch (an ``export_pages`` blob)
        into the ticket's reserved pages. Call under this engine's
        step lock — the scatter swaps the pool arrays and must not
        race a decode step's own swap."""

        pages = list(self._mgr._owned[ticket["key"]])
        rows = jnp.asarray(self._pad_pow2(self._mgr.phys_rows(
            pages[batch["lo"]:batch["hi"]])))
        self._ck = restore_scatter_jit(
            self._ck, rows, jnp.asarray(self._pad_pow2(batch["k"])))
        self._cv = restore_scatter_jit(
            self._cv, rows, jnp.asarray(self._pad_pow2(batch["v"])))

    def export_slot_tail(self, i: int, lo: int) -> dict:
        """The source's closing export for an async migration: slot
        metadata plus ONLY the pages from ``lo`` on (the mutable tail
        the background stream could not safely copy). Call under the
        source's step lock so ``len``/``last_tok`` and the tail bytes
        are one consistent snapshot."""
        req = self._slots[i]
        if req is None:
            raise KeyError(f"slot {i} is not decoding")
        n = len(self._mgr._owned[("slot", i)])
        tail = self.export_pages(i, lo, n) if lo < n else None
        return {"req": req, "len": int(self._lens[i]),
                "last_tok": int(self._last_tok[i]),
                "n_pages": n, "tail": tail}

    def import_finish(self, ticket, i: int, blob: dict) -> bool:
        """Join: adopt the reserved pages as free slot ``i`` and
        re-home the request with its final metadata (``blob`` from
        ``export_slot_tail`` — page range covers only the
        not-yet-streamed tail). The reservation grows to cover pages
        allocated on the source AFTER it was taken (decode kept
        running there). False when the slot was taken or the pool
        can't cover the growth — the caller aborts and falls back."""
        n = int(blob["n_pages"])
        if not self._slot_free(i):
            return False
        have = len(self._mgr._owned[ticket["key"]])
        if n > have and (n - have) > self._mgr.free_pages:
            return False
        if n > have:
            self._mgr.grow(ticket["key"], n - have)
        self._mgr.rekey(ticket["key"], ("slot", i))
        if blob.get("tail") is not None:
            self.import_pages({"key": ("slot", i)}, blob["tail"])
        self._slots[i] = blob["req"]
        self._lens[i] = int(blob["len"])
        self._last_tok[i] = int(blob["last_tok"])
        return True

    def import_abort(self, ticket):
        """Release an unfinished migration reservation."""
        self._mgr.free(ticket["key"])

    # -------- host-tier page spill/restore (tiered KV, ISSUE 20) --------
    #
    # Unlike slot migration, spill/restore moves IMMUTABLE pages only
    # (full prefix-cache pages, a preempted slot's complete pages), so
    # the int8 cache-KV mode is supported: a page's quantized rows spill
    # together with their f32 scale-plane columns and the pair restores
    # byte-identically — spilled traffic roughly halves vs bf16.

    def can_spill(self) -> bool:
        """Host-DRAM spill/restore supports plain AND int8 pools; only
        TP kv-head-sharded pools fall back (a one-shard blob could not
        restore into a differently-sharded peer pool)."""
        return self._mgr._mesh is None and self._rs is None \
            and not self._latent

    def _scale_cols(self, rows_np: np.ndarray) -> np.ndarray:
        """Scale-plane columns of the given pool rows: row r position t
        lives at plane column r * page_size + t (kv_cache.fresh_cache
        lane-major layout)."""
        ps = self.page_size
        return (rows_np[:, None] * ps
                + np.arange(ps, dtype=np.int64)[None, :]).reshape(-1)

    def export_kv_pages(self, pages) -> dict:
        """Gather arbitrary (immutable) pool pages to host memory —
        layer-major page-inner layout per ``phys_rows``, so the blob
        scatters back via ``import_kv_pages`` on any engine with the
        same geometry. int8 pools add the per-token scale columns."""
        self._needs_kv_pages("host-tier page spill")
        if not self.can_spill():
            raise NotImplementedError(
                "host-tier KV spill needs an unsharded pool — TP "
                "kv-head shards fall back to evict/recompute")
        rows_np = self._mgr.phys_rows(list(pages))
        nr = len(rows_np)
        rows_pad = self._pad_pow2(rows_np)
        rows = jnp.asarray(rows_pad)
        if isinstance(self._ck, tuple):
            nc = nr * self.page_size
            cols = jnp.asarray(self._scale_cols(rows_pad))
            return {"n_pages": len(pages), "int8": True,
                    "k": np.asarray(self._ck[0][rows])[:nr],
                    "v": np.asarray(self._cv[0][rows])[:nr],
                    "k_scale": np.asarray(self._ck[1][:, cols])[:, :nc],
                    "v_scale": np.asarray(self._cv[1][:, cols])[:, :nc]}
        return {"n_pages": len(pages), "int8": False,
                "k": np.asarray(gather_rows(self._ck, rows))[:nr],
                "v": np.asarray(gather_rows(self._cv, rows))[:nr]}

    def import_kv_pages(self, pages, blob: dict) -> None:
        """Scatter a spilled host blob into freshly allocated pool
        pages (the restore half — ``kv_cache.restore_scatter``, the
        donated ``serve.kv_restore`` program). Swaps the functional
        pool arrays; call from the step thread / under the step lock."""
        self._needs_kv_pages("host-tier page restore")

        rows_np = self._mgr.phys_rows(list(pages))
        nr = len(rows_np)
        rows_pad = self._pad_pow2(rows_np)
        rows = jnp.asarray(rows_pad)
        if blob.get("int8"):
            ps = self.page_size
            reps = len(rows_pad) - nr

            def _pad_sc(x):
                # the duplicated last row's scale columns, tiled to
                # match the padded cols (identical duplicate writes)
                x = np.asarray(x)
                if reps:
                    x = np.concatenate(
                        [x, np.tile(x[:, -ps:], (1, reps))], axis=1)
                return x

            cols = jnp.asarray(self._scale_cols(rows_pad))
            ck, cks = self._ck
            cv, cvs = self._cv
            self._ck = (restore_scatter_jit(
                            ck, rows,
                            jnp.asarray(self._pad_pow2(blob["k"]))),
                        cks.at[:, cols].set(jnp.asarray(
                            _pad_sc(blob["k_scale"]), cks.dtype)))
            self._cv = (restore_scatter_jit(
                            cv, rows,
                            jnp.asarray(self._pad_pow2(blob["v"]))),
                        cvs.at[:, cols].set(jnp.asarray(
                            _pad_sc(blob["v_scale"]), cvs.dtype)))
        else:
            self._ck = restore_scatter_jit(
                self._ck, rows, jnp.asarray(self._pad_pow2(blob["k"])))
            self._cv = restore_scatter_jit(
                self._cv, rows, jnp.asarray(self._pad_pow2(blob["v"])))

    # ---------------- internals ----------------

    def _release(self, i: int):
        self._mgr.free(("slot", i))
        self._mgr.recurrent_free(i)
        self._slots[i] = None
        self._lens[i] = 0
        self._last_tok[i] = 0
        if self._spec is not None:
            # slot reuse: the next occupant's drafter state re-drafts
            # from its own recorded history (resume semantics)
            self._spec.reset_slot(i)

    def _postprocess_tokens(self, toks_np, active):
        """Hook over the decode chunk's fetched token matrix, called
        before the per-slot append loop. Base engine: identity. The
        serving frontend overrides it with fault-injection corruption
        + token-range validation (serving/scheduler.py)."""
        return toks_np

    def _adapter_operands(self, active):
        """Multi-LoRA decode operands hook: ``(slot_map, banks)``
        when any active slot decodes through a LoRA adapter, else
        ``(None, None)`` — the base engine has no adapter bank; the
        serving frontend overrides this against its AdapterBank
        (serving/scheduler.py)."""
        return None, None

    def _finish_hook(self, req, slot: int):
        """Called once per finished request, BEFORE its pages release.
        Base engine: journal a finish event when a flight recorder is
        installed. The serving frontend overrides this with SLO
        verdicts + lifecycle stamps (serving/scheduler.py)."""
        j = self._journal
        if j is not None:
            j.record("finish", req.id, slot,
                     {"n_tokens": len(req.generated)})

    def _grow_decode_slot(self, i: int, n_pages: int) -> bool:
        """Extend slot ``i``'s pages before a decode chunk; False means
        the slot was vacated instead of grown. The base engine's pool
        is sized for max_batch full-length sequences, so exhaustion
        here is a configuration error and raises; the serving frontend
        overrides this with prefix-cache eviction and, as a last
        resort, preemption-by-recompute."""
        self._mgr.grow(("slot", i), n_pages)
        return True

    def _slot_free(self, i: int) -> bool:
        """Is slot i available for admission? (The serving scheduler
        also parks chunk-prefilling requests on slots.)"""
        return self._slots[i] is None

    def _can_admit(self, req) -> bool:
        """Do the pool's free pages cover this request's prompt (+1
        decode token)? Overridden by the serving frontend to account
        for prefix-cache hits and to evict cold cached prefixes."""
        return self._mgr.pages_needed(len(req.prompt) + 1) \
            <= self._mgr.free_pages

    def _pick_waiting(self):
        """Next admissible waiting request, with BOUNDED SKIP-AHEAD:
        when the head's pages don't fit, up to ``admit_window`` later
        requests are tried (small requests flow past a parked big one
        instead of head-of-line blocking behind it). Each pass-over
        bumps the skipped requests' ``_admit_skips`` and the
        ``serving.admission_skips`` counter; once the head has been
        skipped ``starvation_bound`` times the window collapses to the
        head alone, so it admits next no matter what fits behind it."""
        if not self.waiting:
            return None
        head = self.waiting[0]
        window = 1 if head._admit_skips >= self.starvation_bound \
            else min(len(self.waiting), self.admit_window)
        for j in range(window):
            req = self.waiting[j]
            if self._can_admit(req):
                if j > 0:
                    for skipped in self.waiting[:j]:
                        skipped._admit_skips += 1
                    _stats.inc("serving.admission_skips", j)
                return self.waiting.pop(j)
        return None

    def _admit(self):
        """Move admissible waiting requests into free slots (skip-ahead
        selection via ``_pick_waiting``); prefill each prompt into the
        shared page pool (bucketed lengths bound recompiles)."""
        for i in range(self.max_batch):
            if not self.waiting or not self._slot_free(i):
                continue
            req = self._pick_waiting()
            if req is None:
                break  # nothing in the window fits — retry next step
            self._admit_into(req, i)

    def _admit_into(self, req, i: int):
        """Prefill ``req``'s whole prompt and start it decoding in slot
        ``i``. (The serving frontend overrides this with chunked
        prefill: the prompt fills in fixed-size chunks interleaved with
        decode steps instead of one monolithic program.)"""
        if self._pattern_built:
            raise NotImplementedError(
                "a pattern-built model (mamba, attention or "
                "latent_attention layers) prefills in chunks that carry "
                "its state and write its pool: serve it through "
                "paddle_tpu.serving.ServingEngine")
        self._slots[i] = req
        _stats.inc("serving.admitted")
        self._gen._count_a8w8(1)
        L = len(req.prompt)
        self._mgr.allocate(("slot", i), L)
        tables = self._mgr.block_tables([("slot", i)],
                                        self._pages_per_seq)
        # bucket the padded prompt length to bound compile count
        bs = self.prompt_bucket
        s_pad = -(-L // bs) * bs
        ids = np.zeros((1, s_pad), np.int32)
        ids[0, :L] = req.prompt
        lnf_s, lnf_b = self._gen._lnf()
        logits, self._ck, self._cv = self._gen._prefill(
            self._gen._weights(), self._gen._embed(),
            self._gen._head_t, lnf_s, lnf_b, jnp.asarray(ids),
            jnp.asarray([L], jnp.int32), self._ck, self._cv, tables)
        t = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
        req.generated.append(t)
        cb = getattr(req, "on_token", None)
        if cb is not None:
            cb(req, t)
        if (req.eos_token_id is not None and t == req.eos_token_id) \
                or req.max_new_tokens <= 1:
            req.done = True
            self._finish_hook(req, i)
            self._release(i)
            self.finished.append(req)
            return
        self._lens[i] = L + 1
        self._last_tok[i] = t
