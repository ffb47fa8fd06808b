"""Speculative decoding: self-drafting + draft-model speculation with a
batched paged verify pass.

Decode is weight-bandwidth-bound: the grouped stream kernels (PR 5)
move the ENTIRE weight stack through VMEM per generated token, and TP
(PR 10) only shrank the per-chip slice, not the per-token cost. This
module amortizes that bandwidth k-fold: a cheap DRAFTER proposes k
tokens per active slot, and ONE streamed verify pass — reusing
``FusedMultiTransformer.prefill_chunk_raw`` against the paged pool —
scores the whole (k+1)-token window, so the weight stack is read once
per accepted window instead of once per token (ROADMAP item 1; the
verify-tail fusion follows "LLM Inference Acceleration via Efficient
Operation Fusion", PAPERS.md).

Greedy-token parity is BY CONSTRUCTION: the verify pass computes the
target model's own greedy picks ``cand[j]`` at every window position;
draft token ``d_j`` is accepted iff it EQUALS ``cand[j-1]``, and the
round emits the accepted prefix plus the bonus token ``cand[a]`` — all
of which are exactly the tokens non-speculative greedy decode would
have produced, whatever the drafter proposed. A bad drafter costs
throughput, never output.

The fused verify tail (logits → accept-prefix → bonus selection) runs
INSIDE the compiled program — the host fetches one ``[b, k+1]`` token
matrix and one ``[b]`` accept length per round, never a per-token
round-trip — and a rejection costs a page-table truncation
(``BlockKVCacheManager.truncate``): rejected positions' KV stays as
masked-dead garbage that the next round's window overwrites, while the
over-grown tail pages return to the pool (refcount-aware — shared
prefix pages only drop a reference).

Drafters (one ``Drafter`` interface, engine-agnostic):

- :class:`DraftModelDrafter` — a small :class:`FusedCausalLM` draft
  model with its own TINY, NON-PAGED KV state (one contiguous
  max_length region per slot; rollback = a length counter, no page
  ops). Draft weights are never sharded — under TP they stay
  replicated while the verify pass runs shard_mapped.
- :class:`SelfDraftHeads` — Medusa-style self-drafting heads,
  training-free: head ``h`` drafts greedy top-1 from the TARGET
  model's last verified hidden state through a fixed seeded residual
  projection and the target's own lm head. Zero extra weights to
  stream; acceptance depends on workload regularity.
- :class:`ScheduledDrafter` — proposes from a per-request token
  script. The forced accept/reject schedules of the parity tests and
  the acceptance-ceiling bench rung (``bench.py --decode-spec``
  replays a recorded greedy stream → accept rate 1.0, isolating pure
  verify amortization).

Scheduler integration: ``ContinuousBatchingEngine(speculative=...)``
(and thus ``ServingEngine``) replaces the decode-chunk step with one
speculative round — speculation takes the decode slot of the
SLO-weighted interleave cycle and composes with chunked prefill,
preemption-by-recompute (a resumed request's drafter state resets and
re-drafts), deadlines and the progress watchdog (accepted tokens move
``len(req.generated)``, the watchdog's mark).

Telemetry: ``serving.spec_{drafted,accepted,rejected}_tokens`` +
``serving.spec_rounds`` counters, the ``serve.accept_len`` histogram,
``spec.{propose_ms,verify_ms}`` timing histograms and the ``spec.k``
gauge; each round journals a ``spec_verify[k,accepted]`` lifecycle
event (rendered as a span in the chrome trace and as the accept-rate
row in ``tools/serve_top.py``). The verify program reports under the
``serve.verify[k=*,mp=N]`` roofline rung and is registered as the
``serve.verify`` program site for the tpu_lint whole-program passes.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..incubate.nn.fused_transformer import (
    FusedMultiTransformer, PagedKV, rope_table)
from ..profiler import roofline as _roofline
from ..profiler import stats as _stats

__all__ = ["Drafter", "DraftModelDrafter", "SelfDraftHeads",
           "ScheduledDrafter", "SpeculativeDecoder",
           "build_speculative_decoder"]


class Drafter:
    """Draft-token source for speculative decoding.

    One instance serves every slot of one engine; ``bind`` is called
    once by the :class:`SpeculativeDecoder` before the first round.
    ``propose`` must be IDEMPOTENT for unchanged engine state: a
    crash-isolated retry re-runs the whole round, and re-proposing
    must rewrite the same drafter state with identical values (commit
    is the only place per-slot progress advances).
    """

    k = 0

    def bind(self, engine, k: int) -> None:
        self.k = int(k)

    def reset(self, slot: int) -> None:
        """Slot reuse/preemption: drop slot state; the next round
        re-drafts from the request's recorded history."""

    def propose(self, engine, active) -> np.ndarray:
        """[max_batch, k] int32 draft tokens continuing each active
        slot's stream past its ``_last_tok`` (rows of inactive slots
        are ignored)."""
        raise NotImplementedError

    def commit(self, slot: int, accepted: int) -> None:
        """A verify round accepted ``accepted`` of this slot's drafts
        (and emitted the bonus token): advance per-slot state."""

    def observe_hidden(self, hidden, active) -> None:
        """Target-model hidden state at each slot's accept boundary
        (``[max_batch, d]``), from the verify pass — the self-drafting
        heads' input. Called once per round for surviving slots."""


class DraftModelDrafter(Drafter):
    """A small FusedCausalLM draft model with its own tiny, NON-PAGED
    KV state.

    Each slot owns one contiguous ``max_length``-token KV region (a
    degenerate one-page-per-sequence layout: ``page_size ==
    max_length``), so rollback after a rejection is a per-slot length
    counter — no page-table surgery, no data movement. The drafter
    maintains the invariant ``_lens[slot] <= engine._lens[slot] - 1``
    (tokens of the request's history present in the draft cache); a
    lag (resume after preemption, the fully-accepted round's last
    draft) is closed by bucketed catch-up chunks through the draft
    stack's ``prefill_chunk_raw`` before the next propose.

    Draft weights never shard: under TP the propose/catch-up programs
    run plain (replicated) jit while the target's verify pass runs
    shard_mapped.
    """

    def __init__(self, model, prompt_bucket: int = 16):
        self.model = model
        self.prompt_bucket = max(int(prompt_bucket), 1)

    def bind(self, engine, k: int) -> None:
        from .kv_cache import BlockKVCacheManager

        self.k = int(k)
        st = self.model.stack
        if self.model.vocab_size != engine.model.vocab_size:
            raise ValueError(
                f"draft model vocab ({self.model.vocab_size}) != target "
                f"vocab ({engine.model.vocab_size})")
        if st.max_position < engine.max_length:
            raise ValueError(
                f"draft model max_position ({st.max_position}) < engine "
                f"max_length ({engine.max_length})")
        self._B = engine.max_batch
        self._max_len = int(engine.max_length)
        wd = st.qkv_weight._data.dtype
        self._cdtype = jnp.bfloat16 if wd == jnp.int8 else wd
        self._cos, self._sin = rope_table(st.max_position, st.head_dim,
                                          st.rope_theta)
        self._head_t = jnp.array(self.model.embed._data.T) \
            .astype(self._cdtype)
        # tiny non-paged KV: one max_length page per slot (+ scratch)
        self._mgr = BlockKVCacheManager(
            st.num_layers, st.num_kv_heads, st.head_dim,
            page_size=self._max_len, num_pages=self._B + 1,
            dtype=(jnp.bfloat16 if self._cdtype == jnp.int8
                   else self._cdtype),
            reserve_scratch=True)
        for i in range(self._B):
            self._mgr.allocate(i, 1)
        self._tables = self._mgr.block_tables(range(self._B), 1)
        cache = self._mgr.fresh_cache()
        self._ck, self._cv = cache.k, cache.v
        self._lens = np.zeros((self._B,), np.int64)
        self._propose_jit = None
        self._catchup_jit: dict = {}
        _stats.set_gauge(
            "spec.draft_params",
            sum(int(np.prod(p.shape))
                for p in self.model.parameters()))

    def reset(self, slot: int) -> None:
        self._lens[slot] = 0

    def commit(self, slot: int, accepted: int) -> None:
        # propose wrote k tokens ([last_tok, d_1..d_{k-1}]); they are
        # correct through the fed last_tok plus the accepted prefix
        self._lens[slot] += min(accepted + 1, self.k)

    # ---------- compiled draft programs ----------

    def _catchup_fn(self, weights, embed, ids, start, chunk_lens,
                    ck, cv, tables):
        st = self.model.stack
        x = embed[ids].astype(self._cdtype)
        _h, cache = st.prefill_chunk_raw(
            weights, x, PagedKV(ck, cv), tables, start, chunk_lens,
            self._cos, self._sin)
        return cache.k, cache.v

    def _get_catchup(self, c: int):
        if c not in self._catchup_jit:
            self._catchup_jit[c] = _roofline.AotProgram(
                f"spec.draft_catchup[c={c}]",
                jax.jit(self._catchup_fn, donate_argnums=(5, 6)))
        return self._catchup_jit[c]

    def _propose_fn(self, weights, embed, head_t, lnf_s, lnf_b, tok,
                    lens, ck, cv, tables, *, k):
        """k greedy draft steps as ONE scan program: the picked token
        feeds back inside the loop (the target engine's _decode_k_fn
        shape), writing the fed tokens' KV into the per-slot regions."""
        st = self.model.stack
        from .engine import GenerationEngine

        def step(carry, _):
            tok, lens, ck, cv = carry
            x = embed[tok].astype(self._cdtype)
            h, cache = st.decode_raw(
                weights, x, PagedKV(ck, cv), tables, lens,
                self._cos, self._sin)
            hl = FusedMultiTransformer._ln(
                h, lnf_s, lnf_b, st.epsilon).astype(head_t.dtype)
            logits = jax.lax.dot_general(
                hl, head_t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            nxt = GenerationEngine._argmax(logits)
            return (nxt, lens + 1, cache.k, cache.v), nxt

        (_t, _l, ck, cv), toks = jax.lax.scan(
            step, (tok, lens, ck, cv), None, length=k)
        return jnp.swapaxes(toks, 0, 1), ck, cv          # [B, k]

    def _get_propose(self):
        if self._propose_jit is None:
            import functools

            self._propose_jit = _roofline.AotProgram(
                f"spec.draft_propose[k={self.k}]",
                jax.jit(functools.partial(self._propose_fn, k=self.k),
                        donate_argnums=(7, 8)))
        return self._propose_jit

    # ---------- Drafter API ----------

    def _ensure(self, engine, i: int) -> None:
        """Close any history lag (admission, resume-after-preempt, the
        fully-accepted round's unfed last draft) with bucketed catch-up
        chunks. No-op when the slot is already synced — so a retried
        round re-enters idempotently."""
        need = int(engine._lens[i]) - 1
        have = int(self._lens[i])
        if have >= need:
            return
        req = engine._slots[i]
        hist = np.concatenate(
            [req.prompt, np.asarray(req.generated[:-1], np.int32)]) \
            if req.generated else np.asarray(req.prompt, np.int32)
        w = self.model.stack._stack()
        embed = self.model.embed._data
        bs = self.prompt_bucket
        while have < need:
            n = min(need - have, 4 * bs)
            c = -(-n // bs) * bs
            ids = np.zeros((1, c), np.int32)
            ids[0, :n] = hist[have: have + n]
            self._ck, self._cv = self._get_catchup(c)(
                w, embed, jnp.asarray(ids),
                jnp.asarray([have], jnp.int32),
                jnp.asarray([n], jnp.int32),
                self._ck, self._cv, self._tables[i: i + 1])
            have += n
        self._lens[i] = have

    def propose(self, engine, active) -> np.ndarray:
        for i in active:
            self._ensure(engine, i)
        tok = np.zeros((self._B,), np.int32)
        lens = np.zeros((self._B,), np.int32)
        for i in active:
            tok[i] = engine._last_tok[i]
            lens[i] = self._lens[i]
        toks, self._ck, self._cv = self._get_propose()(
            self.model.stack._stack(), self.model.embed._data,
            self._head_t, self.model.lnf_scale._data,
            self.model.lnf_bias._data, jnp.asarray(tok),
            jnp.asarray(lens), self._ck, self._cv, self._tables)
        return np.asarray(toks)


class SelfDraftHeads(Drafter):
    """Medusa-style self-drafting heads, training-free.

    Head ``h`` drafts position ``+h+1`` as the greedy top-1 of the
    TARGET model's lm head over a fixed seeded residual projection of
    the last verified hidden state (``hidden + hidden @ W_h``,
    ``W_h ~ scale * N(0, 1)`` — no training in-repo; near-zero scale
    degenerates every head to the model's own next-token belief, which
    accepts on locally repetitive streams). Costs no extra weight
    streaming — the heads ride the already-resident lm head — so even
    low acceptance rarely loses; acceptance never changes output.
    """

    def __init__(self, scale: float = 0.02, seed: int = 0):
        self.scale = float(scale)
        self.seed = int(seed)

    def bind(self, engine, k: int) -> None:
        self.k = int(k)
        self._engine = engine
        d = engine.model.stack.embed_dim
        self._w = jax.random.normal(
            jax.random.PRNGKey(self.seed), (self.k, d, d),
            jnp.float32) * self.scale
        self._hidden = np.zeros((engine.max_batch, d), np.float32)
        self._jit = None

    def reset(self, slot: int) -> None:
        self._hidden[slot] = 0.0

    def observe_hidden(self, hidden, active) -> None:
        h = np.asarray(hidden, np.float32)
        for i in active:
            self._hidden[i] = h[i]

    def _heads_fn(self, head_t, lnf_s, lnf_b, ws, hidden):
        g = self._engine._gen
        from .engine import GenerationEngine

        def one(w):
            hh = hidden + hidden @ w
            logits = g._logits(hh.astype(g._cdtype), head_t,
                               lnf_s, lnf_b)
            return GenerationEngine._argmax(logits)

        toks = jax.lax.map(one, ws)                      # [k, B]
        return jnp.swapaxes(toks, 0, 1)

    def propose(self, engine, active) -> np.ndarray:
        if self._jit is None:
            self._jit = _roofline.AotProgram(
                f"spec.heads_propose[k={self.k}]",
                jax.jit(self._heads_fn))
        lnf_s, lnf_b = engine._gen._lnf()
        toks = self._jit(engine._gen._head_t, lnf_s, lnf_b, self._w,
                         jnp.asarray(self._hidden))
        return np.asarray(toks)


class ScheduledDrafter(Drafter):
    """Drafts from a per-request token script: ``lookup(req)`` returns
    the request's full expected generated stream; each round proposes
    its next k tokens. The parity tests' forced accept/reject
    schedules and the bench's acceptance-ceiling oracle (replay a
    recorded greedy stream → accept rate 1.0) both use this."""

    def __init__(self, lookup):
        self._lookup = lookup

    def bind(self, engine, k: int) -> None:
        self.k = int(k)
        self._B = engine.max_batch

    def propose(self, engine, active) -> np.ndarray:
        out = np.zeros((self._B, self.k), np.int32)
        for i in active:
            req = engine._slots[i]
            fut = np.asarray(self._lookup(req),
                             np.int32)[len(req.generated):]
            n = min(len(fut), self.k)
            out[i, :n] = fut[:n]
        return out


class SpeculativeDecoder:
    """Per-engine speculative-round driver: drafter + the batched
    verify program + accept/rollback bookkeeping. Owned by
    ``ContinuousBatchingEngine`` (``self._spec``); ``run_round`` is the
    decode-slot payload of the scheduler's interleave cycle."""

    def __init__(self, engine, drafter: Drafter, k: int):
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        self.engine = engine
        self.drafter = drafter
        self.k = int(k)
        drafter.bind(engine, self.k)
        self._rid = [None] * engine.max_batch
        self._verify_jit = None
        _stats.set_gauge("spec.k", self.k)

    def _rung(self) -> str:
        tp = self.engine._gen._tp
        mp = f",mp={tp.mp}" if tp is not None else ""
        return f"serve.verify[k={self.k}{mp}]"

    def reset_slot(self, i: int) -> None:
        self._rid[i] = None
        self.drafter.reset(i)

    # ---------- the compiled verify program ----------

    def _verify_fn(self, weights, embed, head_t, lnf_s, lnf_b, ids,
                   start, chunk_lens, draft, ck, cv, tables, *, k):
        """ONE streamed pass scores the whole (k+1)-token window
        ``ids[b] = [last_tok, d_1..d_k]`` at positions ``start[b]..``
        against the paged pool (``prefill_chunk_raw`` — cached pages +
        the in-window causal triangle), then fuses the verify tail:
        greedy picks at every window position, the accept-prefix
        length, and the accept-boundary hidden state (the self-draft
        heads' input) — so the host consumes one token matrix per
        round, never a per-token sync. Rows with ``chunk_lens == 0``
        (idle slots) write scratch and are ignored."""
        g = self.engine._gen
        st = self.engine.model.stack
        from .engine import GenerationEngine

        x = embed[ids].astype(g._cdtype)
        h, cache = st.prefill_chunk_raw(
            weights, x, PagedKV(ck, cv), tables, start, chunk_lens,
            g._cos, g._sin, a8w8=g._a8w8, tp=g._tp)
        b, c, d = h.shape                                # c = k + 1
        logits = g._logits(h.reshape(b * c, d), head_t, lnf_s, lnf_b)
        cand = GenerationEngine._argmax(logits).reshape(b, c)
        # fused accept-prefix: draft j (window index j+1) is accepted
        # iff it equals the model's own greedy pick at index j AND its
        # window index is inside the (clamped) valid window
        valid = (jnp.arange(k, dtype=jnp.int32)[None, :] + 2) \
            <= chunk_lens[:, None]
        match = jnp.logical_and(draft == cand[:, :-1], valid)
        acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                      axis=1).astype(jnp.int32)
        h_acc = h[jnp.arange(b), acc]                    # [b, d]
        return cand, acc, h_acc, cache.k, cache.v

    def _get_verify(self):
        if self._verify_jit is None:
            import functools

            self._verify_jit = _roofline.AotProgram(
                self._rung(),
                jax.jit(functools.partial(self._verify_fn, k=self.k),
                        donate_argnums=(9, 10)))
        return self._verify_jit

    # ---------- one speculative round ----------

    def run_round(self, eng, active, win):
        """Draft + verify + consume for the active decode batch.

        ``win[i]`` is slot i's clamped window length (<= k+1). NO host
        state mutates before the fetched round validates
        (``_postprocess_tokens``), so a crash-isolated retry re-runs
        the round cleanly — propose/catch-up rewrite identical values
        at identical positions. Returns requests finished this round.
        """
        import time as _time

        g = eng._gen
        B, k = eng.max_batch, self.k
        mgr = eng._mgr
        # (re)bind drafter slots whose request changed — admission or
        # resume-after-preemption ("resume re-drafts")
        for i in active:
            req = eng._slots[i]
            if self._rid[i] != req.id:
                self.drafter.reset(i)
                self._rid[i] = req.id
        t0 = _time.perf_counter()
        draft_np = np.asarray(self.drafter.propose(eng, active),
                              np.int32)
        _stats.observe("spec.propose_ms",
                       (_time.perf_counter() - t0) * 1e3)
        ids = np.zeros((B, k + 1), np.int32)
        start = np.zeros((B,), np.int32)
        clens = np.zeros((B,), np.int32)
        for i in active:
            ids[i, 0] = eng._last_tok[i]
            ids[i, 1:] = draft_np[i]
            start[i] = int(eng._lens[i]) - 1
            clens[i] = int(win[i])
        tables = mgr.block_tables(
            [("slot", i) for i in range(B)], eng._pages_per_seq,
            allow_missing=True)
        # re-stamped per round: benches reset the registry after
        # warmup, and the window size must survive into telemetry
        _stats.set_gauge("spec.k", k)
        if g._tp is not None:
            _stats.set_gauge("dist.mp_degree", g._tp.mp)
        g._count_a8w8(1)
        lnf_s, lnf_b = g._lnf()
        t0 = _time.perf_counter()
        cand, acc, h_acc, eng._ck, eng._cv = self._get_verify()(
            g._weights(), g._embed(), g._head_t, lnf_s, lnf_b,
            jnp.asarray(ids), jnp.asarray(start), jnp.asarray(clens),
            jnp.asarray(draft_np), eng._ck, eng._cv, tables)
        cand_np, acc_np = np.asarray(cand), np.asarray(acc)
        # the fetch above synced the round — honest verify roofline
        dt = _time.perf_counter() - t0
        _roofline.analyze(self._rung(), dt)
        _stats.observe("spec.verify_ms", dt * 1e3)
        # validation BEFORE any request mutates (serving override:
        # corruption detection) — a raise leaves the round retryable
        cand_np = eng._postprocess_tokens(cand_np, active)

        _stats.inc("serving.spec_rounds")
        jr = eng._journal
        u = eng._usage
        done_now = []
        alive = []
        for i in active:
            req = eng._slots[i]
            a = int(acc_np[i])
            _stats.inc("serving.spec_drafted_tokens", k)
            _stats.inc("serving.spec_accepted_tokens", a)
            _stats.inc("serving.spec_rejected_tokens", k - a)
            _stats.observe("serve.accept_len", a)
            if jr is not None:
                jr.record("spec_verify", req.id, i,
                          {"k": k, "accepted": a,
                           "dur_ms": round(dt * 1e3, 3)})
            cb = getattr(req, "on_token", None)
            consumed = 0
            for j in range(a + 1):
                if req.done:
                    break
                t = int(cand_np[i, j])
                req.generated.append(t)
                consumed += 1
                if cb is not None:
                    cb(req, t)
                if (req.eos_token_id is not None
                        and t == req.eos_token_id) or \
                        len(req.generated) >= req.max_new_tokens:
                    req.done = True
            # window tokens decoded past req.done are executed-but-
            # discarded device work, same meaning as the decode-chunk
            # counter (here bounded by the accept length)
            _stats.inc("serving.wasted_decode_tokens",
                       a + 1 - consumed)
            if u is not None:
                u.add_tokens(req, spec_accepted=a,
                             wasted=a + 1 - consumed)
            if req.done:
                eng._finish_hook(req, i)
                eng._release(i)          # also resets the drafter slot
                done_now.append(req)
            else:
                eng._lens[i] += consumed          # consumed == a + 1
                eng._last_tok[i] = int(cand_np[i, consumed - 1])
                # rejection rollback = page-table truncation: pages
                # grown for the rejected window tail return to the
                # pool (refcount-aware — shared prefix pages only
                # drop a reference, never free under a live sharer)
                mgr.truncate(("slot", i), int(eng._lens[i]) - 1)
                if u is not None:
                    u.set_pages(req, len(
                        mgr._owned.get(("slot", i), ())))
                self.drafter.commit(i, a)
                alive.append(i)
        if alive:
            self.drafter.observe_hidden(h_acc, alive)
        eng.finished.extend(done_now)
        return done_now


def build_speculative_decoder(engine, speculative,
                              spec_k: Optional[int] = None
                              ) -> SpeculativeDecoder:
    """Resolve the engines' ``speculative=`` argument: ``True`` reads
    ``FLAGS_spec_drafter``; ``"self"`` builds the self-drafting heads;
    a :class:`FusedCausalLM` wraps into a :class:`DraftModelDrafter`;
    a :class:`Drafter` instance is used as-is. ``spec_k`` defaults to
    ``FLAGS_spec_k``."""
    from ..core.flags import flag as _flag
    from .engine import FusedCausalLM

    k = int(spec_k) if spec_k is not None else int(_flag("spec_k"))
    if speculative is True:
        speculative = str(_flag("spec_drafter"))
    if isinstance(speculative, str):
        if speculative == "self":
            drafter = SelfDraftHeads()
        elif speculative == "draft":
            raise ValueError(
                "speculative='draft' needs a draft model — pass "
                "speculative=DraftModelDrafter(draft_model) (or the "
                "FusedCausalLM itself)")
        else:
            raise ValueError(
                f"speculative={speculative!r}: expected 'self', a "
                "Drafter instance, or a FusedCausalLM draft model")
    elif isinstance(speculative, FusedCausalLM):
        drafter = DraftModelDrafter(speculative)
    elif isinstance(speculative, Drafter):
        drafter = speculative
    else:
        raise ValueError(
            f"speculative={speculative!r}: expected True, 'self', a "
            "Drafter instance, or a FusedCausalLM draft model")
    return SpeculativeDecoder(engine, drafter, k)
