"""SLO-aware serving frontend over the continuous-batching engine.

The kernels under this (PR 4–5 fused weight-stream decode) are fast;
what turns them into a SERVICE is the layer here (ROADMAP item 2): an
async admission queue feeding a scheduler that interleaves CHUNKED
PREFILL with grouped decode — a 4k-token prompt fills the paged pool
in fixed-size chunks BETWEEN decode chunks, so admitting it never
stalls the decode batch for its whole prompt length — plus prefix/KV
reuse (serving/prefix_cache.py) so requests sharing a system prompt
map the prefix's pages instead of recomputing them. Only a prompt's
last chunk is waited for; the next dispatch waits for any other, so
the host plans that step while the device still runs the chunk.

Scheduling policy (``SLOConfig``): admission uses the engine's bounded
skip-ahead (head-of-line fix) ordered by request priority; the
prefill-vs-decode interleave is a weighted cycle derived from the
TTFT-vs-TPOT weights — ``ttft_weight : tpot_weight`` of 2:1 runs up to
two prefill chunks per decode chunk (new requests reach their first
token sooner), 1:2 the reverse (active streams keep their inter-token
gap tight). With any decode-ready request present, at most
``prefill_burst`` consecutive prefill chunks ever run, so an active
request's inter-token stall is BOUNDED by
``prefill_burst * prefill_chunk + decode_chunk`` tokens of device work
— the tier-1 stall-bound test pins this.

Telemetry (the PR 1–2 stats/roofline stack): per-request
``serve.{ttft_ms,tpot_ms,queue_wait_ms}`` histograms,
``serving.prefix_{hit,miss,pages_saved}`` + chunk counters, and every
scheduler phase reports under its own roofline rung —
``serve.prefill[c=N]`` per chunk size (honest post-sync timing of a
prompt's last chunk) next to the engine's ``decode.*[k=N]`` rungs.

Observability (PR 9): every lifecycle transition additionally lands in
the FLIGHT RECORDER (``serving/journal.py``, ``FLAGS_serve_journal``)
— a bounded ring journal from which one request's whole life is
reconstructable post-mortem — and every finish feeds the SLO monitor
(``serving/slo.py``: per-request TTFT/TPOT verdicts, rolling
``slo.goodput``, burn rate). ``run()`` dumps the journal tail + a
stats snapshot + every still-unserved request to a JSONL crash
artifact on any raise (``crash_dump``), so a production stack trace
always arrives with the request timelines that led to it.

Speculative decoding (ISSUE 12): constructed with ``speculative=``
(and optionally ``spec_k=``), the engine's decode slot of the
SLO-weighted interleave cycle runs DRAFT+VERIFY rounds instead of
token-by-token chunks (``inference/speculative.py`` — one streamed
``serve.verify[k=*,mp=N]`` pass per accepted window, greedy parity by
construction). It composes with everything here: chunked prefill
interleaves unchanged, preemption-by-recompute resets the drafter
slot so a resumed request re-drafts, accepted tokens count as
watchdog/deadline progress, and under TP the verify pass shard_maps
like ``prefill_chunk_raw`` while draft weights stay replicated. Each
round lands a ``spec_verify[k,accepted]`` journal event and the
``serve.accept_len`` histogram; serve_top renders the accept-rate
row.

Failure semantics (ISSUE 11 — see README "Failure semantics"): one
request's failure must never take the loop down. Per-request
``deadline_ms`` aborts a request wherever it sits (queue/prefill/
decode) and frees its pages; an exception inside one slot's
prefill/decode chunk retries with capped exponential backoff
(``FLAGS_serve_step_retries`` / ``FLAGS_serve_retry_backoff_ms``)
through the injectable serving clock, then errors out ONLY the
offending request; a progress watchdog
(``FLAGS_serve_watchdog_steps``) preempts/requeues a request that
stopped emitting tokens, and kills it on the second trip; admission
sheds with a typed ``ServerOverloaded`` when the (bounded) inbox,
queue depth, or SLO burn rate crosses its threshold — after the
scheduler has already degraded gracefully by shrinking prefill chunks
under pool pressure. All of it drivable deterministically by the
seeded fault registry in ``serving/faults.py``.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ..core.flags import flag as _flag
from ..incubate.nn.fused_transformer import PagedKV
from ..incubate.nn.layer_pattern import SLOT_RECURRENT
from ..inference.engine import ContinuousBatchingEngine, FusedCausalLM
from ..nn.functional.mixed_attention import window_pairs
from ..nn.functional.mla_attention import prefill_keys_expanded
from ..profiler import RecordEvent
from ..profiler import roofline as _roofline
from ..profiler import stats as _stats
from . import faults as _faults
from .accounting import UsageLedger
from .faults import (DeadlineExceeded, PoolSizingError, ServerOverloaded,
                     TokenCorruption, WatchdogTimeout)
from .journal import FlightRecorder
from .prefix_cache import PrefixCache
from ..inference.engine import (_refuse_latent, _refuse_recurrent,
                                _refuse_window)
from .request import Request
from .slo import SLOMonitor

__all__ = ["SLOConfig", "ServingEngine"]


class SLOConfig:
    """Scheduler knobs (see module docstring for the policy).

    ``ttft_weight`` / ``tpot_weight``: relative urgency of prefill
    (time-to-first-token) vs decode (time-per-output-token) work; the
    integer interleave cycle is derived from their ratio.
    ``prefill_chunk``: tokens per chunked-prefill program (the stall
    bound's unit; one compiled program serves every chunk of this size).
    ``admit_window`` / ``starvation_bound``: admission skip-ahead reach
    and its fairness bound (inference/engine.py ``_pick_waiting``).
    ``prefix_cache``: enable prefix/KV reuse (None, the default: on
    wherever the model can reuse pages, i.e. off on a model with
    recurrent layers, whose state the pages do not hold; an explicit
    True there is refused at engine construction); ``prefix_cache_pages``
    caps the registered pages (None = pool-pressure eviction only).
    ``ttft_target_ms`` / ``tpot_target_ms``: per-request SLO targets
    the monitor (serving/slo.py) judges verdicts against (None
    disables that leg); ``goodput_objective`` + ``slo_window`` shape
    the rolling ``slo.goodput`` gauge and its burn rate.
    ``tenant_fair``: replace priority-FIFO admission with
    DEFICIT-WEIGHTED round-robin over per-tenant queues (ISSUE 18) —
    each admission round credits every waiting tenant
    ``fair_quantum * weight`` tokens of deficit, the richest tenant's
    earliest admissible request admits and pays its token cost
    (prompt + max_new), so a flooding tenant cannot starve a light
    one; the engine's ``starvation_bound`` still caps how long ANY
    head-of-queue request can be passed over. ``tenant_weights`` maps
    tenant name -> relative share (missing tenants weigh 1.0).
    """

    def __init__(self, ttft_weight: float = 1.0,
                 tpot_weight: float = 1.0, prefill_chunk: int = 256,
                 admit_window: int = 8, starvation_bound: int = 16,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_pages: Optional[int] = None,
                 ttft_target_ms: Optional[float] = 1000.0,
                 tpot_target_ms: Optional[float] = 100.0,
                 goodput_objective: float = 0.99,
                 slo_window: int = 256, tenant_fair: bool = False,
                 tenant_weights: Optional[dict] = None,
                 fair_quantum: int = 256):
        if ttft_weight <= 0 or tpot_weight <= 0:
            raise ValueError("SLO weights must be positive")
        self.ttft_weight = float(ttft_weight)
        self.tpot_weight = float(tpot_weight)
        self.prefill_chunk = max(int(prefill_chunk), 1)
        self.admit_window = max(int(admit_window), 1)
        self.starvation_bound = max(int(starvation_bound), 1)
        self.prefix_cache = None if prefix_cache is None \
            else bool(prefix_cache)
        self.prefix_cache_pages = prefix_cache_pages
        self.ttft_target_ms = None if ttft_target_ms is None \
            else float(ttft_target_ms)
        self.tpot_target_ms = None if tpot_target_ms is None \
            else float(tpot_target_ms)
        if not 0.0 < float(goodput_objective) < 1.0:
            raise ValueError("goodput_objective must be in (0, 1)")
        self.goodput_objective = float(goodput_objective)
        self.slo_window = max(int(slo_window), 1)
        self.tenant_fair = bool(tenant_fair)
        self.tenant_weights = dict(tenant_weights or {})
        self.fair_quantum = max(int(fair_quantum), 1)
        r = self.ttft_weight / self.tpot_weight
        #: consecutive prefill chunks allowed while decoders wait /
        #: decode chunks between prefill opportunities — the weighted
        #: interleave cycle (1:1 → strict alternation)
        self.prefill_burst = max(1, int(round(r)))
        self.decode_burst = max(1, int(round(1.0 / r)))


class _Prefill:
    """Progress of one chunk-prefilling request parked on a slot.

    ``tokens`` is what gets prefilled: the prompt, or — for a request
    preempted out of a decode slot under pool pressure — the prompt
    plus everything already generated, so the final chunk's logits
    yield the NEXT token of the stream (recompute-style resume)."""

    __slots__ = ("req", "pos", "tokens")

    def __init__(self, req: Request, pos: int, tokens):
        self.req = req
        self.pos = pos  # tokens already in the pool
        self.tokens = tokens


class ServingEngine(ContinuousBatchingEngine):
    """Production-shaped serving frontend (see module docstring).

    Usage::

        eng = ServingEngine(model, max_batch=8,
                            slo=SLOConfig(prefill_chunk=128))
        eng.submit([1, 2, 3], max_new_tokens=16,
                   on_token=lambda r, t: push(t))   # any thread
        finished = eng.run()        # or step() on the serving thread

    Chunk-prefilling requests park on a slot under a side page-table
    key (``("prefill", i)``) so the decode batch's slot tables never
    see their half-filled pages; completion rekeys the pages to
    ``("slot", i)`` and the request joins the decode batch with its
    first token already emitted (from the final chunk's logits).
    """

    def __init__(self, model: FusedCausalLM,
                 slo: Optional[SLOConfig] = None, faults=None,
                 adapters=None, **engine_kwargs):
        slo = slo or SLOConfig()
        engine_kwargs.setdefault("admit_window", slo.admit_window)
        engine_kwargs.setdefault("starvation_bound",
                                 slo.starvation_bound)
        super().__init__(model, **engine_kwargs)
        self.slo = slo
        # multi-LoRA adapter bank (ISSUE 18, serving/adapters.py):
        # None serves the base model only; a bank may be SHARED by
        # fleet replicas (refcounts key on request id). Requests pin
        # their adapter at submit and release at every terminal path.
        self.adapters = adapters
        # deficit-weighted round-robin state (SLOConfig.tenant_fair):
        # tenant -> accumulated token deficit
        self._fair_deficit: Dict[str, float] = {}
        # flight recorder (FLAGS_serve_journal): None when disabled,
        # so every hot-path hook is a single attribute test — no
        # event tuples or extra dicts are ever allocated
        self.journal: Optional[FlightRecorder] = None
        if _flag("serve_journal"):
            self.journal = FlightRecorder(
                int(_flag("serve_journal_events")))
        self._journal = self.journal  # base-engine finish hook
        self.slo_monitor = SLOMonitor(
            ttft_target_ms=slo.ttft_target_ms,
            tpot_target_ms=slo.tpot_target_ms,
            objective=slo.goodput_objective, window=slo.slo_window)
        # usage ledger (ISSUE 17, FLAGS_usage_ledger): None when
        # disabled, so — exactly like the journal — every hot-path
        # hook is a single attribute test with zero allocations
        self.usage: Optional[UsageLedger] = None
        if _flag("usage_ledger"):
            self.usage = UsageLedger()
        self._usage = self.usage  # engine/speculative token hooks
        self._now = _faults.now   # plan / run / emit stamps: the seam
        self._n_steps = 0         # ``step=`` of the pt.serve.step span
        self.last_crash_dump: Optional[str] = None
        self.prefix_cache: Optional[PrefixCache] = None
        want_prefix = slo.prefix_cache
        if self._window:
            if want_prefix:
                _refuse_window("SLOConfig(prefix_cache=True): prefix "
                               "reuse")
            want_prefix = False
        elif self._mgr.slot_state == SLOT_RECURRENT:
            if want_prefix:
                _refuse_recurrent("SLOConfig(prefix_cache=True): prefix "
                                  "reuse")
            want_prefix = False
        if self._latent:
            if want_prefix:
                _refuse_latent("SLOConfig(prefix_cache=True): prefix "
                               "reuse")
            want_prefix = False
        if want_prefix or want_prefix is None:
            self.prefix_cache = PrefixCache(
                self._mgr, self.page_size, slo.prefix_cache_pages,
                journal=self.journal)
        # host-DRAM KV tier (ISSUE 20, FLAGS_kv_host_tier_bytes):
        # evicted prefix pages and preempted-slot pages spill to host
        # buffers behind the prefix cache's chain keys instead of being
        # recomputed; None (flag 0, no prefix cache, or a TP-sharded
        # pool) keeps every spill site one attribute test
        self.host_tier = None
        if self.prefix_cache is not None \
                and int(_flag("kv_host_tier_bytes") or 0) > 0 \
                and self.can_spill():
            from .host_tier import HostKVTier

            self.host_tier = HostKVTier(
                self, int(_flag("kv_host_tier_bytes")),
                journal=self.journal)
            self.prefix_cache.host_tier = self.host_tier
        self._prefilling: Dict[int, _Prefill] = {}
        # async admission: submit() appends here from ANY thread; the
        # scheduler thread drains into the priority-ordered waiting
        # list at each step
        self._inbox: List[Request] = []
        self._inbox_lock = threading.Lock()
        self._arrival = itertools.count()
        self._chunk_jit: dict = {}
        self._cycle_pos = 0
        #: scheduler action trace ("prefill"/"decode"), the stall-bound
        #: test's evidence; cheap (one short str per step)
        self.action_log: List[str] = []
        # crash-isolation bookkeeping (ISSUE 11): the request/slot a
        # risky phase is operating on (so its failure can be pinned to
        # the offending request), and the decode-chunk retry budget
        # (decode failures aren't attributable to one slot until the
        # budget is spent)
        self._admitting = None            # (req, slot) mid-_admit_into
        self._prefill_active = None       # (req, slot) mid-chunk
        # (req, slot, result) of the one prefill chunk the host did not
        # wait for (not its prompt's last): the next dispatch waits for it
        self._deferred = None
        self._decode_retries = 0
        # fault injection (serving/faults.py): installed on the engine,
        # the page manager (kv.alloc/kv.grow sites + squeeze target)
        # and the prefix cache; None keeps every site one attr test
        self.faults = None
        if faults is not None:
            self.install_faults(faults)

    def install_faults(self, faults) -> None:
        """Arm a :class:`~paddle_tpu.serving.faults.FaultInjector` on
        every wired site — the engine itself (``prefill.dispatch``,
        ``decode.step``, ``journal.dump``), the page manager
        (``kv.alloc``/``kv.grow`` + the squeeze target) and the prefix
        cache (``prefix.insert``). Callable after construction so a
        chaos bench can warm compile caches fault-free first."""
        self.faults = faults
        self._faults = faults             # base-engine decode.step site
        faults.bind(mgr=self._mgr, journal=self.journal)
        self._mgr._faults = faults
        if self.prefix_cache is not None:
            self.prefix_cache._faults = faults

    # ---------------- public API ----------------

    def submit(self, prompt, max_new_tokens: int = 32,
               eos_token_id=None, priority: int = 0,
               on_token=None, deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None,
               adapter_id: Optional[str] = None) -> int:
        """Thread-safe admission (any thread): queue a request, return
        its id. Tokens stream through ``on_token`` as they decode.
        ``deadline_ms`` bounds the request's whole life from arrival
        (see README "Failure semantics"); ``tenant`` stamps the usage
        ledger's billing identity (None bills to the default tenant);
        ``adapter_id`` routes decode through that LoRA adapter in the
        engine's :class:`~paddle_tpu.serving.adapters.AdapterBank`
        (the adapter is pinned against unload until this request
        terminates). Raises :class:`ServerOverloaded` — backpressure
        to the SUBMITTING thread — when the bounded inbox, the queue
        depth, or the SLO burn rate is past its shed threshold; a
        ``KeyError`` rejects an unknown or draining adapter."""
        req = Request(prompt, max_new_tokens, eos_token_id,
                      priority=priority, on_token=on_token,
                      deadline_ms=deadline_ms, tenant=tenant,
                      adapter_id=adapter_id)
        return self.submit_request(req)

    def submit_request(self, req: Request) -> int:
        if len(req.prompt) + req.max_new_tokens > self.max_length:
            raise ValueError("request exceeds engine max_length")
        self._check_overload(req)
        self._adapter_acquire(req)
        with self._inbox_lock:
            self._inbox.append(req)
        jr = self.journal
        if jr is not None:
            extra = {"prompt_len": int(len(req.prompt)),
                     "max_new": int(req.max_new_tokens)}
            if getattr(req, "tenant", None) is not None:
                extra["tenant"] = req.tenant
            if getattr(req, "adapter_id", None) is not None:
                extra["adapter"] = req.adapter_id
            jr.record("submit", req.id, -1, extra)
        _stats.inc("serve.submitted")
        return req.id

    # ---------------- multi-LoRA lifecycle (ISSUE 18) ----------------

    def _adapter_acquire(self, req: Request) -> None:
        """Pin ``req``'s adapter in this engine's bank and stamp the
        resolved bank slot on the request. Raises before the request
        enters any queue: an unknown/draining adapter (``KeyError``
        from the bank) or an adapter on a bank-less engine
        (``ValueError``) surfaces to the submitting thread."""
        name = getattr(req, "adapter_id", None)
        if name is None:
            return
        if self.adapters is None:
            raise ValueError(
                f"request {req.id} names adapter {name!r} but the "
                "engine has no adapter bank")
        if self._spec is not None:
            raise ValueError(
                "adaptered requests don't compose with speculative "
                "decoding (the verify pass has no delta path yet)")
        req._adapter_slot = self.adapters.acquire(name, req.id)

    def _adapter_release(self, req) -> None:
        """Unpin ``req``'s adapter (idempotent — safe on every
        terminal path, and a no-op for base-model requests)."""
        bank = self.adapters
        if bank is not None \
                and getattr(req, "adapter_id", None) is not None:
            bank.release(req.id)

    def _adapter_operands(self, active):
        """Serving override of the decode-chunk adapter hook: when any
        active slot decodes through a bank adapter, return the traced
        operands — the per-slot bank-slot map (-1 = base model) plus
        the bank's device-cached ``[L, S, ...]`` A/B stacks. A pure-
        base batch returns ``(None, None)`` and keeps the fast grouped
        decode program; adapter membership rides the slot map, so the
        compiled-program count never depends on WHICH adapters are
        live (hot load/unload only bumps the bank's device cache)."""
        bank = self.adapters
        if bank is None:
            return None, None
        slots = np.full((self.max_batch,), -1, np.int32)
        any_adaptered = False
        for i in active:
            req = self._slots[i]
            s = getattr(req, "_adapter_slot", None) \
                if req is not None else None
            if s is not None and s >= 0:
                slots[i] = s
                any_adaptered = True
        if not any_adaptered:
            return None, None
        return jnp.asarray(slots), bank.operands(tp=self._gen._tp)

    def _check_overload(self, req: Request) -> None:
        """Admission-time overload shedding (ISSUE 11): reject with a
        typed ``ServerOverloaded`` when (a) the inbox is at its hard
        bound (``FLAGS_serve_inbox_limit``; an unbounded producer can
        no longer grow the waiting list without backpressure), (b) the
        queue depth (inbox + waiting) crossed
        ``FLAGS_serve_shed_queue_depth``, or (c) the PR 9 SLO
        burn-rate gauge crossed ``FLAGS_serve_shed_burn_rate`` (the
        service is already missing its objective — more load only
        deepens the miss). 0 disables each threshold."""
        limit = int(_flag("serve_inbox_limit"))
        depth_cap = int(_flag("serve_shed_queue_depth"))
        with self._inbox_lock:
            inbox = len(self._inbox)
        reason = None
        if limit > 0 and inbox >= limit:
            reason = f"inbox at its bound ({inbox}/{limit})"
        elif depth_cap > 0 and inbox + len(self.waiting) >= depth_cap:
            reason = (f"queue depth {inbox + len(self.waiting)} >= "
                      f"shed threshold {depth_cap}")
        else:
            burn_cap = float(_flag("serve_shed_burn_rate"))
            burn = self.slo_monitor.burn_rate
            if burn_cap > 0 and burn is not None and burn > burn_cap:
                reason = (f"SLO burn rate {burn:.2f} > shed "
                          f"threshold {burn_cap:.2f}")
        if reason is None:
            return
        _stats.inc("serving.shed")
        u = self.usage
        # terminal-state audit (ISSUE 17): a shed-at-submit request
        # DID enter the system — close its (empty) usage record so
        # every request emits exactly one
        rec = u.finish(req, "shed") if u is not None else None
        jr = self.journal
        if jr is not None:
            extra = {"reason": reason}
            if rec is not None:
                extra["usage"] = rec
            jr.record("shed", req.id, -1, extra)
        raise ServerOverloaded(
            f"request {req.id} shed at submit: {reason}")

    @property
    def num_prefilling(self) -> int:
        return len(self._prefilling)

    @property
    def queue_depth(self) -> int:
        """Queued-but-not-yet-admitted requests (inbox + waiting) —
        the fleet router's load/shed signal for this replica."""
        with self._inbox_lock:
            return len(self._inbox) + len(self.waiting)

    @property
    def has_work(self) -> bool:
        """Anything for ``step()`` to do (the fleet replica loop's
        idle test)."""
        return bool(self._inbox or self.waiting or self._prefilling
                    or self.num_active)

    # ---------------- fleet hooks (ISSUE 14) ----------------

    def adopt_request(self, req: Request) -> int:
        """Fleet-tier admission (serving/router.py): enqueue an
        already-constructed request WITHOUT the per-engine overload
        check — the router owns shedding at its tier, and a failover/
        hedge re-dispatch must never bounce off the surviving
        replica's thresholds. The request keeps its original lifecycle
        marks (arrival, TTFT) and any ``_resume_tokens``, so a
        failed-over stream just continues."""
        if len(req.prompt) + req.max_new_tokens > self.max_length:
            raise ValueError("request exceeds engine max_length")
        # re-resolve the adapter against THIS engine's bank: the slot
        # id stamped by the dead replica is meaningless here (acquire
        # is idempotent by rid, so a shared fleet bank just re-pins)
        self._adapter_acquire(req)
        with self._inbox_lock:
            self._inbox.append(req)
        jr = self.journal
        if jr is not None:
            extra = {"prompt_len": int(len(req.prompt)),
                     "max_new": int(req.max_new_tokens),
                     "adopted": True}
            if getattr(req, "tenant", None) is not None:
                extra["tenant"] = req.tenant
            if getattr(req, "adapter_id", None) is not None:
                extra["adapter"] = req.adapter_id
            jr.record("submit", req.id, -1, extra)
        _stats.inc("serve.submitted")
        return req.id

    def detach_inflight(self) -> List[Request]:
        """Crash-failover support (serving/router.py): strip and
        return EVERY in-flight request — inbox, waiting list, prefill
        slots, decode slots — in admission-priority order (queued
        first, then prefilling, then decoding). Pages are deliberately
        NOT freed: this runs against a replica the router already
        declared dead, whose pool (and possibly wedged step) dies with
        it; touching the manager from another thread would race a
        half-finished step. The caller re-dispatches the requests via
        the recompute resume path."""
        with self._inbox_lock:
            inbox, self._inbox = self._inbox, []
        waiting, self.waiting = list(self.waiting), []
        prefilling = [self._prefilling[i].req
                      for i in sorted(self._prefilling)]
        self._prefilling.clear()
        self._deferred = None
        decoding = [r for r in self._slots if r is not None]
        self._slots = [None] * self.max_batch
        self._lens[:] = 0
        self._last_tok[:] = 0
        u = self.usage
        if u is not None:
            # the detached requests stop holding USABLE pages here
            # (the stranded pool dies with the replica): close their
            # page-second integrals so the fleet fold charges them
            # only for time the pages could still serve them
            for r in prefilling + decoding:
                u.set_pages(r, 0)
        out = [r for r in inbox + waiting + prefilling + decoding
               if not r.done]
        # unpin adapters held by this (dead) replica's bank — the
        # adopting replica re-acquires against its own (possibly the
        # same shared) bank, so refcounts never leak across failover
        for r in out:
            self._adapter_release(r)
        return out

    def step(self):
        """One scheduler action: drain admissions (shed-aware), expire
        deadlines, tick the progress watchdog, then run EITHER one
        prefill chunk or one decode chunk per the SLO interleave —
        CRASH-ISOLATED: an exception inside admission or either chunk
        retries with capped exponential backoff and then errors out
        only the offending request (``_recover_*``); the loop keeps
        serving everyone else. Returns requests finished this step.

        Each completed step's wall time is ATTRIBUTED into phase
        histograms via the clock seam (``serve.step.{admit,
        prefill_chunk,decode_chunk,spec_verify}_ms``, the work phase
        once more as ``{plan,run,emit}_ms``, plus the
        ``host_overhead_ms`` residual — see ``_observe_step``), and
        every phase is a ``pt.serve.*`` span in the profiler's trace;
        recovery early-returns skip attribution so the phase sums
        stay an exact partition of the observed ``total_ms``."""
        self._n_steps += 1
        with RecordEvent("serve.step", step=self._n_steps) as span:
            return self._step(span)

    def _step(self, span):
        ts0 = _faults.now()
        self._run_ts = None
        with RecordEvent("serve.admit"):
            self._drain_inbox()
            self._expire_deadlines()
            try:
                self._admit()
            except Exception as e:
                self._recover_admit(e)
            self.slo_monitor.update_gauges(
                len(self.waiting) + len(self._inbox), self.num_active,
                len(self._prefilling), self.max_batch)
            self._watchdog_tick()
        ts_admit = _faults.now()
        action = self._pick_action()
        span.annotate(action=action)
        if action == "prefill":
            self.action_log.append("prefill")
            try:
                out = self._prefill_step()
            except Exception as e:
                return self._recover_prefill(e)
            tgt, self._prefill_active = self._prefill_active, None
            if tgt is not None and self._deferred is None:
                tgt[0].n_retries = 0  # chunk landed — budget restored
            ts_work = _faults.now()
            u = self.usage
            if u is not None:
                # the chunk prefilled exactly one request: charge it
                # the SAME float the phase histogram observes below —
                # the ledger's conservation invariant is bitwise
                u.charge_phase("prefill_chunk",
                               (ts_work - ts_admit) * 1e3,
                               (tgt[0],) if tgt is not None else ())
            self._observe_step(ts0, ts_admit, ts_work,
                               "prefill_chunk")
            return out
        if self.num_active == 0:
            self._observe_step(ts0, ts_admit, ts_admit, None)
            return []
        self.action_log.append("decode")
        before = [(r, len(r.generated))
                  for r in self._slots if r is not None]
        t0 = time.perf_counter()
        try:
            done = super().step()
        except Exception as e:
            return self._recover_decode(e)
        self._decode_retries = 0
        ts_work = _faults.now()
        dt_ms = (time.perf_counter() - t0) * 1e3
        u = self.usage
        advanced = []
        for req, n0 in before:
            emitted = len(req.generated) - n0
            if emitted <= 0:
                continue
            if u is not None:
                advanced.append(req)
                u.add_tokens(req, decode=emitted)
            # the request waited the whole chunk for its tokens, so
            # its streaming gap is dt_ms/emitted — observed once PER
            # TOKEN, so a slot that finished mid-chunk neither drops
            # out of the histogram nor understates its gap
            gap = dt_ms / emitted
            for _ in range(emitted):
                _stats.observe("serve.tpot_ms", gap)
        phase = ("spec_verify"
                 if getattr(self, "_spec", None) is not None
                 else "decode_chunk")
        if u is not None:
            # the chunk's device time splits over the slots it
            # ADVANCED (a slot the chunk couldn't move shouldn't pay
            # for it); a wholly-stalled chunk splits over everyone
            # who was active when it started — same float as the
            # histogram observation below
            u.charge_phase(phase, (ts_work - ts_admit) * 1e3,
                           advanced or [r for r, _ in before])
        self._observe_step(ts0, ts_admit, ts_work, phase)
        return done

    def _observe_step(self, ts0, ts_admit, ts_work, phase):
        """Per-step serving-time attribution (continuous-telemetry
        tentpole): split the step's wall clock into admit (drain +
        deadline sweep + admission + watchdog), the work phase
        (prefill_chunk / decode_chunk / spec_verify when speculation
        drives decode; migration is timed by the router around slot
        export/import), and host_overhead — the RESIDUAL between the
        work phase's end and step exit (token bookkeeping, tpot
        observes, finish hooks). The work phase splits once more, at
        the two stamps its program call left in ``_run_ts``: plan
        (action picked .. operands built), run (the program call until
        its token fetch returned, or, for a chunk not waited for, until
        the chunk deferred before it was: the host's waits on the
        device all fall here) and emit (tokens -> requests,
        callbacks, finish hooks, page release); a work phase that
        never reached its program (a stalled chunk) is all plan.
        admit + plan + run + emit + host_overhead == total EXACTLY
        per step, and plan + run + emit is the work phase, so the
        histograms answer "where did the step go" with no unaccounted
        remainder. All stamps come from the clock seam — ManualClock
        tests see exact values."""
        if not _stats.is_enabled():
            return
        ts_end = _faults.now()
        _stats.observe("serve.step.admit_ms", (ts_admit - ts0) * 1e3)
        if phase is not None:
            _stats.observe("serve.step.%s_ms" % phase,
                           (ts_work - ts_admit) * 1e3)
            t_run0, t_run1 = self._run_ts or (ts_work, ts_work)
            _stats.observe("serve.step.plan_ms",
                           (t_run0 - ts_admit) * 1e3)
            _stats.observe("serve.step.run_ms", (t_run1 - t_run0) * 1e3)
            _stats.observe("serve.step.emit_ms",
                           (ts_work - t_run1) * 1e3)
        _stats.observe("serve.step.host_overhead_ms",
                       (ts_end - ts_work) * 1e3)
        _stats.observe("serve.step.total_ms", (ts_end - ts0) * 1e3)

    def _finish_hook(self, req, slot: int):
        """Serving finish path (called from the engine the moment a
        request completes, before its pages release): stamp t_done,
        observe the lifetime per-token mean, judge the SLO verdict,
        and journal a verdict-rich finish event."""
        req.t_done = _faults.now()
        if getattr(req, "state", None) is None:
            req.state = "ok"
        tpot = getattr(req, "tpot_s", None)
        if tpot is not None:
            # whole-lifetime per-token mean (the chunk-level
            # serve.tpot_ms is the streaming-gap view)
            _stats.observe("serve.request_tpot_ms", tpot * 1e3)
        v = self.slo_monitor.observe_finish(req)
        self._adapter_release(req)
        u = self.usage
        # close the usage record exactly once (a snapshot rides the
        # finish event; the chunk that finished the request may still
        # charge its tail after this — exports read final values)
        rec = u.finish(req, "ok") if u is not None else None
        jr = self.journal
        if jr is not None:
            extra = {"n_tokens": len(req.generated),
                     "ttft_ms": v["ttft_ms"],
                     "tpot_ms": v["tpot_ms"],
                     "slo_ok": v["slo_ok"]}
            if getattr(req, "tenant", None) is not None:
                extra["tenant"] = req.tenant
            if getattr(req, "adapter_id", None) is not None:
                extra["adapter"] = req.adapter_id
            if rec is not None:
                extra["usage"] = rec
            jr.record("finish", req.id, slot, extra)

    # ---------------- failure semantics (ISSUE 11) ----------------

    _FAIL_COUNTERS = {"deadline_exceeded": "serving.deadline_exceeded",
                      "shed": "serving.shed",
                      "error": "serving.request_errors"}

    def _fail_request(self, req: Request, slot: int, state: str,
                      exc: BaseException):
        """Terminal failure path: stamp the request's terminal state
        and error, roll it into the SLO window as a miss, journal the
        terminal event, and move it to ``finished``. The error
        surfaces ONLY to this request (``req.error`` / its caller) —
        never to the serve loop. Callers remove the request from
        queue/slot structures and free its pages FIRST."""
        req.done = True
        req.state = state
        req.error = exc
        req.t_done = _faults.now()
        self.slo_monitor.observe_error(req)
        self._adapter_release(req)
        u = self.usage
        rec = u.finish(req, state) if u is not None else None
        _stats.inc(self._FAIL_COUNTERS.get(
            state, "serving.request_errors"))
        jr = self.journal
        if jr is not None:
            ev = state if state in ("deadline_exceeded", "shed") \
                else "error"
            extra = {"error": type(exc).__name__,
                     "msg": str(exc)[:200]}
            if rec is not None:
                extra["usage"] = rec
            jr.record(ev, req.id, slot, extra)
        self.finished.append(req)

    def _drop_prefill_slot(self, i: int):
        """Vacate prefill slot ``i`` and free its pages (no requeue —
        the caller decides the request's fate)."""
        self._settle_deferred(i)
        stt = self._prefilling.pop(i, None)
        self._mgr.recurrent_free(i)
        if ("prefill", i) in self._mgr._owned:
            self._mgr.free(("prefill", i))
        u = self.usage
        if u is not None and stt is not None:
            u.set_pages(stt.req, 0)

    def _release(self, i: int) -> None:
        """Serving override: close the vacating request's page-second
        integral (the ledger's KV accounting) before the base engine
        frees slot ``i``'s pages."""
        u = self.usage
        if u is not None:
            req = self._slots[i]
            if req is not None:
                u.set_pages(req, 0)
        super()._release(i)

    def _expire_deadlines(self):
        """Abort every request whose ``deadline_ms`` budget elapsed —
        wherever it sits (waiting list, prefill slot, decode slot) —
        freeing its pages and surfacing ``DeadlineExceeded`` only to
        it. Runs once per scheduler step on the injected clock."""
        now = _faults.now()
        expired = [r for r in self.waiting if r.past_deadline(now)]
        for req in expired:
            self.waiting.remove(req)
            self._fail_request(req, -1, "deadline_exceeded",
                               DeadlineExceeded(
                                   f"request {req.id} exceeded its "
                                   f"{req.deadline_ms}ms deadline in "
                                   "queue"))
        for i in [i for i, s in list(self._prefilling.items())
                  if s.req.past_deadline(now)]:
            req = self._prefilling[i].req
            self._drop_prefill_slot(i)
            self._fail_request(req, i, "deadline_exceeded",
                               DeadlineExceeded(
                                   f"request {req.id} exceeded its "
                                   f"{req.deadline_ms}ms deadline "
                                   "during prefill"))
        for i in range(self.max_batch):
            req = self._slots[i]
            if req is not None and req.past_deadline(now):
                self._release(i)
                self._fail_request(req, i, "deadline_exceeded",
                                   DeadlineExceeded(
                                       f"request {req.id} exceeded "
                                       f"its {req.deadline_ms}ms "
                                       "deadline during decode"))

    def _note_retry(self, req, slot: int, exc: BaseException,
                    phase: str) -> bool:
        """Crash-isolation retry bookkeeping: True = a retry is still
        in budget (``FLAGS_serve_step_retries``) and its capped
        exponential backoff has been slept through the serving clock;
        False = the budget is spent and the caller must error the
        request out."""
        budget = int(_flag("serve_step_retries"))
        if req.n_retries >= budget:
            return False
        req.n_retries += 1
        _stats.inc("serving.step_retries")
        u = self.usage
        if u is not None:
            u.add_event(req, retry=1)
        delay_ms = min(
            float(_flag("serve_retry_backoff_ms"))
            * (2 ** (req.n_retries - 1)),
            float(_flag("serve_retry_backoff_cap_ms")))
        jr = self.journal
        if jr is not None:
            jr.record("retry", req.id, slot,
                      {"phase": phase, "attempt": req.n_retries,
                       "backoff_ms": delay_ms,
                       "error": type(exc).__name__})
        _faults.clock().sleep(delay_ms / 1e3)
        return True

    def _recover_admit(self, e: Exception):
        """An exception inside admission: roll back the half-admitted
        request (its prefill-key pages release), then retry-or-fail
        it. Failures outside any admission (no request attributable)
        are not isolable and propagate to ``run()``'s crash dump."""
        if isinstance(e, PoolSizingError):
            raise e
        tgt = self._admitting
        self._admitting = None
        if tgt is None:
            raise e
        req, i = tgt
        self._drop_prefill_slot(i)
        if self._note_retry(req, i, e, "admit"):
            self.waiting.append(req)
            self._sort_waiting()
        else:
            self._fail_request(req, i, "error", e)

    def _recover_prefill(self, e: Exception):
        """An exception inside one slot's prefill chunk: the offending
        request is known (``_prefill_active``); retry it in place with
        backoff, then error out only it. Chunk re-dispatch is clean —
        nothing host-side mutated before the raise, and re-running the
        chunk rewrites the same KV pages with identical values."""
        if isinstance(e, PoolSizingError):
            raise e
        tgt = self._prefill_active
        self._prefill_active = None
        if tgt is None:
            raise e
        req, i = tgt
        if self._note_retry(req, i, e, "prefill"):
            return []
        self._drop_prefill_slot(i)
        if self._slots[i] is req:   # failed past the decode handoff
            self._release(i)
        self._fail_request(req, i, "error", e)
        return []

    def _recover_deferred(self, e: Exception, req, i: int):
        """An exception while a deferred chunk was waited for: charged
        to that chunk's request (``req``, still prefilling on slot
        ``i``), not to the step that waited. Its prompt restarts from
        the queue, as the pool and state the chunk wrote are not
        trusted, or, the retry budget spent, it errors out."""
        if self._note_retry(req, i, e, "prefill"):
            self._requeue_prefill(i)
        else:
            self._drop_prefill_slot(i)
            self._fail_request(req, i, "error", e)

    def _recover_decode(self, e: Exception):
        """An exception inside the decode chunk: not attributable to
        one slot (the chunk is batched), so retry the whole chunk with
        backoff; once the budget is spent, sacrifice the LEAST-urgent
        active slot (bounded degradation — a persistent fault sheds
        one request per exhausted budget instead of hanging or killing
        the loop) and keep serving."""
        if isinstance(e, PoolSizingError):
            raise e
        budget = int(_flag("serve_step_retries"))
        if self._decode_retries < budget:
            self._decode_retries += 1
            _stats.inc("serving.step_retries")
            delay_ms = min(
                float(_flag("serve_retry_backoff_ms"))
                * (2 ** (self._decode_retries - 1)),
                float(_flag("serve_retry_backoff_cap_ms")))
            jr = self.journal
            if jr is not None:
                jr.record("retry", -1, -1,
                          {"phase": "decode",
                           "attempt": self._decode_retries,
                           "backoff_ms": delay_ms,
                           "error": type(e).__name__})
            _faults.clock().sleep(delay_ms / 1e3)
            return []
        self._decode_retries = 0
        victims = [j for j in range(self.max_batch)
                   if self._slots[j] is not None]
        if not victims:
            raise e
        j = max(victims, key=lambda j: self._urgency(self._slots[j]))
        req = self._slots[j]
        self._release(j)
        self._fail_request(req, j, "error", e)
        return []

    def _watchdog_tick(self):
        """Progress watchdog: a request whose token progress marker
        hasn't moved for ``FLAGS_serve_watchdog_steps`` scheduler
        steps is preempted/requeued (first trip) and failed (second) —
        the loop never hangs behind a wedged slot. 0 disables."""
        n = int(_flag("serve_watchdog_steps"))
        if n <= 0:
            return
        for i, stt in list(self._prefilling.items()):
            self._wd_check(stt.req, ("prefill", stt.pos), i, n, True)
        for i in range(self.max_batch):
            req = self._slots[i]
            if req is not None:
                self._wd_check(req, ("decode", len(req.generated)),
                               i, n, False)

    def _wd_check(self, req, mark, slot: int, n: int,
                  prefilling: bool):
        if req._wd_mark != mark:
            req._wd_mark = mark
            req._wd_steps = 0
            return
        req._wd_steps += 1
        if req._wd_steps < n:
            return
        req._wd_steps = 0
        req._wd_mark = None
        req._wd_trips += 1
        jr = self.journal
        if jr is not None:
            jr.record("watchdog", req.id, slot,
                      {"trip": req._wd_trips,
                       "phase": "prefill" if prefilling else "decode"})
        if req._wd_trips <= 1:
            # first trip: give the stack one recovery shot — requeue
            # (prefill) / preempt-by-recompute (decode); re-admission
            # is prefix-cache-hot, so a transient wedge costs little
            _stats.inc("serving.watchdog_preempts")
            if prefilling:
                self._requeue_prefill(slot)
            else:
                self._preempt_slot(slot)
            return
        _stats.inc("serving.watchdog_kills")
        if prefilling:
            self._drop_prefill_slot(slot)
        else:
            self._release(slot)
        self._fail_request(req, slot, "error", WatchdogTimeout(
            f"request {req.id}: no token progress for {n} scheduler "
            "steps twice (one preempt/requeue already spent)"))

    def run(self):
        """Drain: step until every submitted request finishes.

        Crash-dump-on-exception: any raise journals an ``error``
        event and writes the flight-recorder tail + stats snapshot +
        every still-in-flight request to a JSONL artifact
        (``crash_dump``) before propagating. On every exit the
        ``serving.unserved`` counter stamps requests that never
        reached admission (their queue wait is otherwise invisible —
        ``serve.queue_wait_ms`` only observes admitted requests)."""
        try:
            while (self._inbox or self.waiting or self._prefilling
                   or self.num_active):
                self.step()
        except BaseException as e:
            jr = self.journal
            if jr is not None:
                jr.record("error", -1, -1,
                          {"error": type(e).__name__})
            self.crash_dump(error=e)
            raise
        finally:
            unserved = len(self._inbox) + len(self.waiting)
            if unserved:
                _stats.inc("serving.unserved", unserved)
                u = self.usage
                if u is not None:
                    # terminal-state audit: never-admitted requests
                    # still emit exactly one usage record each
                    for req in list(self._inbox) + list(self.waiting):
                        u.finish(req, "unserved")
            if self.journal is not None:
                self.journal.publish_gauges()
        return self.finished

    def crash_dump(self, error=None,
                   path: Optional[str] = None) -> Optional[str]:
        """Post-mortem JSONL artifact: every surviving journal event
        (``type=event`` lines), the full ``stats.snapshot()``
        (``type=stats``), and a ``type=crash`` header naming the error
        and every request still in flight — inbox/waiting requests
        (the unserved ones), prefilling slots with their chunk
        position, and active decode slots. Written under
        ``FLAGS_serve_journal_dir`` (default: the system temp dir) as
        ``serve_crash_rank<r>_pid<pid>.jsonl``; read it back with
        ``tools/serve_top.py``.

        NEVER RAISES (ISSUE 11): this runs inside ``run()``'s error
        handling, and a failed dump (full disk, bad journal dir, an
        injected ``journal.dump`` fault) must not mask the original
        exception. On failure it warns on stderr and returns None."""
        import sys

        try:
            return self._crash_dump_impl(error, path)
        except BaseException as dump_err:  # noqa: BLE001 — by design
            print(f"serve: crash dump FAILED ({dump_err!r}) — "
                  "original error preserved", file=sys.stderr)
            return None

    def _crash_dump_impl(self, error, path: Optional[str]) -> str:
        import json
        import os
        import sys
        import tempfile

        f0 = self.faults
        if f0 is not None:
            f0.fire("journal.dump")
        if path is None:
            d = str(_flag("serve_journal_dir")) or tempfile.gettempdir()
            rank = 0
            try:
                import jax

                rank = int(jax.process_index())
            except Exception:
                pass
            path = os.path.join(
                d, f"serve_crash_rank{rank}_pid{os.getpid()}.jsonl")
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        unserved = []
        with self._inbox_lock:
            inbox = list(self._inbox)
        for req in inbox:
            unserved.append({"rid": req.id, "state": "inbox",
                             "prompt_len": int(len(req.prompt))})
        for req in self.waiting:
            unserved.append({"rid": req.id, "state": "waiting",
                             "prompt_len": int(len(req.prompt))})
        for i, stt in sorted(self._prefilling.items()):
            unserved.append({"rid": stt.req.id, "state": "prefilling",
                             "slot": i, "pos": int(stt.pos),
                             "prompt_len": int(len(stt.tokens))})
        for i, req in enumerate(self._slots):
            if req is not None:
                unserved.append({"rid": req.id, "state": "decoding",
                                 "slot": i,
                                 "n_tokens": len(req.generated)})
        events = self.journal.events() if self.journal is not None \
            else []
        with open(path, "w") as f:
            for ev in events:
                f.write(json.dumps({"type": "event", **ev}) + "\n")
            f.write(json.dumps({"type": "stats",
                                "stats": _stats.snapshot()}) + "\n")
            f.write(json.dumps({
                "type": "crash",
                "error": repr(error) if error is not None else None,
                "unserved": unserved,
                "dropped_events": (self.journal.dropped
                                   if self.journal is not None
                                   else 0)}) + "\n")
        self.last_crash_dump = path
        print(f"serve: crash dump -> {path}", file=sys.stderr)
        return path

    # ---------------- admission ----------------

    def _drain_inbox(self):
        """Move submitted requests into the priority-ordered waiting
        list — SHED-AWARE (ISSUE 11): once the sorted queue is past
        ``FLAGS_serve_shed_queue_depth``, the overflow tail (lowest
        priority, newest arrivals) terminates in the ``shed`` state
        instead of growing the waiting list without bound. The
        submit-side check already rejects most overload; this is the
        backstop for racing producers that got past it."""
        with self._inbox_lock:
            newly, self._inbox = self._inbox, []
        for req in newly:
            req._seq = next(self._arrival)
            self.waiting.append(req)
        if newly:
            jr = self.journal
            if jr is not None:
                for req in newly:
                    jr.record("queued", req.id, -1, None)
            self._sort_waiting()
            cap = int(_flag("serve_shed_queue_depth"))
            if cap > 0 and len(self.waiting) > cap:
                overflow = self.waiting[cap:]
                del self.waiting[cap:]
                for req in overflow:
                    self._fail_request(
                        req, -1, "shed", ServerOverloaded(
                            f"request {req.id} shed at drain: queue "
                            f"depth past {cap}"))

    def _sort_waiting(self):
        # higher priority first; within a level, STABLE adapter
        # grouping (ISSUE 18): requests sharing an adapter sort
        # adjacently, groups ordered by their oldest member's arrival
        # and FIFO inside each group — same-adapter requests admit
        # together so a decode chunk carries fewer distinct adapters
        # (tighter ragged delta groups). With no adapters every
        # request shares the None group and this is EXACTLY the old
        # priority-FIFO order. The skip-ahead window scans THIS order.
        first: Dict[Optional[str], int] = {}
        for r in self.waiting:
            a = getattr(r, "adapter_id", None)
            s = getattr(r, "_seq", r.id)
            if a not in first or s < first[a]:
                first[a] = s
        self.waiting.sort(
            key=lambda r: (-getattr(r, "priority", 0),
                           first[getattr(r, "adapter_id", None)],
                           getattr(r, "_seq", r.id)))

    @staticmethod
    def _tenant_of(req) -> str:
        t = getattr(req, "tenant", None)
        return t if t is not None else "default"

    @staticmethod
    def _admit_cost(req) -> int:
        """DWRR cost of admitting ``req``, in tokens: the prompt it
        will prefill plus the generation budget it may decode — a
        work proxy known BEFORE the request runs."""
        return int(len(req.prompt)) + int(req.max_new_tokens)

    def _pick_waiting(self):
        """Admission pick. Default: the engine's priority-FIFO bounded
        skip-ahead. With ``SLOConfig.tenant_fair``: DEFICIT-WEIGHTED
        round-robin over per-tenant queues — each pick credits every
        waiting tenant ``fair_quantum * weight`` deficit tokens, the
        richest tenant's first admissible request (within the
        skip-ahead window of its own queue) admits and pays its token
        cost. A flooding tenant drains its deficit as fast as it
        earns it, so light tenants accumulate credit and interleave
        at their weighted share. The engine's starvation bound is
        PRESERVED: every pass-over of an earlier arrival bumps its
        ``_admit_skips``, and a head skipped ``starvation_bound``
        times admits next regardless of deficits."""
        if not self.slo.tenant_fair:
            return super()._pick_waiting()
        if not self.waiting:
            return None
        head = self.waiting[0]
        if head._admit_skips >= self.starvation_bound:
            # bounded unfairness: the window collapses to the head
            return self.waiting.pop(0) if self._can_admit(head) \
                else None
        queues: Dict[str, List[Request]] = {}
        for r in self.waiting:
            queues.setdefault(self._tenant_of(r), []).append(r)
        d = self._fair_deficit
        for t in list(d):
            if t not in queues:   # vanished tenant banks no credit
                del d[t]
        w = self.slo.tenant_weights
        for t in queues:
            d[t] = d.get(t, 0.0) \
                + self.slo.fair_quantum * float(w.get(t, 1.0))
        for t in sorted(queues, key=lambda q: (-d[q], q)):
            for r in queues[t][: self.admit_window]:
                if self._can_admit(r):
                    d[t] -= self._admit_cost(r)
                    j = self.waiting.index(r)
                    if j > 0:
                        for skipped in self.waiting[:j]:
                            skipped._admit_skips += 1
                        _stats.inc("serving.admission_skips", j)
                    return self.waiting.pop(j)
        return None

    def _slot_free(self, i: int) -> bool:
        return self._slots[i] is None and i not in self._prefilling

    @staticmethod
    def _admit_tokens(req):
        """What admission will prefill: the prompt, or the recorded
        prompt+generated resume stream of a preempted request."""
        toks = getattr(req, "_resume_tokens", None)
        return req.prompt if toks is None else toks

    def _first_chunk_pages(self, req) -> int:
        """Pages the FIRST prefill chunk needs beyond any prefix hit."""
        toks = self._admit_tokens(req)
        shared = self.prefix_cache.match(toks) \
            if self.prefix_cache is not None else []
        covered = len(shared) * self.page_size
        c = self._chunk_size(len(toks) - covered)
        need = min(self._mgr.pages_needed(covered + c),
                   self._pages_per_seq)
        return need - len(shared)

    def _restore_prefix(self, req) -> int:
        """Host-tier promotion ahead of admission (ISSUE 20): pull the
        spilled continuation of this request's chain back into free
        pool pages, so the ``match`` below sees it as an ordinary
        prefix hit and the suffix prefill shrinks by the restored
        coverage. Reserves the first chunk's worth of pages so a
        restore can never starve the very admission it serves."""
        ht = self.host_tier
        if ht is None or not len(ht):
            return 0
        toks = self._admit_tokens(req)
        reserve = self._mgr.pages_needed(
            self._chunk_size(len(toks))) + 1
        restored = self.prefix_cache.restore_chain(toks,
                                                   reserve=reserve)
        if restored:
            _stats.inc("serving.prefix_restored_pages", restored)
        return restored

    def _can_admit(self, req) -> bool:
        self._restore_prefix(req)
        need = self._first_chunk_pages(req)
        # pool pressure: evict cold cached prefixes page by page (an
        # evicted entry only frees its page if no live sequence still
        # maps it, so re-check after each drop)
        while need > self._mgr.free_pages \
                and self.prefix_cache is not None \
                and self.prefix_cache.evict(1):
            # eviction can drop the very pages the match above counted
            # as covered, so recompute — the admit decision must
            # reflect the post-eviction cache. match() LRU-touches its
            # chain, so the matched prefix is the LAST thing evicted.
            need = self._first_chunk_pages(req)
        return need <= self._mgr.free_pages

    def _evict_for(self, n_pages: int) -> bool:
        """Free pool pages for an n_pages grow by dropping cold cached
        prefixes; True once the free list covers it."""
        if self.prefix_cache is not None:
            while n_pages > self._mgr.free_pages \
                    and self.prefix_cache.evict(1):
                pass
        return n_pages <= self._mgr.free_pages

    def _admit_into(self, req: Request, i: int):
        """Park ``req`` on slot ``i`` in the chunk-prefill phase: map
        any cached prefix pages, allocate the first chunk's tail pages,
        and let ``_prefill_step`` fill the prompt chunk by chunk. No
        prefill compute happens at admission — admitting a 4k prompt
        costs a page-table update, not a 4k-token program."""
        self._admitting = (req, i)   # crash-isolation attribution
        now = _faults.now()
        u = self.usage
        if req.t_admitted is None:
            # first admission only — a preempted/requeued request
            # keeps its original marks (queue-wait and TTFT measure
            # the user-visible wait, and the on_token wrapper is
            # already installed)
            req.t_admitted = now
            arrival = getattr(req, "arrival_time", now)
            _stats.observe("serve.queue_wait_ms",
                           (now - arrival) * 1e3)
            _stats.inc("serving.admitted")
            if u is not None:
                u.note_queue(req, now - arrival)
            self._hook_first_token(req)
        toks = self._admit_tokens(req)
        shared = []
        if self.prefix_cache is not None:
            shared = self.prefix_cache.match(toks)
            if shared:
                _stats.inc("serving.prefix_hit")
                _stats.inc("serving.prefix_pages_saved", len(shared))
            else:
                _stats.inc("serving.prefix_miss")
        jr = self.journal
        if jr is not None:
            jr.record("admitted", req.id, i,
                      {"prefix_pages": len(shared),
                       "resume": getattr(req, "_resume_tokens", None)
                       is not None})
        key = ("prefill", i)
        if shared:
            self._mgr.share(key, shared)
            if u is not None:
                # shared pages charge EACH holder from its own map
                # time — the sharer starts paying page-seconds now —
                # and the pages it did NOT have to prefill are a
                # credit (the prefix-cache's own refs charge nobody)
                u.credit_prefix(req, len(shared))
                u.set_pages(req, len(shared), now=now)
        if self._mgr.slot_state == SLOT_RECURRENT:
            # the slot's recurrent state is dead from here: this
            # sequence's first chunk starts from zeros, also when it is
            # a preempted request coming back for recompute
            self._mgr.recurrent_admit(i)
            _stats.inc("serving.recurrent.resets")
        self._prefilling[i] = _Prefill(
            req, pos=len(shared) * self.page_size, tokens=toks)
        self._admitting = None

    def _hook_first_token(self, req):
        """Wrap the user's on_token with the TTFT stamp (fires exactly
        once, on the first emitted token)."""
        user_cb = getattr(req, "on_token", None)

        def cb(r, t, _u=user_cb):
            if getattr(r, "t_first_token", None) is None:
                r.t_first_token = _faults.now()
                ttft_ms = (r.t_first_token
                           - getattr(r, "arrival_time",
                                     r.t_first_token)) * 1e3
                _stats.observe("serve.ttft_ms", ttft_ms)
                jr = self.journal
                if jr is not None:
                    jr.record("first_token", r.id, -1,
                              {"ttft_ms": round(ttft_ms, 3)})
            if _u is not None:
                _u(r, t)

        req.on_token = cb

    # ---------------- scheduling ----------------

    def _pick_action(self) -> str:
        """Prefill vs decode for this step: the weighted interleave
        cycle, active only under CONTENTION (both phases have work).
        The cycle restarts whenever contention (re)starts, so while any
        request is decode-ready at most ``prefill_burst`` consecutive
        prefill chunks ever run — the stall bound."""
        if not self._prefilling:
            self._cycle_pos = 0
            return "decode"
        if self.num_active == 0:
            self._cycle_pos = 0
            return "prefill"
        cycle = self.slo.prefill_burst + self.slo.decode_burst
        pos = self._cycle_pos % cycle
        self._cycle_pos += 1
        return "prefill" if pos < self.slo.prefill_burst else "decode"

    def _chunk_size(self, remaining: int) -> int:
        """Chunk length for ``remaining`` prompt tokens: full chunks
        while they last, the tail bucket-padded (one compiled program
        per SIZE — prompt_bucket bounds the tail-program count)."""
        if remaining >= self.slo.prefill_chunk:
            return self.slo.prefill_chunk
        bs = self.prompt_bucket
        return max(min(-(-remaining // bs) * bs,
                       self.slo.prefill_chunk), 1)

    def _chunk_floor(self) -> int:
        """Smallest chunk graceful degradation may shrink to: one
        page/bucket of tokens (whichever is smaller — shrunk sizes
        stay multiples of it, bounding the per-size compile count to
        the halving chain)."""
        return max(1, min(self.prompt_bucket, self.page_size))

    def _shrunk_chunk(self, c: int) -> int:
        """Next smaller chunk size in the degradation chain: half of
        ``c``, rounded up to the floor's multiple, strictly below
        ``c``."""
        floor = self._chunk_floor()
        nxt = -(-(c // 2) // floor) * floor
        return max(min(nxt, c - 1), floor)

    def _postprocess_tokens(self, toks_np, active):
        """Serving override of the decode-chunk token filter (ISSUE
        11): route the chunk's token matrix through any scheduled
        ``decode.step`` corruption, then validate the whole ACTIVE
        block before a single request mutates — a detected corruption
        raises :class:`TokenCorruption` while the crash-isolated retry
        is still clean (re-running the chunk rewrites the same KV
        pages with identical values)."""
        f = self._faults
        if f is not None and active:
            i0 = active[0]
            cur = int(toks_np[i0, 0])
            poked = f.corrupt("decode.step", cur)
            if poked != cur:
                # np.asarray over a jax array is a read-only view —
                # corrupt a writable copy (the fault path only)
                toks_np = np.array(toks_np)
                toks_np[i0, 0] = poked
        v = self.model.vocab_size
        blk = toks_np[active]
        if blk.size and (int(blk.min()) < 0 or int(blk.max()) >= v):
            raise TokenCorruption(
                f"decode chunk produced token(s) outside [0, {v}) "
                f"for slots {active}")
        return toks_np

    def _urgency(self, req):
        """Sort key: most urgent first (priority, then admission order
        — finish what started first)."""
        return (-getattr(req, "priority", 0), req.t_admitted)

    def _pick_prefilling(self) -> int:
        """Most urgent prefilling slot: priority, then admission
        order (finish what started first — chunks of one prompt don't
        interleave with another's without cause)."""
        return min(self._prefilling,
                   key=lambda i: self._urgency(self._prefilling[i].req))

    def _chunk_rung(self, c: int, adaptered: bool = False) -> str:
        """Rung name of the c-token chunk program —
        ``serve.prefill[c=N,mp=M]`` under tensor parallelism; the
        multi-LoRA variant reports as ``serve.prefill.lora[...]``."""
        tp = self._gen._tp
        mp = f",mp={tp.mp}" if tp is not None else ""
        tag = "serve.prefill.lora" if adaptered else "serve.prefill"
        return f"{tag}[c={c}{mp}]"

    def _get_chunk_prefill(self, c: int, adaptered: bool = False):
        """One compiled chunk program per (chunk SIZE, adaptered):
        start/len are traced operands — every chunk of every request
        shares it — and the adapter operands (slot map + banks) are
        traced too, so adapter membership and hot load/unload never
        add programs (at most 2 per chunk size)."""
        key = (c, adaptered)
        if key not in self._chunk_jit:
            rung = self._chunk_rung(c, adaptered)
            if self._pattern_built:
                # a pattern-built model brings its own chunk program
                # (the recurrent state rides with the pool)
                prog = self._gen._get_chunk_prefill(rung)
            else:
                import jax

                prog = _roofline.AotProgram(
                    rung, jax.jit(self._chunk_prefill_fn,
                                  donate_argnums=(8, 9)))
            self._chunk_jit[key] = prog
        return self._chunk_jit[key]

    def _chunk_prefill_fn(self, weights, embed, head_t, lnf_s, lnf_b,
                          ids, start, chunk_len, ck, cv, tables,
                          adapter_slots=None, adapter_banks=None):
        """Compiled chunk program: prefill ``ids`` at positions
        ``start..`` against the cached prefix + in-chunk causal
        triangle, returning the last VALID position's logits (read
        only on the prompt's final chunk). With adapter
        operands set, every projection adds its ragged grouped LoRA
        delta (one launch per projection per layer)."""
        g = self._gen
        st = self.model.stack
        adapters = None
        if adapter_banks is not None:
            adapters = dict(adapter_banks)
            adapters["slots"] = adapter_slots
        x = embed[ids].astype(g._cdtype)
        h, cache = st.prefill_chunk_raw(
            weights, x, PagedKV(ck, cv), tables, start, chunk_len,
            g._cos, g._sin, a8w8=g._a8w8, tp=g._tp, adapters=adapters)
        hl = h[jnp.arange(h.shape[0]), chunk_len - 1]
        logits = g._logits(hl, head_t, lnf_s, lnf_b)
        return logits, cache.k, cache.v

    def _prefill_step(self):
        """Run ONE prefill chunk for the most urgent prefilling slot;
        on prompt completion the request joins the decode batch with
        its first token emitted. A chunk that is not its prompt's last
        is not waited for: nothing reads its token, so its result is
        kept (``_deferred``) and the next program's dispatch waits for
        it, while the host admits, plans and dispatches that program
        with the device still busy. Returns requests finished this step
        (a one-token request can finish straight out of prefill)."""
        with RecordEvent("serve.plan"):
            plan = self._plan_prefill()
        if plan is None:
            return []
        i, stt, c, n, program, lead, tail = plan
        last = self._last_chunk(stt, n)
        tok = None
        t_run0 = self._now()
        with RecordEvent("serve.run", program=program.name,
                         rid=stt.req.id):
            t0 = time.perf_counter()
            out = self._run_program(program, lead, tail)
            # the wait for the chunk deferred before this one may have
            # sent this request back to the queue or failed it
            alive = self._prefilling.get(i) is stt
            if alive and not last:
                self._deferred = (stt.req, i, out)
                _stats.inc("serving.prefill.deferred")
            elif alive and not self._pattern_built:
                tok = int(np.asarray(
                    self._gen._argmax(jnp.asarray(out)))[0])
            elif alive:
                # a pattern-built model's chunk program picks the token
                # itself and returns it with its pick counts
                tok = int(self._fetch(out)[0])
        self._run_ts = (t_run0, self._now())
        if not alive:
            self._prefill_active = None
            return []
        if last:
            # the fetch synced the chunk — honest phase roofline
            _roofline.analyze(program.name, time.perf_counter() - t0)
        with RecordEvent("serve.emit"):
            return self._emit_prefill(i, stt, c, n, tok)

    def _last_chunk(self, stt, n: int) -> bool:
        """Whether a chunk of ``n`` real tokens completes ``stt``'s
        prompt: only that chunk's token is ever read."""
        return stt.pos + n >= len(stt.tokens)

    def _run_program(self, program, lead, tail):
        """Dispatch, then wait for the chunk deferred before this
        program: the device goes from that one straight to this one."""
        out = super()._run_program(program, lead, tail)
        self._settle_deferred()
        return out

    def _settle_deferred(self, slot: Optional[int] = None):
        """Wait for the deferred prefill chunk (only where it is
        ``slot``'s, if given): its pick counts reach the counters, and
        a fault the wait raises is charged to the chunk's own request.
        A slot that leaves prefill settles its own chunk first; a fault
        there is moot, as the caller decides the request's fate."""
        d = self._deferred
        if d is None or slot not in (None, d[1]):
            return
        self._deferred = None
        req, i, out = d
        try:
            fi = self.faults
            if fi is not None:
                fi.fire("prefill.wait", rid=req.id)
            self._settle(out)
        except Exception as e:
            if slot is None:
                self._recover_deferred(e, req, i)
            return
        req.n_retries = 0  # chunk landed — budget restored

    def _plan_prefill(self):
        """The host's work before a prefill chunk's program call: pick
        the slot, size the chunk, grow its pages (evicting, shrinking
        the chunk or requeueing others under pool pressure), build the
        operands. Returns (slot, its state, chunk size, real tokens,
        the program, operands before the pool, operands after it), or
        None where the chunk is deferred to a later step."""
        i = self._pick_prefilling()
        stt = self._prefilling[i]
        req = stt.req
        self._prefill_active = (req, i)  # crash-isolation attribution
        toks = stt.tokens
        L = len(toks)
        c = self._chunk_size(L - stt.pos)
        n = min(L - stt.pos, c)
        key = ("prefill", i)
        need = min(self._mgr.pages_needed(stt.pos + c),
                   self._pages_per_seq)
        have = len(self._mgr._owned.get(key, ()))
        if need > have and not self._evict_for(need - have):
            # graceful degradation FIRST (ISSUE 11): shrink this
            # step's chunk until its tail pages fit the squeezed pool
            # — smaller chunks keep tokens flowing where the full
            # chunk would stall, requeue, or shed
            if _flag("serve_chunk_shrink"):
                c2 = c
                while c2 > self._chunk_floor():
                    c2 = self._shrunk_chunk(c2)
                    need2 = min(self._mgr.pages_needed(stt.pos + c2),
                                self._pages_per_seq)
                    if need2 <= have \
                            or self._evict_for(need2 - have):
                        _stats.inc("serving.chunk_shrinks")
                        c, n = c2, min(L - stt.pos, c2)
                        need = need2
                        break
        if need > have and not self._evict_for(need - have):
            # pool exhausted even after dropping every cold cached
            # prefix (admission only reserved the FIRST chunk's pages,
            # so later chunks can outgrow the pool under load)
            if self.num_active > 0:
                # decoders hold the pages and free them as they
                # finish — defer this chunk, the interleave cycle
                # keeps decode draining meanwhile
                _stats.inc("serving.prefill_stalls")
                jr = self._journal
                if jr is not None:
                    jr.record("stall", req.id, i,
                              {"need": need - have})
                return None
            # no decoders to wait for: requeue LESS-urgent prefilling
            # requests (never this one — ``i`` is the most urgent, and
            # sacrificing it would livelock: it re-admits first and
            # starves the survivor all over again) until this chunk's
            # pages fit
            while len(self._prefilling) > 1 \
                    and not self._evict_for(need - have):
                victim = max(
                    (j for j in self._prefilling if j != i),
                    key=lambda j: self._urgency(
                        self._prefilling[j].req))
                self._requeue_prefill(victim)
            if not self._evict_for(need - have):
                raise PoolSizingError(
                    f"request {req.id} needs {need} KV pages but the "
                    f"pool can only ever provide "
                    f"{self._mgr.free_pages + have} "
                    f"(num_pages={self._mgr.num_pages}); increase "
                    f"num_pages or cap prompt/generation length")
        if need > have:
            self._mgr.grow(key, need - have)
            u = self.usage
            if u is not None:
                u.set_pages(req, len(self._mgr._owned[key]))
        fi = self.faults
        if fi is not None:
            fi.fire("prefill.dispatch", rid=req.id)
        tables = self._mgr.block_tables([key], self._pages_per_seq)
        ids = np.zeros((1, c), np.int32)
        ids[0, :n] = toks[stt.pos: stt.pos + n]
        self._gen._count_a8w8(1)
        lnf_s, lnf_b = self._gen._lnf()
        a_slot = getattr(req, "_adapter_slot", None)
        adaptered = self.adapters is not None and a_slot is not None \
            and a_slot >= 0
        extra = ()
        if adaptered:
            extra = (jnp.asarray([a_slot], jnp.int32),
                     self.adapters.operands(tp=self._gen._tp))
            _stats.inc("lora.grouped_launches",
                       4 * self.model.stack.num_layers)
        if self._pattern_built:
            # which slot's state the chunk continues, and whether it
            # starts from zeros (the first chunk after an admission)
            fresh = self._mgr.recurrent_is_fresh(i)
            if self._mgr.slot_state == SLOT_RECURRENT and not fresh:
                _stats.inc("serving.recurrent.resumed_chunks")
            extra = (jnp.asarray([i], jnp.int32), jnp.asarray([fresh]))
        if self._latent:
            # causal query-key pairs of this chunk, a layer: real row r
            # attends the stt.pos + r + 1 positions up to its own
            layers = self._mgr.num_layers
            _stats.inc("serving.mla.prefill_pairs",
                       (n * stt.pos + n * (n + 1) // 2) * layers)
            # (key row, head) expansions of the chunk's kernel: every
            # head expands the whole tiles up to the last real row once
            _stats.inc("serving.mla.prefill_rows_expanded",
                       prefill_keys_expanded(stt.pos, n, self.page_size,
                                             self._pages_per_seq)
                       * self.model.stack.pattern.latent.num_heads * layers)
        if self._window:
            # query-key pairs of this chunk's real rows, a layer: causal
            # in the full layers, at most ``window`` a row in the others
            pat = self.model.stack.pattern
            _stats.inc("serving.attn.prefill_pairs",
                       (n * stt.pos + n * (n + 1) // 2) * pat.n_attention)
            _stats.inc("serving.window.prefill_pairs",
                       window_pairs(stt.pos, n, pat.window_attention.window)
                       * pat.n_window)
        lead = (self._gen._weights(), self._gen._embed(),
                self._gen._head_t, lnf_s, lnf_b, jnp.asarray(ids),
                jnp.asarray([stt.pos], jnp.int32),
                jnp.asarray([n], jnp.int32))
        return (i, stt, c, n, self._get_chunk_prefill(c, adaptered), lead,
                (tables, *extra))

    def _emit_prefill(self, i, stt, c, n, tok):
        """The chunk's token -> its request: corruption check, journal,
        and on prompt completion the handoff to the decode batch with
        the first token emitted (``on_token``, finish hook, page
        release for a one-token request). ``tok`` is None for a chunk
        that was not waited for (not its prompt's last). Returns the
        requests that finished."""
        req, toks, L = stt.req, stt.tokens, len(stt.tokens)
        key = ("prefill", i)
        if tok is not None:
            fi = self.faults
            if fi is not None:
                tok = fi.corrupt("prefill.dispatch", tok)
            if not 0 <= tok < self.model.vocab_size:
                # corrupt-and-DETECT: the poisoned token never reaches
                # the request's stream; the raise happens before any
                # host-side mutation, so the crash-isolated retry
                # re-runs this chunk cleanly (same KV pages rewritten
                # with identical values)
                raise TokenCorruption(
                    f"prefill chunk for request {req.id} produced token "
                    f"{tok} outside [0, {self.model.vocab_size})")
        _stats.inc("serve.prefill_chunks")
        _stats.inc("serve.prefill_tokens", n)
        self._mgr.recurrent_landed(i)
        u = self.usage
        if u is not None:
            u.add_tokens(req, prefill=n)
        stt.pos += n
        jr = self._journal
        if jr is not None:
            jr.record("prefill_chunk", req.id, i,
                      {"c": c, "pos": stt.pos, "n": n})
        if stt.pos < L:
            return []
        # prompt complete: emit the next token, join the decode batch
        del self._prefilling[i]
        self._mgr.rekey(key, ("slot", i))
        if self.prefix_cache is not None:
            try:
                self.prefix_cache.insert(
                    toks, self._mgr._owned[("slot", i)])
            except Exception:
                # a prefix-cache registration failure (e.g. an
                # injected prefix.insert fault) costs future page
                # reuse, never the request — absorbed here, counted,
                # and the request proceeds to decode untouched
                _stats.inc("serving.prefix_insert_errors")
        self._slots[i] = req
        req.generated.append(tok)
        if u is not None:
            # the final chunk's logits emitted the stream's first
            # token — a generated (decode-side) token in the ledger
            u.add_tokens(req, decode=1)
        cb = getattr(req, "on_token", None)
        if cb is not None:
            cb(req, tok)
        if (req.eos_token_id is not None and tok == req.eos_token_id) \
                or len(req.generated) >= req.max_new_tokens:
            req.done = True
            self._finish_hook(req, i)
            self._release(i)
            self.finished.append(req)
            return [req]
        if jr is not None:
            jr.record("decode", req.id, i, None)
        self._lens[i] = L + 1
        self._last_tok[i] = tok
        return []

    # ---------------- pool-pressure recovery ----------------

    def _requeue_prefill(self, i: int):
        """Abort slot ``i``'s chunk prefill back to the waiting list,
        freeing its pages (its _resume_tokens, if any, survive so a
        preempted request still resumes mid-stream). Progress is kept
        by the surviving prefilling slots, which can now grow."""
        self._settle_deferred(i)
        stt = self._prefilling.pop(i)
        self._mgr.free(("prefill", i))
        self._mgr.recurrent_free(i)
        _stats.inc("serving.prefill_requeues")
        req = stt.req
        req.n_requeues = getattr(req, "n_requeues", 0) + 1
        u = self.usage
        if u is not None:
            u.set_pages(req, 0)
            u.add_event(req, requeue=1)
        jr = self.journal
        if jr is not None:
            jr.record("requeue", req.id, i, {"pos": int(stt.pos)})
        self.waiting.append(req)
        self._sort_waiting()
        if jr is not None:
            jr.record("queued", req.id, -1, None)
        return []

    def _preempt_slot(self, j: int):
        """Preempt decode slot ``j`` by recomputation (vLLM-style):
        free its pages and requeue the request with prompt+generated
        as its resume stream — re-admission chunk-prefills the whole
        history (usually prefix-cache-hot) and the final chunk emits
        the NEXT token, so the user-visible stream just continues."""
        req = self._slots[j]
        req._resume_tokens = np.concatenate(
            [req.prompt, np.asarray(req.generated, np.int32)])
        self._park_preempted_kv(j, req._resume_tokens)
        self._release(j)   # the override closes the page integral
        _stats.inc("serving.preemptions")
        req.n_preempts = getattr(req, "n_preempts", 0) + 1
        u = self.usage
        if u is not None:
            u.add_event(req, preempt=1)
        jr = self.journal
        if jr is not None:
            jr.record("preempt", req.id, j,
                      {"n_generated": len(req.generated)})
        self.waiting.append(req)
        self._sort_waiting()
        if jr is not None:
            jr.record("queued", req.id, -1, None)

    def _park_preempted_kv(self, j: int, resume_toks) -> None:
        """Keep a preempted slot's COMPLETE KV pages reachable instead
        of dropping them (ISSUE 20): register them in the prefix cache
        under the resume stream's content chain before the release.
        Under continued pressure they are exactly the coldest entries
        ``_evict_for`` evicts next — which, with a host tier, demotes
        them to host DRAM — so re-admission restores pages and
        re-prefills only the tail, and full recompute becomes the last
        resort. Only positions strictly below ``lens-1`` are certainly
        written between steps, hence the (lens-1)//page_size bound."""
        if self.prefix_cache is None:
            return
        n_full = min((int(self._lens[j]) - 1) // self.page_size,
                     len(resume_toks) // self.page_size)
        if n_full <= 0:
            return
        pages = self._mgr._owned.get(("slot", j), [])[:n_full]
        if not pages:
            return
        try:
            self.prefix_cache.insert(resume_toks, pages)
        except Exception:
            # registration is an optimization; an injected
            # prefix.insert fault must never break the preemption
            _stats.inc("serving.prefix_insert_errors")

    def _grow_decode_slot(self, i: int, n_pages: int) -> bool:
        """Serving override of the decode-time grow: under pool
        pressure evict cold cached prefixes first; if the pool is
        STILL exhausted, preempt the LEAST-urgent active slot (freeing
        its pages may also unpin cached prefixes, so re-evict each
        round) until slot ``i`` fits or is itself the victim."""
        while not self._evict_for(n_pages):
            victim = max(
                (j for j in range(self.max_batch)
                 if self._slots[j] is not None),
                key=lambda j: self._urgency(self._slots[j]))
            self._preempt_slot(victim)
            if victim == i:
                return False
        self._mgr.grow(("slot", i), n_pages)
        u = self.usage
        if u is not None and self._slots[i] is not None:
            u.set_pages(self._slots[i],
                        len(self._mgr._owned[("slot", i)]))
        return True
