"""Global flags registry.

TPU-native equivalent of the reference's gflags-compatible flag system
(reference: paddle/phi/core/flags.cc — 120 PHI_DEFINE_EXPORTED_* flags,
macro at flags.h:145, settable by env ``FLAGS_*`` or ``paddle.set_flags``).

We keep the same surface: flags declared once with a default + doc, env
``FLAGS_<name>`` overrides the default at first read, and ``set_flags`` /
``get_flags`` mutate/inspect at runtime.

Every flag is ALSO settable via ``PADDLE_TPU_<NAME>`` (upper-cased) —
the deployment convention the PR 5 compile-cache flag established,
generalized to the whole registry. ``FLAGS_<name>`` wins when both are
set (reference parity). The README flags table lists both forms per
flag; ``tools/tpu_lint.py`` (flags pass) asserts the table stays
complete.
"""
from __future__ import annotations

import os
from typing import Any, Dict

__all__ = ["define_flag", "set_flags", "get_flags", "flag", "env_var_for"]

_FLAGS: Dict[str, dict] = {}


def _coerce(value, proto):
    if isinstance(proto, bool):
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    if isinstance(proto, int):
        return int(value)
    if isinstance(proto, float):
        return float(value)
    return value


def env_var_for(name: str) -> str:
    """The deployment-convention env override for a flag name."""
    return "PADDLE_TPU_" + name.upper()


def define_flag(name: str, default: Any, doc: str = "") -> None:
    if name in _FLAGS:
        return
    env = os.environ.get(f"FLAGS_{name}")
    if env is None:
        env = os.environ.get(env_var_for(name))
    value = _coerce(env, default) if env is not None else default
    _FLAGS[name] = {"default": default, "value": value, "doc": doc}


def set_flags(flags: Dict[str, Any]) -> None:
    """Mirror of ``paddle.set_flags`` (python/paddle/base/framework.py:64)."""
    for name, value in flags.items():
        key = name[len("FLAGS_"):] if name.startswith("FLAGS_") else name
        if key not in _FLAGS:
            raise ValueError(f"unknown flag {name!r}")
        _FLAGS[key]["value"] = _coerce(value, _FLAGS[key]["default"])


def get_flags(flags) -> Dict[str, Any]:
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for name in flags:
        key = name[len("FLAGS_"):] if name.startswith("FLAGS_") else name
        if key not in _FLAGS:
            raise ValueError(f"unknown flag {name!r}")
        out[name] = _FLAGS[key]["value"]
    return out


def flag(name: str):
    """Fast internal read."""
    return _FLAGS[name]["value"]


# ---- core flags (subset of reference's paddle/phi/core/flags.cc) ----
define_flag("check_nan_inf", False, "scan op outputs for NaN/Inf in eager mode")
define_flag("check_nan_inf_level", 0, "0: error on nan/inf; >0: report stats only")
define_flag("record_double_grad", True,
            "record primal recipes on the tape for paddle.grad(create_graph=True); disable to save memory in first-order-only runs")
define_flag("benchmark", False, "synchronize after each op for timing")
define_flag("attn_varlen_backend", "auto",
            "flash_attn_unpadded varlen flash-attention backend "
            "(nn/functional/flash_varlen.py): auto (segment-aware "
            "block-skipping Pallas kernel on TPU, the math-identical "
            "tiled XLA walk elsewhere) | pallas | interpret (the "
            "Pallas kernel through the interpreter — debug) | xla | "
            "dense (the legacy O(T^2) masked-dense path, reference "
            "only)")
define_flag("prefill_attention_backend", "auto",
            "chunked-prefill / speculative-verify attention over the "
            "paged pool (nn/functional/flash_varlen.py "
            "paged_prefill_attention): auto (block-table-indexed "
            "varlen kernel on TPU reading pages in place, tiled XLA "
            "walk elsewhere) | varlen (force the tiled walk family) | "
            "gather (legacy dense gather_kv_pages copy per chunk — "
            "also the forced path for int8-quantized pools)")
define_flag("moe_grouped_backend", "auto",
            "no-drop MoE ragged grouped-GEMM backend "
            "(nn/functional/grouped_gemm.py): auto (Pallas kernel on "
            "TPU, the math-identical tiled XLA walk elsewhere) | "
            "pallas | interpret (the kernel through the Pallas "
            "interpreter — debug/parity) | xla")
define_flag("check_donation", False,
            "use-after-donate poison mode (paddle_tpu.analysis.donation): "
            "buffers donated by the compiled-forward fast path are "
            "registered as poisoned after dispatch, and every subsequent "
            "dispatch / Tensor.numpy() asserts none of its inputs is one "
            "— CPU runs then fail exactly where TPU donation would read "
            "freed HBM, instead of silently passing (CPU jaxlib ignores "
            "donation)")
define_flag("serve_journal", True,
            "request-lifecycle flight recorder for the serving "
            "frontend (serving/journal.py): every lifecycle "
            "transition (submit/queued/admitted/prefill_chunk/"
            "first_token/decode/preempt/requeue/stall/evict_trigger/"
            "finish/error) lands in a bounded in-memory ring, dumped "
            "as a JSONL artifact on any run() exception; off = the "
            "scheduler holds no recorder and every hook is a single "
            "attribute test (zero journal allocations)")
define_flag("serve_journal_events", 4096,
            "flight-recorder ring capacity in events; older events "
            "are overwritten once the ring wraps (the journal.dropped "
            "gauge counts them)")
define_flag("serve_journal_dir", "",
            "directory for serving crash-dump artifacts "
            "(serve_crash_rank<r>_pid<pid>.jsonl, written by "
            "ServingEngine.run() on any raise; read back with "
            "tools/serve_top.py); empty = the system temp dir")
define_flag("serve_step_retries", 2,
            "crash-isolated stepping (serving/scheduler.py): retries "
            "granted to one request's prefill chunk / one decode "
            "chunk after an exception, each with capped exponential "
            "backoff, before the OFFENDING request alone errors out "
            "(state='error') while the serve loop keeps going")
define_flag("serve_retry_backoff_ms", 5.0,
            "base backoff between crash-isolated step retries; "
            "attempt k sleeps min(base * 2^(k-1), "
            "serve_retry_backoff_cap_ms) through the injectable "
            "serving clock (serving/faults.py — a ManualClock makes "
            "backoff a pure time-warp in tests)")
define_flag("serve_retry_backoff_cap_ms", 500.0,
            "cap on the exponential step-retry backoff")
define_flag("serve_watchdog_steps", 256,
            "progress watchdog: a request whose token progress "
            "(prefill position / generated count) hasn't moved for "
            "this many scheduler steps is preempted/requeued once, "
            "then failed on a second trip — the serve loop never "
            "hangs behind a wedged slot; 0 disables")
define_flag("serve_inbox_limit", 4096,
            "hard bound on the ServingEngine submit inbox; a full "
            "inbox rejects submit() with the typed ServerOverloaded "
            "(backpressure to the producer thread); 0 = unbounded")
define_flag("serve_shed_queue_depth", 0,
            "overload shedding: queue depth (inbox + waiting) at "
            "which admission rejects with ServerOverloaded and "
            "_drain_inbox sheds the sorted queue's overflow tail "
            "(lowest priority, newest first) into the 'shed' "
            "terminal state; 0 disables")
define_flag("serve_shed_burn_rate", 0.0,
            "overload shedding on service health: reject submits "
            "with ServerOverloaded while the rolling SLO burn-rate "
            "gauge (serving/slo.py) exceeds this; 0 disables")
define_flag("spec_k", 4,
            "speculative decoding window (inference/speculative.py): "
            "draft tokens proposed per verify round when the engines "
            "run with speculative= and no explicit spec_k; the verify "
            "pass scores k+1 tokens in ONE streamed program, so the "
            "weight stack is read once per accepted window instead of "
            "once per token")
define_flag("spec_drafter", "self",
            "default drafter for speculative=True: self (Medusa-style "
            "training-free self-drafting heads off the target's "
            "hidden state — zero extra weights to stream) | draft "
            "(requires an explicit FusedCausalLM draft model / "
            "DraftModelDrafter passed as speculative=, which keeps "
            "its own tiny non-paged KV state)")
define_flag("fleet_heartbeat_ms", 50.0,
            "fleet replica heartbeat interval (serving/router.py): "
            "each replica's serve loop stamps a beat through the "
            "injectable serving clock once per iteration; the "
            "router's health checker measures missed beats against "
            "this interval to walk a silent replica through the "
            "suspect -> dead state machine")
define_flag("fleet_suspect_beats", 3,
            "missed heartbeats before a fleet replica is marked "
            "SUSPECT (its queued-but-unadmitted requests hedge to a "
            "healthy peer); twice this many marks it DEAD and every "
            "in-flight request fails over via the recompute resume "
            "path")
define_flag("fleet_breaker_threshold", 3,
            "per-replica circuit breaker (serving/router.py): "
            "consecutive dispatch errors against one replica before "
            "its breaker opens and the router stops routing to it; a "
            "half-open probe re-admits it after the cooldown")
define_flag("fleet_dispatch_queue", 4096,
            "router-tier overload bound: fleet-wide queued-but-not-"
            "yet-admitted requests (every replica's inbox + waiting "
            "list) past this shed new submits with the typed "
            "FleetOverloaded BEFORE any replica admits; 0 = unbounded")
define_flag("tp_overlap", "psum",
            "row-parallel TP reduction schedule "
            "(nn/functional/stream_linear.py reduce_axis= seam, "
            "distributed/tp.py reduce_over_axis): psum (one blocking "
            "all-reduce per projection pair — the bitwise/census "
            "reference) | ring (the partial splits into mp column "
            "chunks and each chunk all-reduces via mp-1 ppermute "
            "steps pipelined under the next chunk's GEMM — "
            "mp*(mp-1) collective-permutes per reduction, none "
            "blocking the weight stream)")
define_flag("ep_overlap", False,
            "double-buffer the MoE expert-parallel exchange "
            "(nn/functional/grouped_gemm.py moe_ffn_ep): the "
            "dispatched capacity splits into two half buffers so "
            "expert compute on buffer 0 overlaps buffer 1's dispatch "
            "all_to_all and buffer 0's combine overlaps buffer 1's "
            "compute — census becomes 4 all_to_alls + 1 all_gather "
            "per MoE layer (off = the serialized "
            "dispatch/compute/combine triple, the census reference)")
define_flag("migrate_async", False,
            "asynchronous KV-page migration on a fleet drain "
            "(serving/router.py): COMPLETE pages stream to the "
            "destination in page-granular batches while BOTH "
            "endpoints keep taking decode steps (append-only pool "
            "writes never touch a completed page), and only the "
            "tail pages + slot metadata copy under the step locks "
            "at re-home; off = the whole export/import runs under "
            "the locks (the zero-loss reference path)")
define_flag("kv_host_tier_bytes", 0,
            "host-DRAM KV tier capacity per engine in bytes "
            "(serving/host_tier.py): cold PrefixCache chains and "
            "preempted-slot pages spill to host buffers instead of "
            "being evicted/recomputed, and re-admissions restore "
            "them back into free pool pages (int8-KV pools spill "
            "quantized rows + scale columns, so traffic roughly "
            "halves); 0 disables the tier and eviction releases "
            "pages outright")
define_flag("kv_restore_gbps", 10.0,
            "assumed host->HBM restore bandwidth (GB/s) for the "
            "router prefix-directory cost model "
            "(serving/router.py): a host-tier directory entry is "
            "worth PULLING when pages*page_bytes/bandwidth beats "
            "re-prefilling the covered tokens at "
            "FLAGS_disagg_prefill_tflops")
define_flag("disagg_prefill_tflops", 100.0,
            "assumed chunk-prefill throughput (TFLOP/s) for the "
            "directory cost model's re-prefill arm; lower it on "
            "hosts where prefill is slow (CPU rungs) so long "
            "host-tier prefixes pull instead of recompute")
define_flag("disagg", "",
            "fleet role split (serving/router.py FleetRouter): "
            "'' = symmetric replicas; 'auto' = half the fleet "
            "(>=1) becomes prefill-heavy and the rest decode-heavy; "
            "'P:D' pins the split explicitly. Prefill replicas take "
            "new admissions with prefill-weighted SLO interleave "
            "and hand finished-prefill slots to decode replicas "
            "over the export/import migration path (async when "
            "FLAGS_migrate_async), so decode TPOT never pays "
            "prefill stalls")
define_flag("lora_delta_backend", "auto",
            "batched multi-LoRA ragged delta-GEMM backend "
            "(nn/functional/lora.py lora_delta): auto (Pallas kernel "
            "on TPU, the math-identical tiled XLA walk elsewhere) | "
            "pallas | interpret (the kernel through the Pallas "
            "interpreter — debug/parity) | xla")
define_flag("tenant_quota_rps", 0.0,
            "router-tier per-tenant request rate limit "
            "(serving/router.py): submits from one tenant past this "
            "many requests per second (measured over "
            "FLAGS_tenant_quota_window_s on the injectable serving "
            "clock) shed with the typed TenantQuotaExceeded before "
            "any replica admits; 0 disables")
define_flag("tenant_quota_tokens", 0,
            "router-tier per-tenant token quota (serving/router.py): "
            "tokens billed to one tenant by the usage ledger "
            "(prefill + decode, FLAGS_usage_ledger must be on) "
            "within the rolling FLAGS_tenant_quota_window_s window "
            "past this shed the tenant's new submits with "
            "TenantQuotaExceeded; 0 disables")
define_flag("tenant_quota_window_s", 1.0,
            "rolling window (serving-clock seconds) both tenant "
            "quota legs measure against: the rate limiter keeps a "
            "per-tenant arrival deque pruned to this window and the "
            "token quota re-baselines each tenant's ledger token "
            "count once the window elapses")
define_flag("usage_ledger", False,
            "per-request -> per-tenant usage metering "
            "(serving/accounting.py UsageLedger): partitions every "
            "serve.step work phase across the requests it served and "
            "integrates KV page-seconds per request; off = the "
            "engine holds usage=None and every hook is one attribute "
            "test (zero per-step allocations)")
define_flag("usage_tenants_max", 64,
            "cardinality bound on per-tenant SLO goodput windows "
            "(serving/slo.py): tenants past this roll into the "
            "__other__ window instead of growing state unboundedly")
define_flag("usage_top_k", 4,
            "tenant gauges exported per telemetry tick "
            "(tenant.top<i>.device_ms, index-keyed): the bounded "
            "top-K slice of the ledger's per-tenant device time")
define_flag("telemetry_interval_ms", 0.0,
            "continuous time-series sampler "
            "(profiler/timeseries.py): default background sampling "
            "interval for TimeSeriesSampler.start() — every interval "
            "the sampler folds the stats registry (counters -> delta "
            "rates, gauges -> levels, histograms -> count/total) into "
            "bounded per-metric ring windows; 0 disables the default "
            "sampler (explicit tick() still works in tests)")
define_flag("telemetry_window", 512,
            "time-series retention: points kept per metric ring "
            "(profiler/timeseries.py) — fixed memory however long the "
            "serve runs; window aggregates (min/mean/max/p99) and "
            "serve_top --history sparklines read this window")
define_flag("telemetry_port", 0,
            "Prometheus text-format scrape endpoint "
            "(profiler/timeseries.py start_http_server): a stdlib "
            "http.server thread serves the stats registry as "
            "/metrics (counters *_total, histogram cumulative "
            "*_bucket) on this port; FleetRouter.start_telemetry "
            "serves the fleet-aggregated per-replica series (sum "
            "counters, max gauges) the same way; 0 = no exporter")
define_flag("serve_chunk_shrink", True,
            "graceful degradation under pool pressure: before a "
            "prefill chunk stalls/requeues for pages, shrink it "
            "(halving, page/bucket-aligned) until its tail pages fit "
            "the squeezed pool — tokens keep flowing at reduced "
            "chunk size instead of the request parking")
define_flag("use_bf16_matmul", True, "prefer bfloat16 matmul accumulation on the MXU")
define_flag("eager_fwd_cache", True,
            "no-grad eager dispatch through the signature-keyed "
            "compiled-forward cache (ops/dispatch.py); disable to force "
            "primitive-by-primitive eager execution")
define_flag("optimizer_donate_grads", False,
            "donate gradient buffers to the optimizer's fused update; "
            "grads are consumed by step() (p.grad is cleared), halving "
            "the step's transient gradient footprint")
define_flag("eager_jit_ops", True, "dispatch eager ops through cached jit computations")
define_flag("stop_check_timeout", 900, "bound (seconds) on distributed store waits")
define_flag("allocator_strategy", "auto_growth", "kept for API parity; PJRT owns memory")
define_flag("cudnn_deterministic", False, "kept for API parity; XLA is deterministic")
