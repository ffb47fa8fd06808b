"""Poisson-load serving benchmark: SLO numbers for the serving frontend.

Drives ``paddle_tpu.serving.ServingEngine`` the way traffic does — a
seeded Poisson arrival process submits N concurrent streams of mixed
prompt lengths from a background thread while the scheduler loop runs
— and prints ONE JSON line with the SLO rungs ``tools/bench_gate.py``
gates (TTFT regresses UP, throughput DOWN):

    python tools/serve_bench.py --streams 8 --seed 0

    {"serve_p50_ttft_ms": ..., "serve_p99_ttft_ms": ...,
     "serve_tokens_per_sec": ..., "serve_goodput": ...,
     ..., "telemetry": {...}}

``serve_goodput`` is the fraction of finished requests meeting BOTH
the ``--ttft-target`` and ``--tpot-target`` SLOs (verdicts stamped
per request by serving/slo.py). ``--requests-out`` writes one JSONL
row per request (waits/ttft/tpot/preempt counts/verdict) and
``--journal-out`` dumps the flight recorder for
``tools/serve_top.py`` forensics.

Defaults are CPU-sized (tiny model) so the rung runs in CI; on a chip
pass the 1.3B geometry (--d-model 2048 --layers 24 --heads 16
--vocab 51200) and a rate that saturates it. A warmup pass compiles
every chunk/decode program first (--no-warmup to include compiles in
the measured TTFTs — the cold-start view).

``--speculative`` (ISSUE 12) runs the scheduler's decode slot as
draft+verify rounds (``--spec-drafter self|draft|oracle``,
``--spec-k``): every ``serve_*`` key re-emits as ``serve_spec_*`` plus
``serve_spec_accept_rate`` / ``serve_spec_rounds``, so bench_gate
tracks the speculative SLO rungs (throughput/accept-rate regress
DOWN, TTFT UP) independently of the plain ones. ``oracle`` drives the
target model as its own drafter — the acceptance-ceiling workload.

``--fleet N`` (ISSUE 14) drives a :class:`FleetRouter` over N
replicas (one serve-loop thread each) under a SKEWED-PREFIX Poisson
load — ``--system-prompts K`` distinct system prompts with Zipf-ish
popularity — and emits ``fleet_{goodput,tokens_per_sec,p50_ttft_ms,
p99_ttft_ms,failovers,migrations,...}``. ``--fleet-policy rr`` runs
the round-robin baseline the affinity policy is pinned against.
``--fleet --chaos`` re-drives the measured workload with a seeded
fleet fault schedule (a replica KILL mid-load, a hang, dispatch
faults, beat suppression) and pins the ISSUE 14 acceptance: zero
admitted requests lost, survivor greedy-token parity vs the
undisturbed run, and bounded goodput loss (``fleet_chaos_*`` keys,
nonzero exit on a failed pin).

``--fleet --drain-async`` (ISSUE 19) gracefully drains replica 0
MID-LOAD with ``FLAGS_migrate_async`` on: each occupied decode slot
streams its complete KV pages to a peer in page batches while both
endpoints keep decoding, and only the mutable tail + metadata copy
under the step locks at the join. Emits ``fleet_async_migration_*``
(streamed migration count, total migration stall-ms, decode tokens
generated fleet-wide during the drain window) and exits nonzero when
nothing streamed, decode made no progress during the drain, or any
request was lost.

``--chaos`` (ISSUE 11) re-drives the SAME measured workload against a
fresh engine with a seeded fault schedule installed
(``serving/faults.py`` — raises, delays, token corruption, and pool
squeezes across >=5 distinct sites) and pins the robustness
acceptance: the serve loop never exits, every faulted request lands
in a terminal ``error``/``deadline_exceeded``/``shed`` state, every
SURVIVING request's greedy tokens are identical to the fault-free
run, and goodput stays within a pinned bound of the fault-free run's.
Emits ``serve_chaos_*`` keys (gated by tools/bench_gate.py) and exits
nonzero when any pin fails.

``--adapters K`` (ISSUE 18) serves K distinct LoRA adapters from one
:class:`AdapterBank` — every request is stamped with a round-robin
``adapter_id`` so each decode chunk mixes adapters and the batched
ragged grouped-GEMM delta path carries the whole set in ONE launch
per target projection. The rung measures the multi-tenancy tax
directly: the same workload is first driven single-tenant (every
request on ONE adapter — the same adaptered programs, no grouping
spread) and then multi-adapter, and ``serve_lora_pct_of_single_
tenant`` is the ratio of the two throughputs (gated DOWN; the ISSUE
18 acceptance pins >= 0.8 at K=32 on CPU). Also emits
``serve_lora_{tokens_per_sec,swap_count,decode_programs}`` — the
program count must stay independent of the adapter set.

``--tenants K`` (ISSUE 17) stamps a Zipf-popular tenant id on every
request (rank k drawn ∝ 1/(k+1)^``--tenant-skew``) and turns the
per-tenant usage ledger on (``serving/accounting.py``): the run emits
``serve_tenant_{count,max_share,min_goodput}`` and
``usage_unattributed_ms`` — the last gated UP by bench_gate with NO
noise floor (device time the ledger failed to attribute is an
accounting leak however small). ``--usage-out`` dumps the per-request
usage JSONL (``serve_top --tenants`` / ``trace_merge`` input; fleet
runs write one ``_r<idx>`` file per replica plus ``_router``). The
usage keys are ALWAYS emitted with ledger-off defaults so the gated
key set is stable across runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np


def _telemetry():
    """Runtime-telemetry block (the bench.py shape): stats registry
    snapshot + the per-program roofline table, so the serve rungs
    carry the serve.{ttft,tpot,queue_wait} histograms and the
    per-phase ``serve.prefill[c=*]`` / ``decode.*[k=*]`` rows."""
    from paddle_tpu.profiler import roofline, stats

    snap = stats.snapshot()
    out = {
        "counters": {k: v for k, v in snap["counters"].items()
                     if not k.startswith("op.")},
        "gauges": snap["gauges"],
        "histograms": snap["histograms"],
    }
    rl = roofline.report()
    if rl:
        out["roofline"] = {k: v for k, v in rl.items()
                           if k.startswith(("serve", "decode",
                                            "prefill"))}
    return out


def _start_telemetry(args, journal=None, n_replicas=None):
    """Continuous-telemetry wiring (ISSUE 16): when --telemetry-out
    is set, run a background TimeSeriesSampler over the stats
    registry for the measured window with the default alert rules
    attached (burn-rate, HBM pressure, replica-death when fleet,
    preemption spike), journaling alert transitions into the serve's
    flight recorder. Returns the sampler or None."""
    if not getattr(args, "telemetry_out", None):
        return None
    from paddle_tpu.profiler import AlertEngine, TimeSeriesSampler
    from paddle_tpu.profiler import default_rules

    alerts = AlertEngine(default_rules(n_replicas), journal=journal)
    sampler = TimeSeriesSampler(
        interval_ms=args.telemetry_interval_ms,
        enabled=True).attach_alerts(alerts)
    sampler.start()
    return sampler


def _stop_telemetry(sampler, path):
    """Stop the measured window's sampler (one final tick) and dump
    the series JSONL (serve_top --history / trace_merge input)."""
    if sampler is None:
        return {}
    sampler.stop()
    sampler.dump_jsonl(path)
    return {"telemetry_ticks": sampler.n_ticks,
            "telemetry_out": path}


def _alert_keys():
    """The gated alert/attribution scalars — emitted on every run
    (zero when telemetry is off) so bench_gate can hold the line:
    ``alert_fired`` UP with no noise floor (a run that starts paging
    is a regression however small), host overhead UP (the residual
    the attribution exists to expose)."""
    from paddle_tpu.profiler import stats

    h = stats.histogram("serve.step.host_overhead_ms")
    return {
        "alert_fired": int(stats.counter("alert.fired").value),
        "alert_resolved": int(stats.counter("alert.resolved").value),
        "serve_step_host_overhead_ms": round(h.total / h.count, 4)
        if h.count else None,
    }


def _usage_keys(eng=None, router=None):
    """The per-tenant usage scalars (ISSUE 17) — ALWAYS emitted, with
    ledger-off defaults, so bench_gate's gated key set is stable:
    ``serve_tenant_max_share`` regresses UP (one tenant crowding out
    the rest) and ``usage_unattributed_ms`` UP with no noise floor."""
    from paddle_tpu.serving.accounting import (tenant_rollup,
                                               unattributed_ms)

    if router is not None:
        ledgers = [r.eng.usage for r in router.replicas
                   if r.eng.usage is not None]
        if router.usage is not None:
            ledgers.append(router.usage)
        recs = router.fleet_usage() if ledgers else []
        mons = [r.eng.slo_monitor for r in router.replicas]
    else:
        ledgers = [eng.usage] if eng.usage is not None else []
        recs = eng.usage.records() if ledgers else []
        mons = [eng.slo_monitor]
    if not ledgers:
        return {"serve_tenant_count": 0,
                "serve_tenant_max_share": 0.0,
                "serve_tenant_min_goodput": None,
                "usage_unattributed_ms": 0.0}
    roll = tenant_rollup(recs)
    goodputs = [m.tenant_min_goodput for m in mons
                if m.tenant_min_goodput is not None]
    return {
        "serve_tenant_count": len(roll),
        "serve_tenant_max_share": round(max(
            (t["share"] for t in roll.values()), default=0.0), 4),
        "serve_tenant_min_goodput": round(min(goodputs), 4)
        if goodputs else None,
        "usage_unattributed_ms": unattributed_ms(*ledgers),
    }


def _dump_usage(args, eng=None, router=None):
    """--usage-out: per-request usage JSONL. Single engine writes one
    hop-0 file; a fleet writes the export_journals shape — one
    ``<prefix>_r<idx>.jsonl`` per replica plus ``<prefix>_router`` —
    which trace_merge folds back into one record per request."""
    if not args.usage_out:
        return
    if router is not None:
        import os

        d = os.path.dirname(args.usage_out) or "."
        base = os.path.basename(args.usage_out)
        router.export_usage(d, prefix=base.replace(".jsonl", ""))
    elif eng is not None and eng.usage is not None:
        eng.usage.dump_jsonl(args.usage_out, hop=0)


def build_engine(args, faults=None):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import FusedCausalLM
    from paddle_tpu.serving import ServingEngine, SLOConfig

    paddle.seed(args.seed)
    lens = [int(x) for x in args.prompt_mix.split(",")]
    max_len = max(lens) + args.system_prompt + args.max_new + 1
    model = FusedCausalLM(
        vocab_size=args.vocab, embed_dim=args.d_model,
        num_heads=args.heads, dim_feedforward=4 * args.d_model,
        num_layers=args.layers, max_position=max_len + 1)
    if args.bf16:
        st = model.stack
        for n in ("qkv_weight", "qkv_bias", "out_weight", "out_bias",
                  "ffn1_weight", "ffn1_bias", "ffn2_weight",
                  "ffn2_bias"):
            p = getattr(st, n)
            p._rebind(p._data.astype(jnp.bfloat16))
    slo = SLOConfig(ttft_weight=args.ttft_weight,
                    tpot_weight=args.tpot_weight,
                    prefill_chunk=args.prefill_chunk,
                    ttft_target_ms=args.ttft_target,
                    tpot_target_ms=args.tpot_target)
    spec = None
    if getattr(args, "speculative", False):
        spec = _build_drafter(args, model, max_len)
    return ServingEngine(
        model, max_batch=args.streams, page_size=args.page_size,
        max_length=max_len, decode_chunk=args.decode_chunk,
        quant=args.quant, slo=slo, faults=faults,
        speculative=spec, spec_k=args.spec_k,
        mp_degree=args.mp if args.mp and args.mp > 1 else None), lens


def _build_drafter(args, model, max_len):
    """--spec-drafter resolution: ``self`` = Medusa-style training-free
    heads (no extra weights); ``draft`` = a quarter-size FusedCausalLM
    draft model with its own tiny non-paged KV state; ``oracle`` =
    the target model ITSELF as draft model — every draft is the
    target's own greedy pick, accept rate 1.0, the amortization
    ceiling rung (an acceptance-friendly workload by construction)."""
    from paddle_tpu.inference import DraftModelDrafter, FusedCausalLM

    if args.spec_drafter == "self":
        return "self"
    if args.spec_drafter == "oracle":
        return DraftModelDrafter(model)
    import paddle_tpu as paddle

    paddle.seed(args.seed + 1)
    draft = FusedCausalLM(
        vocab_size=args.vocab, embed_dim=max(args.d_model // 4, 8),
        num_heads=max(args.heads // 2, 1),
        dim_feedforward=max(args.d_model, 32),
        num_layers=max(args.layers // 2, 1),
        max_position=max_len + 1)
    return DraftModelDrafter(draft)


def make_requests(args, lens, rng):
    """(prompt, arrival_gap_s) list: mixed lengths, a shared system
    prompt on a fraction of requests (the prefix-cache's traffic
    shape), exponential inter-arrival gaps (Poisson process)."""
    sys_prompt = rng.randint(0, args.vocab, (args.system_prompt,)) \
        if args.system_prompt else None
    reqs = []
    for i in range(args.requests):
        L = int(lens[int(rng.randint(len(lens)))])
        body = rng.randint(0, args.vocab, (L,))
        if sys_prompt is not None and rng.rand() < args.system_frac:
            prompt = np.concatenate([sys_prompt, body])
        else:
            prompt = body
        gap = float(rng.exponential(1.0 / args.rate))
        reqs.append((prompt, gap))
    return reqs


def _assign_tenants(reqs, args, rng):
    """Stamp a Zipf-popular tenant id on every request — rank k drawn
    ∝ 1/(k+1)^``--tenant-skew`` — turning ``(prompt, gap)`` pairs into
    ``(prompt, gap, tenant)`` triples. The skew is what makes
    ``tenant.max_share`` move: a uniform tenant mix never trips the
    tenant-hog alert rule."""
    k = max(int(args.tenants), 1)
    w = np.array([1.0 / (i + 1) ** args.tenant_skew
                  for i in range(k)])
    w /= w.sum()
    return [(p, g, f"tenant{int(rng.choice(k, p=w))}")
            for p, g in reqs]


def failed_requests(done, max_new):
    """Requests a reported result must not hide: terminal state
    ``error`` (the scheduler contains a step failure per request, so a
    broken program ends as ``error`` with an empty stream while
    ``run()`` returns normally), or ``ok`` with a token count other
    than ``max_new`` (no request here sets an eos). Deadline expiry
    and shedding are outcomes of the load, not failures."""
    return [(r.id, r.state, len(r.generated),
             type(r.error).__name__ if r.error is not None else None)
            for r in done
            if r.state == "error"
            or (r.state == "ok" and len(r.generated) != max_new)]


def require_served(done, max_new, what):
    """Exit non-zero — after the JSON line, never instead of a loud
    message — when any request failed; see ``failed_requests``."""
    bad = failed_requests(done, max_new)
    if bad:
        print(f"serve_bench {what}: {len(bad)} request(s) failed "
              f"(id, state, n_tokens, error): {bad[:8]}",
              file=sys.stderr)
        sys.exit(1)


def drive(eng, reqs, max_new, deadline_ms=None):
    """Submit on a background thread at the Poisson arrival times;
    run the scheduler loop here until every submitted request reaches
    a TERMINAL state (ok, error, deadline_exceeded, shed-at-drain).
    Returns ``(wall_s, rids)`` — ``rids[i]`` is submission i's request
    id, or None when the engine shed it at submit (typed
    ServerOverloaded backpressure)."""
    from paddle_tpu.serving import ServerOverloaded

    err: list = []
    rids: list = []
    done_submitting = threading.Event()

    def submitter():
        try:
            t_next = time.monotonic()
            for prompt, gap, *rest in reqs:
                t_next += gap
                delay = t_next - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                try:
                    rids.append(eng.submit(prompt,
                                           max_new_tokens=max_new,
                                           deadline_ms=deadline_ms,
                                           tenant=rest[0] if rest
                                           else None,
                                           adapter_id=rest[1]
                                           if len(rest) > 1 else None))
                except ServerOverloaded:
                    rids.append(None)  # backpressure — dropped load
        except BaseException as e:  # surface on the main thread
            err.append(e)
        finally:
            done_submitting.set()

    th = threading.Thread(target=submitter, daemon=True)
    t0 = time.monotonic()
    th.start()
    while True:
        if err:
            raise err[0]
        if done_submitting.is_set() and len(eng.finished) >= sum(
                1 for r in rids if r is not None):
            break
        if (eng._inbox or eng.waiting or eng._prefilling
                or eng.num_active):
            eng.step()
        else:
            time.sleep(0.0005)  # idle: wait for the next arrival
    th.join()
    return time.monotonic() - t0, list(rids)


def make_fleet_requests(args, lens, rng):
    """Skewed-prefix Poisson load (the fleet routing workload):
    ``--system-prompts`` DISTINCT system prompts with Zipf-ish
    popularity (rank k drawn ∝ 1/(k+1)), mixed body lengths,
    exponential inter-arrival gaps. Returns (prompt, gap) pairs."""
    k = max(int(args.system_prompts), 1)
    prefixes = [rng.randint(0, args.vocab, (args.system_prompt,))
                for _ in range(k)]
    w = np.array([1.0 / (i + 1) for i in range(k)])
    w /= w.sum()
    reqs = []
    for _ in range(args.requests):
        L = int(lens[int(rng.randint(len(lens)))])
        body = rng.randint(0, args.vocab, (L,))
        if args.system_prompt and rng.rand() < args.system_frac:
            prompt = np.concatenate(
                [prefixes[int(rng.choice(k, p=w))], body])
        else:
            prompt = body
        reqs.append((prompt, float(rng.exponential(1.0 / args.rate))))
    return reqs, prefixes


def build_fleet(args, faults=None, disagg=None):
    """N identical replicas from one seeded factory (failover replays
    and page migration are byte-exact only because every replica
    computes the same function). ``disagg`` forwards the ISSUE 20
    prefill/decode role split ('auto' or 'P:D'); decode-role replicas
    run role-specialized config — same seeded weights (KV handoffs
    stay byte-exact) but DOUBLE the decode batch (their work is
    admission-free token streaming, so the extra slots cost only
    page-pool headroom and keep prefill handoffs from bouncing off a
    full batch back onto the prefill side) and a QUARTER decode
    chunk (frequent step boundaries, so an inbound handoff's
    import never waits behind a long decode action's step lock)."""
    from paddle_tpu.serving import FleetRouter
    from paddle_tpu.serving.router import _parse_disagg

    roles = _parse_disagg(disagg, args.fleet)

    def factory(i):
        streams0, dchunk0 = args.streams, args.decode_chunk
        if roles is not None and i >= roles[0]:
            args.streams = streams0 * 2
            args.decode_chunk = max(2, dchunk0 // 4)
        try:
            eng, _ = build_engine(args)
        finally:
            args.streams, args.decode_chunk = streams0, dchunk0
        return eng

    lens = [int(x) for x in args.prompt_mix.split(",")]
    return FleetRouter(engine_factory=factory, n_replicas=args.fleet,
                       policy=args.fleet_policy, faults=faults,
                       disagg=disagg), lens


def _fleet_warm(router, args, lens, prefixes):
    """Compile every chunk/decode program on every replica OUTSIDE
    the measured window (synchronous stepping — no beat enforcement,
    so multi-second compiles can't false-kill a replica), then reset
    telemetry/journals to describe only the load run."""
    from paddle_tpu.profiler import stats
    from paddle_tpu.serving import Request

    warm = [np.full((L,), 1, np.int32) for L in lens]
    if args.system_prompt:
        warm += [np.concatenate([p, warm[0]]) for p in prefixes]
    for rep in router.replicas:      # every replica compiles
        for p in warm:
            rep.eng.submit_request(
                Request(p, max_new_tokens=args.max_new))
    while any(r.eng.has_work for r in router.replicas):
        for rep in router.replicas:
            rep.step_once()
    if router.disagg is not None or any(
            getattr(r.eng, "host_tier", None) is not None
            for r in router.replicas):
        _warm_kv_transfer(router)
    for rep in router.replicas:
        rep.eng.finished.clear()
        rep.eng.action_log.clear()
        rep.eng.slo_monitor.reset()
        if rep.eng.journal is not None:
            rep.eng.journal.clear()
        if rep.eng.usage is not None:
            rep.eng.usage.reset()   # the ledger describes the load run
    if router.usage is not None:
        router.usage.reset()
    router._tracked.clear()
    stats.reset()


def _warm_kv_transfer(router):
    """Compile the page-count-BUCKETED KV gather/scatter programs
    (handoff export/import, host-tier spill/restore — see
    ``ContinuousBatchingEngine._pad_pow2``) outside the measured
    window: export doubling page batches and write the blobs straight
    back to the same pages (byte-identical, so pool contents are
    untouched). Without this the FIRST mid-drive handoff or spill
    pays a multi-hundred-ms XLA compile inside a replica's stepping
    thread and the health checker hedges its queue away."""
    for rep in router.replicas:
        eng = rep.eng
        if not eng.can_spill():
            continue
        cap = max(1, min(eng._mgr.num_pages,
                         getattr(eng, "_pages_per_seq", 1 << 30)))
        n = 1
        while True:
            pages = list(range(min(n, cap)))
            eng.import_kv_pages(pages, eng.export_kv_pages(pages))
            if n >= cap:
                break
            n *= 2


def drive_fleet(router, reqs, max_new, deadline_ms=None,
                timeout_s=600.0):
    """Threaded fleet drive: start the replica loops + health monitor,
    submit at the Poisson arrival times, wait until every tracked
    request is terminal. Returns (wall_s, rids) with None for
    router-shed submissions."""
    from paddle_tpu.serving import ServerOverloaded

    router.start()
    rids = []
    t0 = time.monotonic()
    t_next = t0
    for prompt, gap, *rest in reqs:
        t_next += gap
        delay = t_next - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            rids.append(router.submit(prompt, max_new_tokens=max_new,
                                      deadline_ms=deadline_ms,
                                      tenant=rest[0] if rest
                                      else None))
        except ServerOverloaded:
            rids.append(None)
    deadline = time.monotonic() + timeout_s
    while router.pending():
        if time.monotonic() > deadline:
            router.stop()
            raise RuntimeError(
                f"fleet bench stalled: {router.pending()} requests "
                f"in flight, replica states "
                f"{[r.state for r in router.replicas]}")
        time.sleep(0.001)
    wall = time.monotonic() - t0
    router.stop()
    return wall, rids


def fleet_chaos_injector(seed):
    """Seeded FLEET fault schedule (>=5 distinct sites): a replica
    KILL mid-load (the headline crash), a replica.step hang long
    enough to walk suspect -> dead, suppressed heartbeats, dispatch
    faults that trip a circuit breaker, and engine-level chunk faults
    — all of which the router must absorb with zero lost requests."""
    from paddle_tpu.serving import FaultInjector

    return (FaultInjector(seed=seed)
            .add("replica.step", kind="kill", at=10)
            # the hang lands between the suspect (3 beats = 150ms)
            # and dead (6 beats = 300ms) thresholds: the replica is
            # suspected (inbox hedges away) and then RECOVERS — only
            # the kill above may take a replica down, so 1 of 2 dying
            # is exactly the zero-loss acceptance scenario
            .add("replica.step", kind="hang", at=30, delay_ms=200.0)
            .add("replica.heartbeat", kind="raise", at=(5, 6))
            .add("router.dispatch", kind="raise", at=(3, 7))
            .add("prefill.dispatch", kind="raise", at=4)
            .add("decode.step", kind="raise", at=6))


def _start_drainer(router):
    """--drain-async (ISSUE 19): once replica 0 is mid-decode, drain
    it with ``FLAGS_migrate_async`` on — occupied slots STREAM their
    complete KV pages to peers while both endpoints keep decoding —
    and measure fleet-wide decode progress during the drain window
    (the migration-concurrent-decode pin). Returns (thread, state)."""
    from paddle_tpu.core.flags import set_flags

    set_flags({"migrate_async": True})
    state = {}

    def _drainer():
        rep = router.replicas[0]
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if any(rep.eng._slots[i] is not None
                   for i in range(rep.eng.max_batch)):
                break
            time.sleep(0.001)
        tok0 = sum(len(r.generated) for r in router._tracked)
        t0 = time.monotonic()
        router.drain(0)
        while rep.state not in ("drained", "dead") \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        state["decode_tokens"] = sum(
            len(r.generated) for r in router._tracked) - tok0
        state["drain_ms"] = round((time.monotonic() - t0) * 1e3, 3)

    th = threading.Thread(target=_drainer, daemon=True)
    th.start()
    return th, state


def run_fleet(args):
    """The --fleet bench: warmup, measured Poisson run, fleet_* keys;
    with --chaos, a second run under the seeded fleet fault schedule
    pinning zero-loss failover + survivor parity + bounded goodput
    loss; with --drain-async, a mid-load decode-concurrent drain of
    replica 0 pinning streamed async migrations + decode progress
    during the drain window (fleet_async_migration_* keys). Returns
    (out dict, ok)."""
    from paddle_tpu.profiler import stats

    rng = np.random.RandomState(args.seed)
    router, lens = build_fleet(args)
    reqs, prefixes = make_fleet_requests(args, lens, rng)
    if args.tenants:
        reqs = _assign_tenants(reqs, args, rng)
    if not args.no_warmup:
        _fleet_warm(router, args, lens, prefixes)
    sampler = _start_telemetry(
        args, journal=router.replicas[0].eng.journal,
        n_replicas=args.fleet)
    drainer = _start_drainer(router) if args.drain_async else None
    wall, rids = drive_fleet(router, reqs, args.max_new,
                             deadline_ms=args.deadline_ms)
    if drainer is not None:
        drainer[0].join(timeout=10.0)
    tele_out = _stop_telemetry(sampler, args.telemetry_out)
    done = router.results()
    finished = [done[r] for r in rids if r is not None]
    ttfts = np.array([r.ttft_s for r in finished
                      if r.ttft_s is not None], np.float64) * 1e3
    if ttfts.size == 0:
        ttfts = np.array([0.0])
    judged = [r for r in finished
              if getattr(r, "slo_ok", None) is not None]
    goodput = round(sum(1 for r in judged if r.slo_ok)
                    / len(judged), 4) if judged else None
    total_tokens = sum(len(r.generated) for r in finished)
    if args.journal_out:
        import os

        d = os.path.dirname(args.journal_out) or "."
        base = os.path.basename(args.journal_out)
        router.export_journals(d, prefix=base.replace(".jsonl", ""))
    _dump_usage(args, router=router)
    out = {
        "fleet_replicas": args.fleet,
        "fleet_policy": args.fleet_policy,
        "fleet_p50_ttft_ms": round(float(np.percentile(ttfts, 50)), 3),
        "fleet_p99_ttft_ms": round(float(np.percentile(ttfts, 99)), 3),
        "fleet_tokens_per_sec": round(total_tokens / wall, 1)
        if wall > 0 else None,
        "fleet_goodput": goodput,
        "fleet_requests": len(finished),
        "fleet_shed": sum(1 for r in rids if r is None),
        "fleet_failovers": int(
            stats.counter("fleet.failovers").value),
        "fleet_migrations": int(
            stats.counter("fleet.migrations").value),
        "fleet_migrated_pages": int(
            stats.counter("fleet.migrated_pages").value),
        "fleet_hedges": int(stats.counter("fleet.hedges").value),
        "fleet_prefix_pages_saved": int(
            stats.counter("serving.prefix_pages_saved").value),
        "fleet_system_prompts": int(args.system_prompts),
        "fleet_rate": args.rate,
        "fleet_wall_s": round(wall, 3),
        "telemetry": _telemetry(),
    }
    out.update(_alert_keys())
    out.update(_usage_keys(router=router))
    out.update(tele_out)
    ok = True
    bad = failed_requests(finished, args.max_new)
    if bad:
        print(f"serve_bench --fleet: {len(bad)} request(s) failed "
              f"(id, state, n_tokens, error): {bad[:8]}",
              file=sys.stderr)
        ok = False
    if drainer is not None:
        h = stats.histogram("serve.step.migration_ms")
        st = drainer[1]
        out.update({
            "fleet_drain_async": 1,
            "fleet_async_migrations": int(
                stats.counter("fleet.async_migrations").value),
            # stall accounting: total migration phase time (gated UP —
            # overlap exists to shrink what migration steals)
            "fleet_async_migration_stall_ms": round(h.total, 3)
            if h.count else 0.0,
            # tokens generated FLEET-WIDE during the drain window:
            # the migration-concurrent decode-progress pin (gated
            # DOWN — zero means the drain serialized decode)
            "fleet_async_migration_decode_tokens": st.get(
                "decode_tokens"),
            "fleet_async_migration_drain_ms": st.get("drain_ms"),
        })
        lost = sum(1 for r in rids if r is not None
                   and getattr(done.get(r), "state", None) != "ok")
        out["fleet_async_migration_lost"] = lost
        ok = (out["fleet_async_migrations"] >= 1
              and (st.get("decode_tokens") or 0) > 0 and lost == 0)
    if args.chaos:
        chaos_out, chaos_ok = run_fleet_chaos(args, reqs, rids, done,
                                              goodput, lens, prefixes)
        out.update(chaos_out)
        ok = ok and chaos_ok
    return out, ok


def run_fleet_chaos(args, reqs, base_rids, base_done, base_goodput,
                    lens, prefixes):
    """Re-drive the measured fleet workload with the seeded fleet
    fault schedule armed (after a fault-free warmup). Pins the ISSUE
    14 acceptance: a replica dies mid-load yet ZERO admitted requests
    are lost — every one finishes ``ok`` on a survivor with greedy
    tokens identical to the undisturbed run — and goodput stays
    within a pinned bound."""
    from paddle_tpu.profiler import stats

    seed = args.chaos_seed if args.chaos_seed is not None \
        else args.seed
    inj = fleet_chaos_injector(seed)
    router, _ = build_fleet(args)
    if not args.no_warmup:
        _fleet_warm(router, args, lens, prefixes)
    router.install_faults(inj)
    # the chaos window gets its own sampler/series: the replica-death
    # alert must fire at the injected kill, in a dump of its own
    sampler = _start_telemetry(
        args, journal=router.replicas[0].eng.journal,
        n_replicas=args.fleet)
    t0 = time.monotonic()
    wall, rids = drive_fleet(router, reqs, args.max_new,
                             deadline_ms=args.deadline_ms)
    tele_out = _stop_telemetry(
        sampler, args.telemetry_out + ".chaos"
        if args.telemetry_out else None)
    done = router.results()
    survivors = mismatches = lost = 0
    shed = 0
    for idx, rid in enumerate(rids):
        if rid is None:
            shed += 1
            continue
        req = done.get(rid)
        if req is None or getattr(req, "state", None) != "ok":
            lost += 1
            continue
        survivors += 1
        brid = base_rids[idx] if idx < len(base_rids) else None
        base = base_done.get(brid) if brid is not None else None
        if base is not None and \
                list(base.generated) != list(req.generated):
            mismatches += 1
    judged = [r for r in done.values()
              if getattr(r, "slo_ok", None) is not None]
    goodput = round(sum(1 for r in judged if r.slo_ok)
                    / len(judged), 4) if judged else None
    parity = 1.0 if mismatches == 0 and survivors > 0 else 0.0
    bound_ok = True
    if base_goodput is not None and goodput is not None:
        bound_ok = goodput >= base_goodput - 0.3
    failovers = int(stats.counter("fleet.failovers").value)
    dead = sum(1 for r in router.replicas if r.dead)
    sites = sorted({f["site"] for f in inj.fired})
    out = {
        "fleet_chaos_seed": seed,
        "fleet_chaos_survivor_parity": parity,
        "fleet_chaos_survivors": survivors,
        "fleet_chaos_lost": lost,
        "fleet_chaos_shed": shed,
        "fleet_chaos_request_errors": lost,
        "fleet_chaos_goodput": goodput,
        "fleet_chaos_goodput_bound_ok": int(bound_ok),
        "fleet_chaos_tokens_per_sec": round(
            sum(len(r.generated) for r in done.values()) / wall, 1)
        if wall > 0 else None,
        "fleet_chaos_failovers": failovers,
        "fleet_chaos_replicas_dead": dead,
        "fleet_chaos_hedges": int(
            stats.counter("fleet.hedges").value),
        "fleet_chaos_faults_injected": len(inj.fired),
        "fleet_chaos_sites_fired": sites,
        "fleet_chaos_wall_s": round(time.monotonic() - t0, 3),
    }
    out.update({f"fleet_chaos_{k}": v for k, v in tele_out.items()})
    out["fleet_chaos_alert_fired"] = int(
        stats.counter("alert.fired").value)
    # the acceptance pins: zero admitted requests lost, survivor
    # parity, exactly the killed replica died (a second death means
    # the hang overshot and the run proved nothing), >=5 sites
    ok = (parity == 1.0 and lost == 0 and bound_ok
          and failovers >= 1 and dead == 1 and len(sites) >= 5)
    return out, ok


def _drive_arm(args, disagg=None):
    """One measured rep of the --disagg comparison: build a fresh
    fleet (symmetric when ``disagg is None``, role-split otherwise),
    warm it, drive the seeded workload once, and reduce to the
    latency/goodput scalars ``run_disagg`` aggregates across reps.
    Every rep regenerates the request set from ``args.seed`` so all
    reps of both arms replay the identical arrival process."""
    rng = np.random.RandomState(args.seed)
    router, lens = build_fleet(args, disagg=disagg)
    reqs, prefixes = make_fleet_requests(args, lens, rng)
    if args.tenants:
        reqs = _assign_tenants(reqs, args, rng)
    if not args.no_warmup:
        _fleet_warm(router, args, lens, prefixes)
    wall, rids = drive_fleet(router, reqs, args.max_new,
                             deadline_ms=args.deadline_ms)
    done = router.results()
    finished = [done[r] for r in rids if r is not None]
    lost = sum(1 for r in rids if r is not None
               and getattr(done.get(r), "state", None) != "ok")
    ttfts = np.array([r.ttft_s for r in finished
                      if r.ttft_s is not None], np.float64) * 1e3
    if ttfts.size == 0:
        ttfts = np.array([0.0])
    judged = [r for r in finished
              if getattr(r, "slo_ok", None) is not None]
    goodput = round(sum(1 for r in judged if r.slo_ok)
                    / len(judged), 4) if judged else None
    return {"router": router,
            "p50": float(np.percentile(ttfts, 50)),
            "p99": float(np.percentile(ttfts, 99)),
            "tps": sum(len(r.generated) for r in finished) / wall
            if wall > 0 else None,
            "goodput": goodput, "lost": lost,
            "requests": len(finished)}


def run_disagg(args):
    """The --fleet --disagg bench (ISSUE 20): the SAME seeded
    prefill-heavy skewed Poisson workload driven twice — first on the
    symmetric fleet (every replica prefills AND decodes; the standard
    ``fleet_*`` keys), then on the role-split fleet (half the replicas
    prefill-specialized with the host-DRAM KV tier armed; finished
    prefills hand their KV to decode replicas over the migration
    path). Each arm runs ``--disagg-reps`` measured drives (fresh
    fleet per rep, identical seeded arrivals) and reports the MEDIAN
    across reps. Emits ``serve_disagg_*`` + ``fleet_spill_*`` keys
    and pins the acceptance: disagg median TTFT p99 <= symmetric,
    goodput >= symmetric, >=1 handoff actually streamed, zero
    requests lost in any disagg rep.

    CPU rung targets (bench.py --fleet-disagg, 2 replicas, prompt mix
    48,128,256): serve_disagg_p99_ttft_ms <= fleet_p99_ttft_ms,
    serve_disagg_goodput >= fleet_goodput, handoffs >= 1. TPU targets
    (v5e-8, 2 replicas, prompt mix 2048,8192,16384, rate 32):
    serve_disagg_p99_ttft_ms <= 0.7 * fleet_p99_ttft_ms and
    serve_disagg_tokens_per_sec >= 0.95 * fleet_tokens_per_sec — the
    decode fleet never pays a prefill stall, so the TTFT tail
    collapses while throughput holds."""
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.profiler import stats

    if args.prompt_mix == "8,32,96":
        # prefill-heavy skew: long prompts + a hot arrival burst make
        # prefill the contended resource the role split relieves
        args.prompt_mix = "48,128,256"
    if args.tpot_weight == 1.0:
        # production decode-SLO pressure, applied to BOTH runs: the
        # symmetric fleet must interleave decode AHEAD of queued
        # prefills (burst 4:1 from the weight ratio) — the TTFT tax
        # disaggregation deletes, since its prefill replicas override
        # to 8:1 and hand finished slots to the decode side instead
        # of decoding them here
        args.tpot_weight = 4.0
    out, ok = run_fleet(args)          # symmetric baseline
    reps = max(1, int(getattr(args, "disagg_reps", 1)))
    sym_extra = [_drive_arm(args, disagg=None)
                 for _ in range(reps - 1)]
    stats.reset()
    # host tier + CPU-calibrated cost model land BEFORE the disagg
    # engines construct (the tier is wired at __init__). The toy
    # CPU model's per-token prefill cost is ~1e8x smaller than a real
    # chip's, so the re-prefill arm of the directory cost model is
    # priced at a matching tiny TFLOP rate — otherwise restores would
    # never win and the pull path would sit unexercised.
    set_flags({"kv_host_tier_bytes": int(args.host_tier_bytes),
               "disagg_prefill_tflops": 1e-4})
    try:
        dis = [_drive_arm(args, disagg="auto") for _ in range(reps)]
    finally:
        set_flags({"kv_host_tier_bytes": 0,
                   "disagg_prefill_tflops": 100.0})
    # median across reps on BOTH arms: one measured drive per rep,
    # identical seeded workload, fresh fleet each time. A single
    # 12-24-sample p99 is the max order statistic and on a 1-core
    # host GIL scheduling noise swings it by 2x run-to-run — the
    # median rep is the comparison the pin can hold
    sym_p99 = [out["fleet_p99_ttft_ms"]] + [r["p99"] for r in sym_extra]
    sym_gp = [g for g in [out["fleet_goodput"]]
              + [r["goodput"] for r in sym_extra] if g is not None]
    out["fleet_p99_ttft_ms"] = round(float(np.median(sym_p99)), 3)
    if sym_gp:
        out["fleet_goodput"] = round(float(np.median(sym_gp)), 4)
    lost = sum(r["lost"] for r in dis)
    dis_gp = [r["goodput"] for r in dis if r["goodput"] is not None]
    goodput = round(float(np.median(dis_gp)), 4) if dis_gp else None
    c = stats.counter
    handoffs = int(c("fleet.handoffs").value)
    router = dis[-1]["router"]
    out.update({
        "serve_disagg_replicas": f"{router.disagg[0]}P:"
        f"{router.disagg[1]}D",
        "serve_disagg_reps": reps,
        "serve_disagg_p50_ttft_ms": round(
            float(np.median([r["p50"] for r in dis])), 3),
        "serve_disagg_p99_ttft_ms": round(
            float(np.median([r["p99"] for r in dis])), 3),
        "serve_disagg_tokens_per_sec": round(
            float(np.median([r["tps"] for r in dis
                             if r["tps"] is not None] or [0.0])), 1),
        "serve_disagg_goodput": goodput,
        "serve_disagg_requests": dis[-1]["requests"],
        "serve_disagg_lost": lost,
        "serve_disagg_handoffs": handoffs,
        "serve_disagg_handoff_pages": int(
            c("fleet.handoff_pages").value),
        "fleet_spill_pages": int(c("fleet.spills").value),
        "fleet_spill_bytes": int(c("fleet.spill_bytes").value),
        "fleet_restore_pages": int(c("fleet.restores").value),
        "fleet_restore_bytes": int(c("fleet.restore_bytes").value),
        "fleet_host_evictions": int(c("fleet.host_evictions").value),
        "fleet_directory_hits": int(c("fleet.directory_hits").value),
        "fleet_directory_pulls": int(
            c("fleet.directory_pulls").value),
        "fleet_directory_misses": int(
            c("fleet.directory_misses").value),
    })
    base_p99 = out.get("fleet_p99_ttft_ms")
    base_goodput = out.get("fleet_goodput")
    pins_ok = (handoffs >= 1 and lost == 0
               and (base_p99 is None
                    or out["serve_disagg_p99_ttft_ms"] <= base_p99)
               and (base_goodput is None or goodput is None
                    or goodput >= base_goodput))
    return out, ok and pins_ok


def run_lora(args):
    """The --adapters bench (ISSUE 18): one AdapterBank serving K
    distinct LoRA adapters, requests stamped round-robin so every
    decode chunk mixes adapters. Drives the SAME Poisson workload
    twice on one warm engine — single-tenant (every request on one
    adapter: identical adaptered programs, no grouping spread) then
    multi-adapter — and reports the throughput ratio as
    ``serve_lora_pct_of_single_tenant``. The compiled decode-program
    count is emitted too: it must not scale with the adapter set."""
    from paddle_tpu.profiler import stats
    from paddle_tpu.serving import AdapterBank

    rng = np.random.RandomState(args.seed)
    eng, lens = build_engine(args)
    bank = AdapterBank.from_stack(eng.model.stack._stack(),
                                  slots=args.adapters,
                                  rank=args.adapter_rank)
    for i in range(args.adapters):
        bank.load(bank.random_adapter(f"lora{i}", seed=args.seed + i,
                                      rank=args.adapter_rank))
    eng.adapters = bank
    swaps_warm = int(stats.counter("lora.swaps").value)
    reqs = make_requests(args, lens, rng)

    def reset():
        eng.finished.clear()
        eng.action_log.clear()
        eng.slo_monitor.reset()
        if eng.journal is not None:
            eng.journal.clear()
        if eng.usage is not None:
            eng.usage.reset()

    if not args.no_warmup:
        # compile every adaptered chunk/decode program (plus the
        # base-path ones a mixed batch would touch) outside both
        # measured windows, so the single-vs-multi ratio compares
        # steady states
        warm = [(np.full((L,), 1, np.int32), 0.0, None, "lora0")
                for L in lens]
        warm.append((np.full((lens[0],), 1, np.int32), 0.0))
        drive(eng, warm, args.max_new)
        reset()
        stats.reset()

    # single-tenant baseline: the whole load on ONE adapter
    wall_s, rids_s = drive(
        eng, [(p, g, None, "lora0") for p, g in reqs], args.max_new)
    single_tokens = sum(len(r.generated) for r in eng.finished)
    single_tps = single_tokens / wall_s if wall_s > 0 else 0.0
    reset()

    # multi-adapter run: round-robin over the full bank
    multi = [(p, g, None, f"lora{i % args.adapters}")
             for i, (p, g) in enumerate(reqs)]
    sampler = _start_telemetry(args, journal=eng.journal)
    wall_m, rids_m = drive(eng, multi, args.max_new)
    tele_out = _stop_telemetry(sampler, args.telemetry_out)
    done = eng.finished
    ttfts = np.array([r.ttft_s for r in done
                      if r.ttft_s is not None], np.float64) * 1e3
    if ttfts.size == 0:
        ttfts = np.array([0.0])
    multi_tokens = sum(len(r.generated) for r in done)
    multi_tps = multi_tokens / wall_m if wall_m > 0 else 0.0
    judged = [r for r in done if getattr(r, "slo_ok", None) is not None]
    goodput = round(sum(1 for r in judged if r.slo_ok)
                    / len(judged), 4) if judged else None
    if args.journal_out and eng.journal is not None:
        eng.journal.dump_jsonl(args.journal_out)
    _dump_usage(args, eng=eng)
    out = {
        "serve_lora_adapters": args.adapters,
        "serve_lora_rank": args.adapter_rank,
        "serve_lora_tokens_per_sec": round(multi_tps, 1),
        "serve_lora_single_tenant_tokens_per_sec": round(single_tps, 1),
        "serve_lora_pct_of_single_tenant": round(
            multi_tps / single_tps, 4) if single_tps > 0 else None,
        "serve_lora_swap_count": swaps_warm
        + int(stats.counter("lora.swaps").value),
        "serve_lora_grouped_launches": int(
            stats.counter("lora.grouped_launches").value),
        "serve_lora_decode_programs": len(eng._gen._decode_k_jit),
        "serve_lora_p50_ttft_ms": round(
            float(np.percentile(ttfts, 50)), 3),
        "serve_lora_p99_ttft_ms": round(
            float(np.percentile(ttfts, 99)), 3),
        "serve_lora_goodput": goodput,
        "serve_lora_requests": len(done),
        "serve_lora_shed": sum(1 for r in rids_m if r is None),
        "serve_lora_wall_s": round(wall_m, 3),
        "telemetry": _telemetry(),
    }
    out.update(_alert_keys())
    out.update(_usage_keys(eng=eng))
    out.update(tele_out)
    # the acceptance pin: batched multi-LoRA keeps >= 80% of the
    # single-tenant throughput (the grouped delta launch is ONE kernel
    # regardless of how many adapters the chunk mixes)
    ok = out["serve_lora_pct_of_single_tenant"] is not None \
        and out["serve_lora_pct_of_single_tenant"] >= 0.8
    return out, ok


def chaos_injector(seed):
    """The seeded chaos schedule: >=5 distinct serving-hot-path sites
    (kv.grow, prefill.dispatch, decode.step, prefix.insert,
    journal.dump) across every fault kind — raises, a delay, a token
    corruption (detected, never streamed), a pool squeeze that drives
    the REAL pool-pressure recovery paths, and an injected dump
    failure proving a crash dump can't mask an original error."""
    from paddle_tpu.serving import FaultInjector

    return (FaultInjector(seed=seed)
            .add("kv.grow", kind="raise", at=2)
            .add("prefill.dispatch", kind="raise", at=1)
            .add("prefill.dispatch", kind="delay", every=13, times=2,
                 delay_ms=2.0)
            .add("decode.step", kind="raise", at=3)
            .add("decode.step", kind="corrupt", at=6)
            .add("decode.step", kind="squeeze", pages=4, at=8)
            .add("decode.step", kind="release", at=16)
            .add("prefix.insert", kind="raise", at=1)
            .add("journal.dump", kind="raise", at=0))


def run_chaos(args, reqs, base_rids, base_done, base_goodput):
    """Re-drive the measured workload against a fresh engine with the
    seeded fault schedule armed (after a fault-free warmup, so compile
    time stays out of the SLO comparison). Returns
    ``(serve_chaos_* dict, ok: bool)``."""
    from paddle_tpu.profiler import stats

    seed = args.chaos_seed if args.chaos_seed is not None \
        else args.seed
    inj = chaos_injector(seed)
    eng, lens = build_engine(args)
    if not args.no_warmup:
        warm = [(np.full((L,), 1, np.int32), 0.0) for L in lens]
        drive(eng, warm, args.max_new)
        eng.finished.clear()
        eng.slo_monitor.reset()
        if eng.journal is not None:
            eng.journal.clear()
    eng.install_faults(inj)
    sampler = _start_telemetry(args, journal=eng.journal)
    t0 = time.monotonic()
    wall, rids = drive(eng, reqs, args.max_new,
                       deadline_ms=args.deadline_ms)
    tele_out = _stop_telemetry(
        sampler, args.telemetry_out + ".chaos"
        if args.telemetry_out else None)
    done_by_id = {r.id: r for r in eng.finished}
    base_by_id = {r.id: r for r in base_done}
    # survivor parity: every request the chaos run finished in the
    # "ok" state must carry exactly the fault-free run's greedy tokens
    # (keyed by submission index — ids differ between engines)
    survivors = mismatches = 0
    failed = {"error": 0, "deadline_exceeded": 0, "shed": 0}
    for idx, rid in enumerate(rids):
        if rid is None:
            failed["shed"] += 1
            continue
        req = done_by_id.get(rid)
        if req is None:
            continue
        state = getattr(req, "state", None)
        if state == "ok":
            survivors += 1
            brid = base_rids[idx] if idx < len(base_rids) else None
            base = base_by_id.get(brid) if brid is not None else None
            if base is not None and \
                    list(base.generated) != list(req.generated):
                mismatches += 1
        elif state in failed:
            failed[state] += 1
        else:
            failed["error"] += 1
    n = max(len(rids), 1)
    judged = [r for r in done_by_id.values()
              if getattr(r, "slo_ok", None) is not None]
    goodput = round(sum(1 for r in judged if r.slo_ok)
                    / len(judged), 4) if judged else None
    total_tokens = sum(len(r.generated) for r in done_by_id.values())
    parity = 1.0 if mismatches == 0 and survivors > 0 else 0.0
    n_failed = sum(failed.values())
    # pinned goodput bound: losing goodput beyond the failed share
    # plus slack means the faults degraded SURVIVORS too
    bound_ok = True
    if base_goodput is not None and goodput is not None:
        bound_ok = goodput >= base_goodput - n_failed / n - 0.25
    # forensic dump with the journal.dump fault armed: must swallow
    # the injected failure and return None rather than raise
    dump_survived = 1
    try:
        eng.crash_dump(error=None)
    except BaseException:
        dump_survived = 0
    sites = sorted({f["site"] for f in inj.fired})
    out = {
        "serve_chaos_seed": seed,
        "serve_chaos_survivor_parity": parity,
        "serve_chaos_survivors": survivors,
        "serve_chaos_request_errors": failed["error"],
        "serve_chaos_deadline_exceeded": failed["deadline_exceeded"],
        "serve_chaos_shed": failed["shed"],
        "serve_chaos_goodput": goodput,
        "serve_chaos_goodput_bound_ok": int(bound_ok),
        "serve_chaos_tokens_per_sec": round(total_tokens / wall, 1)
        if wall > 0 else None,
        "serve_chaos_faults_injected": len(inj.fired),
        "serve_chaos_sites_fired": sites,
        "serve_chaos_step_retries": int(
            stats.counter("serving.step_retries").value),
        "serve_chaos_dump_survived": dump_survived,
        "serve_chaos_wall_s": round(time.monotonic() - t0, 3),
    }
    out.update({f"serve_chaos_{k}": v for k, v in tele_out.items()})
    ok = (parity == 1.0 and bound_ok and dump_survived == 1
          and len(sites) >= 5)
    return out, ok


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Poisson-load serving benchmark (SLO rungs)")
    ap.add_argument("--streams", type=int, default=8,
                    help="decode slots (max_batch)")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests (default 3*streams)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate, requests/sec")
    ap.add_argument("--prompt-mix", default="8,32,96",
                    help="comma list of prompt lengths, sampled "
                         "uniformly")
    ap.add_argument("--system-prompt", type=int, default=32,
                    help="shared system-prompt tokens prepended to a "
                         "fraction of requests (0 disables)")
    ap.add_argument("--system-frac", type=float, default=0.5)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--ttft-weight", type=float, default=1.0)
    ap.add_argument("--tpot-weight", type=float, default=1.0)
    ap.add_argument("--ttft-target", type=float, default=1000.0,
                    help="SLO TTFT target (ms) for per-request "
                         "verdicts and serve_goodput")
    ap.add_argument("--tpot-target", type=float, default=100.0,
                    help="SLO TPOT target (ms)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline from arrival; exceeded "
                         "-> the request aborts in the "
                         "deadline_exceeded terminal state")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative decoding: the scheduler's decode "
                         "slot runs draft+verify rounds instead of "
                         "token-by-token chunks; every serve_* key "
                         "re-emits as serve_spec_* plus "
                         "serve_spec_accept_rate (bench_gate gates "
                         "throughput/accept down, TTFT up)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft window (default: FLAGS_spec_k)")
    ap.add_argument("--spec-drafter", default="self",
                    choices=["self", "draft", "oracle"],
                    help="self = training-free self-draft heads; "
                         "draft = quarter-size draft model; oracle = "
                         "the target model as its own drafter (accept "
                         "rate 1.0 — the amortization ceiling)")
    ap.add_argument("--long-context", action="store_true",
                    help="long-context serving rung (ISSUE 13): "
                         "defaults the prompt mix to long prompts so "
                         "chunked prefill attends deep into the paged "
                         "pool through the in-place varlen kernel; "
                         "every serve_* key re-emits as serve_long_* "
                         "(gated by bench_gate: TTFT UP, tokens/s "
                         "DOWN)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="fleet mode (ISSUE 14): route the load "
                         "through a FleetRouter over N replicas (one "
                         "serve-loop thread each); emits fleet_* keys "
                         "instead of serve_*; composes with --chaos "
                         "(replica kill mid-load, zero-loss pins)")
    ap.add_argument("--drain-async", action="store_true",
                    help="with --fleet (ISSUE 19): mid-load, "
                         "gracefully drain replica 0 under "
                         "FLAGS_migrate_async — its mid-decode slots "
                         "stream complete KV pages to peers while "
                         "both endpoints keep decoding — and pin "
                         "migration-concurrent decode progress "
                         "(fleet_async_migration_* keys; nonzero "
                         "exit when no pages streamed, decode "
                         "stalled, or a request was lost)")
    ap.add_argument("--disagg", action="store_true",
                    help="with --fleet N: drive the workload on a "
                    "symmetric fleet, then again with a prefill/"
                    "decode role split + host-DRAM KV tier, and pin "
                    "that disaggregation beats the symmetric TTFT "
                    "p99 and goodput (ISSUE 20)")
    ap.add_argument("--host-tier-bytes", type=int, default=8 << 20,
                    help="per-replica host-DRAM KV tier capacity for "
                    "the --disagg run (FLAGS_kv_host_tier_bytes)")
    ap.add_argument("--disagg-reps", type=int, default=3,
                    help="measured drives per arm of the --disagg "
                    "comparison; the pin compares MEDIAN TTFT p99 "
                    "across reps (a single small-sample p99 is the "
                    "max order statistic — thread-scheduling noise "
                    "on a shared-core host swings it 2x run-to-run)")
    ap.add_argument("--fleet-policy", default="affinity",
                    choices=["affinity", "rr"],
                    help="dispatch policy: blake2b prefix-affinity + "
                         "load/SLO tie-break (default), or the "
                         "round-robin baseline it is pinned against")
    ap.add_argument("--system-prompts", type=int, default=4,
                    help="distinct system prompts in the fleet's "
                         "skewed-prefix load (Zipf-ish popularity; "
                         "each is --system-prompt tokens long)")
    ap.add_argument("--chaos", action="store_true",
                    help="re-drive the measured workload under a "
                         "seeded >=5-site fault schedule and pin "
                         "survivor token parity + bounded goodput "
                         "loss (serve_chaos_* keys; nonzero exit on "
                         "a failed pin)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="fault-schedule seed (default: --seed)")
    ap.add_argument("--adapters", type=int, default=0,
                    help="multi-LoRA workload (ISSUE 18): serve K "
                         "distinct adapters from one AdapterBank, "
                         "round-robin adapter_id per request; emits "
                         "serve_lora_* keys and pins "
                         "pct_of_single_tenant >= 0.8 (nonzero exit "
                         "on a failed pin)")
    ap.add_argument("--adapter-rank", type=int, default=8,
                    help="LoRA rank for the bench adapters (padded "
                         "to the bank's sublane tile)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="multi-tenant workload (ISSUE 17): stamp a "
                         "Zipf-popular tenant id (K distinct) on "
                         "every request and turn the per-tenant "
                         "usage ledger on; emits serve_tenant_* and "
                         "usage_unattributed_ms (the latter gated UP "
                         "by bench_gate with no noise floor)")
    ap.add_argument("--tenant-skew", type=float, default=1.0,
                    help="Zipf exponent for tenant popularity "
                         "(rank k drawn ∝ 1/(k+1)^skew; 0 = uniform)")
    ap.add_argument("--usage-out", default=None,
                    help="dump the per-request usage JSONL "
                         "(serve_top --tenants / trace_merge input); "
                         "implies the usage ledger on; fleet runs "
                         "write <path>_r<idx>.jsonl per replica plus "
                         "<path>_router.jsonl")
    ap.add_argument("--requests-out", default=None,
                    help="write per-request JSONL (id, lens, waits, "
                         "ttft/tpot, preempt/requeue counts, slo_ok) "
                         "so offline analysis never re-derives from "
                         "histograms")
    ap.add_argument("--journal-out", default=None,
                    help="dump the flight-recorder journal JSONL "
                         "(tools/serve_top.py input)")
    ap.add_argument("--telemetry-out", default=None,
                    help="continuous telemetry (ISSUE 16): sample "
                         "the stats registry on a background "
                         "TimeSeriesSampler with the default alert "
                         "rules armed during the measured run and "
                         "dump the time-series JSONL here "
                         "(serve_top --history input); a --chaos "
                         "re-drive dumps its own series to "
                         "<path>.chaos")
    ap.add_argument("--telemetry-interval-ms", type=float,
                    default=50.0,
                    help="sampling interval for --telemetry-out")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--bf16", action="store_true",
                    help="cast the stack bf16 (the chip serving dtype)")
    ap.add_argument("--quant", default=None,
                    choices=[None, "int8", "a8w8"])
    ap.add_argument("--no-warmup", action="store_true",
                    help="measure cold compiles inside the TTFTs")
    ap.add_argument("--mp", type=int, default=0,
                    help="tensor-parallel degree: shard the serving "
                         "stack over an mp mesh of that many devices "
                         "(rung keys become serve_tp{N}_*); on a CPU "
                         "run virtual devices are provisioned")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the tpu_lint preflight gate")
    args = ap.parse_args(argv)
    if args.long_context and args.prompt_mix == "8,32,96":
        # CPU-sized long mix (a chip run passes its own, e.g.
        # 2048,8192,16384 via bench.py --serve-long); long prompts +
        # a modest rate keep the run prefill-dominated
        args.prompt_mix = "64,256,768"
        args.rate = min(args.rate, 16.0)
    if args.requests is None:
        args.requests = 3 * args.streams

    import os

    if args.mp and args.mp > 1 and "jax" not in sys.modules \
            and os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # CPU runs (CI) get virtual devices for the mp mesh; must land
        # before the first jax import (backend init reads XLA_FLAGS)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.mp}"
        ).strip()

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.analysis.preflight import preflight

    preflight("serve_bench", no_lint=args.no_lint)

    if args.tenants or args.usage_out:
        # must land before any engine/router is constructed — the
        # ledger is wired (or not) at __init__
        from paddle_tpu.core.flags import set_flags

        set_flags({"usage_ledger": True})

    from paddle_tpu.profiler import stats

    if args.adapters:
        out, lora_ok = run_lora(args)
        print(json.dumps(out))
        if not lora_ok:
            print("serve_bench --adapters: batched multi-LoRA pin "
                  "FAILED (serve_lora_pct_of_single_tenant < 0.8 — "
                  "the grouped delta path is paying per-adapter "
                  "cost)", file=sys.stderr)
            sys.exit(1)
        return 0

    if args.fleet and args.fleet > 1 and args.disagg:
        out, disagg_ok = run_disagg(args)
        print(json.dumps(out))
        if not disagg_ok:
            print("serve_bench --disagg: acceptance pins FAILED "
                  "(no prefill->decode handoff streamed, a request "
                  "was lost, or the disaggregated fleet did not "
                  "beat the symmetric fleet's TTFT p99 / goodput)",
                  file=sys.stderr)
            sys.exit(1)
        return 0

    if args.fleet and args.fleet > 1:
        out, fleet_ok = run_fleet(args)
        print(json.dumps(out))
        if not fleet_ok:
            print("serve_bench --fleet: acceptance pins FAILED "
                  "(--chaos: survivor parity / lost requests / "
                  "goodput bound / failover+death accounting / site "
                  "coverage; --drain-async: no async migration "
                  "streamed, decode made no progress during the "
                  "drain, or a request was lost)", file=sys.stderr)
            sys.exit(1)
        return 0

    eng, lens = build_engine(args)
    rng = np.random.RandomState(args.seed)

    if not args.no_warmup:
        # compile every chunk/decode program shape OUTSIDE the
        # measured window (steady-state SLO; --no-warmup for the
        # cold-start view), then reset telemetry so the measured block
        # describes only the load run
        warm = [(np.full((L,), 1, np.int32), 0.0) for L in lens]
        if args.system_prompt:
            warm.append((np.full(
                (args.system_prompt + lens[0],), 1, np.int32), 0.0))
        drive(eng, warm, args.max_new)
        require_served(eng.finished, args.max_new, "warm-up")
        eng.finished.clear()
        eng.action_log.clear()
        eng.slo_monitor.reset()
        if eng.journal is not None:
            eng.journal.clear()  # the journal describes the load run
        if eng.usage is not None:
            eng.usage.reset()    # so does the usage ledger
        stats.reset()

    reqs = make_requests(args, lens, rng)
    if args.tenants:
        reqs = _assign_tenants(reqs, args, rng)
    sampler = _start_telemetry(args, journal=eng.journal)
    wall, rids = drive(eng, reqs, args.max_new,
                       deadline_ms=args.deadline_ms)
    tele_out = _stop_telemetry(sampler, args.telemetry_out)

    done = eng.finished
    if eng.journal is not None:
        eng.journal.publish_gauges()
    ttfts = np.array([r.ttft_s for r in done
                      if r.ttft_s is not None], np.float64) * 1e3
    if ttfts.size == 0:
        ttfts = np.array([0.0])
    tpots = [r.tpot_s for r in done if r.tpot_s is not None]
    total_tokens = sum(len(r.generated) for r in done)
    # SLO goodput over the WHOLE run (not the monitor's rolling
    # window): fraction of finished requests whose stamped verdict
    # met both targets — bench_gate gates this (direction "down")
    judged = [r for r in done if getattr(r, "slo_ok", None) is not None]
    goodput = round(sum(1 for r in judged if r.slo_ok) / len(judged), 4) \
        if judged else None
    if args.requests_out:
        with open(args.requests_out, "w") as f:
            for r in sorted(done, key=lambda r: r.id):
                f.write(json.dumps({
                    "id": r.id,
                    "prompt_len": int(len(r.prompt)),
                    "new_tokens": len(r.generated),
                    "queue_wait_ms": None if r.queue_wait_s is None
                    else round(r.queue_wait_s * 1e3, 3),
                    "ttft_ms": None if r.ttft_s is None
                    else round(r.ttft_s * 1e3, 3),
                    "tpot_ms": None if r.tpot_s is None
                    else round(r.tpot_s * 1e3, 3),
                    "preempts": getattr(r, "n_preempts", 0),
                    "requeues": getattr(r, "n_requeues", 0),
                    "slo_ok": getattr(r, "slo_ok", None),
                    "state": getattr(r, "state", None),
                    "error": None if getattr(r, "error", None) is None
                    else type(r.error).__name__,
                }) + "\n")
    if args.journal_out and eng.journal is not None:
        eng.journal.dump_jsonl(args.journal_out)
    if eng.usage is not None:
        eng.usage.publish_gauges()
    _dump_usage(args, eng=eng)
    out = {
        "serve_p50_ttft_ms": round(float(np.percentile(ttfts, 50)), 3),
        "serve_p99_ttft_ms": round(float(np.percentile(ttfts, 99)), 3),
        "serve_tokens_per_sec": round(total_tokens / wall, 1),
        "serve_p50_tpot_ms": round(
            float(np.median(tpots)) * 1e3, 3) if tpots else None,
        "serve_goodput": goodput,
        "serve_ttft_target_ms": args.ttft_target,
        "serve_tpot_target_ms": args.tpot_target,
        "serve_preemptions": int(
            stats.counter("serving.preemptions").value),
        "serve_streams": args.streams,
        "serve_requests": len(done),
        "serve_rate": args.rate,
        "serve_prompt_mix": args.prompt_mix,
        "serve_prefill_chunk": args.prefill_chunk,
        "serve_decode_chunk": eng.decode_chunk,
        "serve_prefix_hits": int(
            stats.counter("serving.prefix_hit").value),
        "serve_prefix_pages_saved": int(
            stats.counter("serving.prefix_pages_saved").value),
        "serve_wall_s": round(wall, 3),
        "telemetry": _telemetry(),
    }
    out.update(_alert_keys())
    out.update(_usage_keys(eng=eng))
    out.update(tele_out)
    chaos_ok = True
    if args.chaos:
        chaos_out, chaos_ok = run_chaos(args, reqs, rids, done,
                                        goodput)
        out.update(chaos_out)
    if args.speculative:
        # speculative rung keys: serve_spec_* so bench_gate tracks the
        # draft+verify SLO rungs independently of the plain serve_*
        # ones; accept rate is the amortization health signal (gated
        # DOWN — a drafter regression shows here before throughput)
        drafted = int(
            stats.counter("serving.spec_drafted_tokens").value)
        accepted = int(
            stats.counter("serving.spec_accepted_tokens").value)
        out["serve_accept_rate"] = round(accepted / drafted, 4) \
            if drafted else None
        out["serve_rounds"] = int(
            stats.counter("serving.spec_rounds").value)
        out["serve_drafter"] = args.spec_drafter
        out["serve_k"] = int(eng._spec.k)
        out = {(f"serve_spec_{k[len('serve_'):]}"
                if k.startswith("serve_") else k): v
               for k, v in out.items()}
    if args.long_context:
        # long-context rung keys: serve_long_* so bench_gate tracks
        # the varlen-prefill SLO rungs independently of the short-mix
        # serve_* ones
        out = {(f"serve_long_{k[len('serve_'):]}"
                if k.startswith("serve_") else k): v
               for k, v in out.items()}
    if args.mp and args.mp > 1:
        # TP rung keys: serve_tp{N}_* so bench_gate tracks the
        # mp-sharded SLO rungs independently of the mp1 ones (whose
        # preservation the gate checks on the plain serve_* keys)
        out = {(f"serve_tp{args.mp}_" + k[len("serve_"):]
                if k.startswith("serve_") else k): v
               for k, v in out.items()}
        out["serve_mp_degree"] = args.mp
    print(json.dumps(out))
    if not chaos_ok:
        print("serve_bench --chaos: robustness pins FAILED "
              "(survivor parity / goodput bound / dump survival / "
              "site coverage)", file=sys.stderr)
        sys.exit(1)
    if not args.chaos:
        # (--chaos injects faults on purpose; its pins judge that run)
        require_served(done, args.max_new, "load run")
    return 0


if __name__ == "__main__":
    sys.exit(main())
