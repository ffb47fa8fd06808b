"""CI gate over BENCH_*/OPBENCH_* telemetry blocks.

``tools/op_bench.py --compare`` gates op latencies; this gates the
RUNTIME-TELEMETRY side of two bench JSONs — the counters/histograms
that explain WHY a number moved (retrace storms, cache-hit-rate
collapse, compile-time blowups, roofline regressions):

    python tools/bench_gate.py PREV.json CUR.json
    python tools/bench_gate.py --tol 0.2 PREV_OPBENCH.json CUR_OPBENCH.json
    python tools/bench_gate.py --metrics jit.trace vjp_cache_hit_rate A B

Exits nonzero when any gated metric regressed by more than ``--tol``
(default 10%) between the two files. Direction is metric-aware:

- count-like metrics (``jit.trace``, ``vjp_cache.miss``, compile-time
  histogram avgs) regress UP;
- rate/utilization metrics (``vjp_cache_hit_rate``, ``roofline.mfu``,
  ``roofline.bw_util``) regress DOWN.

Telemetry blocks are discovered anywhere in the JSON under keys named
``telemetry`` / ``*_telemetry`` (bench.py nests one per rung;
op_bench.py keeps one at top level) and same-named blocks are compared
pairwise. The document ROOT is additionally treated as a block so the
serving rungs' top-level scalars (``decode_a8w8_tokens_per_sec``,
``decode_*_pct_of_hbm_roofline``, ...) gate too.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

__all__ = ["gate", "extract_telemetry", "main"]

#: metric -> direction ("up" = an increase is a regression, "down" = a
#: decrease is). The default gate set; extend via --metrics.
DEFAULT_METRICS: Dict[str, str] = {
    # a growing trace count across rounds with the same workload is a
    # retrace storm
    "jit.trace": "up",
    "vjp_cache.miss": "up",
    "vjp_cache.uncacheable": "up",
    "vjp_cache.blocklisted": "up",
    # the no-grad compiled-forward fast path (ops/dispatch.py): growing
    # misses/blocklistings under the same workload mean ops fell off the
    # fast path (a closure crept back in, or statics went unhashable)
    "fwd_cache.miss": "up",
    "fwd_cache.uncacheable": "up",
    "fwd_cache.blocklisted": "up",
    # cache effectiveness / device utilization must not collapse
    "vjp_cache_hit_rate": "down",
    "fwd_cache_hit_rate": "down",
    "roofline.mfu": "down",
    "roofline.bw_util": "down",
    # compile-time histograms gate on their mean
    "compile.vjp_trace_us": "up",
    "compile.vjp_build_us": "up",
    "compile.fwd_trace_us": "up",
    "compile.jit_build_us": "up",
    # serving decode rungs: top-level scalars of the bench JSON (the
    # gate compares the document root as its own block) — throughput
    # and %-of-roofline regress DOWN
    "decode_tokens_per_sec": "down",
    "decode_pct_of_hbm_roofline": "down",
    "decode_int8_tokens_per_sec": "down",
    "decode_int8_pct_of_hbm_roofline": "down",
    "decode_a8w8_tokens_per_sec": "down",
    "decode_a8w8_pct_of_hbm_roofline": "down",
    # grouped bf16 weight-stream decode (r6 tentpole rung): both the
    # throughput and its %-of-weight-roofline must not collapse — the
    # roofline % is the honest one (it normalizes out batch/geometry)
    "decode_bf16_grouped_tokens_per_sec": "down",
    "decode_bf16_grouped_pct_of_hbm_roofline": "down",
    "decode_int8kv_b64_tokens_per_sec": "down",
    # tensor-parallel serving rungs (ISSUE 10, mp2 canonical): the
    # mp-sharded decode/serve throughput regresses DOWN like its mp1
    # siblings — whose unchanged keys above ARE the mp1-throughput-
    # preserved check (TP must not slow the single-chip path)
    "decode_tp2_tokens_per_sec": "down",
    "decode_tp2_pct_of_hbm_roofline": "down",
    "serve_tp2_tokens_per_sec": "down",
    "serve_tp2_p50_ttft_ms": "up",
    "serve_tp2_p99_ttft_ms": "up",
    "serve_tp2_p50_tpot_ms": "up",
    "serve_tp2_goodput": "down",
    # serving-frontend SLO rungs (tools/serve_bench.py): latency
    # percentiles regress UP, delivered throughput DOWN
    "serve_p50_ttft_ms": "up",
    "serve_p99_ttft_ms": "up",
    "serve_p50_tpot_ms": "up",
    "serve_tokens_per_sec": "down",
    # SLO goodput (fraction of finished requests meeting both the
    # TTFT and TPOT targets): both the bench's whole-run scalar and
    # the slo.goodput rolling telemetry gauge regress DOWN
    "serve_goodput": "down",
    "slo.goodput": "down",
    # speculative-decoding rungs (ISSUE 12): delivered throughput and
    # the draft accept rate regress DOWN (a drafter/verify regression
    # shows in accept rate before it shows in tokens/s), TTFT UP like
    # its non-speculative sibling; decode_spec_* is the engine-level
    # acceptance-ceiling rung (bench.py --decode-spec)
    "serve_spec_tokens_per_sec": "down",
    "serve_spec_accept_rate": "down",
    "serve_spec_p50_ttft_ms": "up",
    "serve_spec_p99_ttft_ms": "up",
    "serve_spec_goodput": "down",
    "decode_spec_tokens_per_sec": "down",
    "decode_spec_accept_rate": "down",
    "decode_spec_vs_plain": "down",
    # varlen / long-context attention rungs (ISSUE 13): the packed
    # block-skipping kernel's throughput regresses DOWN and its
    # compiled-program peak bytes UP (the O(T·d) memory pin — a
    # regression back toward the dense path shows here first); the
    # long-context serving rung gates like its short-mix sibling
    "attn_varlen_tokens_per_sec": "down",
    "attn_varlen_peak_bytes": "up",
    "serve_long_p50_ttft_ms": "up",
    "serve_long_p99_ttft_ms": "up",
    "serve_long_p50_tpot_ms": "up",
    "serve_long_tokens_per_sec": "down",
    "serve_long_goodput": "down",
    # chaos-hardened serving rungs (tools/serve_bench.py --chaos,
    # ISSUE 11): survivor token parity is binary and must stay 1.0,
    # chaos goodput/throughput regress DOWN like their fault-free
    # siblings, and request errors under the SAME seeded fault
    # schedule regress UP (more requests dying per injected fault =
    # the isolation got leakier)
    "serve_chaos_survivor_parity": "down",
    "serve_chaos_goodput": "down",
    "serve_chaos_tokens_per_sec": "down",
    "serve_chaos_request_errors": "up",
    # fleet serving rungs (tools/serve_bench.py --fleet, ISSUE 14):
    # routed goodput/throughput regress DOWN and latency UP like the
    # single-replica serve_* siblings; failovers/hedges in the
    # FAULT-FREE fleet run regress UP (any appearing = replicas are
    # falsely suspected/dying under clean load); under the seeded
    # chaos schedule survivor parity is binary (must stay 1.0), lost
    # requests regress UP (the zero-loss failover pin), and chaos
    # goodput/throughput regress DOWN
    "fleet_goodput": "down",
    "fleet_tokens_per_sec": "down",
    "fleet_p50_ttft_ms": "up",
    "fleet_p99_ttft_ms": "up",
    "fleet_failovers": "up",
    "fleet_hedges": "up",
    "fleet_chaos_survivor_parity": "down",
    "fleet_chaos_lost": "up",
    "fleet_chaos_request_errors": "up",
    "fleet_chaos_goodput": "down",
    "fleet_chaos_tokens_per_sec": "down",
    # MoE rungs (ISSUE 15): no-drop train/decode throughput and the
    # activated-FLOPs MFU regress DOWN; moe.dropped_tokens (inside the
    # rung telemetry) regresses UP with NO noise floor — the rung runs
    # in no-drop mode, so a single dropped token is a broken ragged
    # path, not jitter (strict-compared like the lint counters)
    "moe_train_tokens_per_sec": "down",
    "moe_train_mfu": "down",
    "moe_decode_tokens_per_sec": "down",
    "moe.dropped_tokens": "up",
    # static-analysis state the numbers were measured under: the
    # finding count must only go DOWN between rounds, so any growth
    # regresses (direction "up" = an increase fails the gate); gates
    # both the lint.findings counter inside telemetry blocks and a
    # top-level lint_findings scalar
    "lint.findings": "up",
    "lint_findings": "up",
    # continuous telemetry (ISSUE 16): the serving-time attribution's
    # host-overhead residual regresses UP (bookkeeping creep the
    # phase split exists to expose), and alert_fired regresses UP
    # with NO noise floor — the measured rung is a healthy steady
    # state, so a run that starts firing alerts is a regression
    # however small the count (strict-compared like lint)
    "serve_step_host_overhead_ms": "up",
    "alert_fired": "up",
    "alert.fired": "up",
    # batched multi-LoRA serving rungs (tools/serve_bench.py
    # --adapters, ISSUE 18): delivered multi-adapter throughput and
    # its ratio to the single-tenant baseline regress DOWN (the ratio
    # is the honest one — it cancels host noise and pins the grouped
    # delta launch staying ONE kernel however many adapters the chunk
    # mixes); TTFT UP like the plain serve_* siblings; the compiled
    # decode-program count regresses UP (programs scaling with the
    # adapter set is a retrace leak however small)
    "serve_lora_tokens_per_sec": "down",
    "serve_lora_pct_of_single_tenant": "down",
    "serve_lora_p50_ttft_ms": "up",
    "serve_lora_p99_ttft_ms": "up",
    "serve_lora_goodput": "down",
    "serve_lora_decode_programs": "up",
    # per-tenant usage metering (ISSUE 17): one tenant's share of
    # attributed device time regresses UP (a hog crowding out the
    # rest of the mix), and usage_unattributed_ms regresses UP with
    # NO noise floor — device time the ledger failed to attribute is
    # an accounting leak however small (strict-compared like lint)
    "serve_tenant_max_share": "up",
    "usage_unattributed_ms": "up",
    # collective-overlap rungs (ISSUE 19): the ring-overlapped mp2
    # decode and the double-buffered ep2 MoE decode regress DOWN like
    # their blocking-psum siblings (overlap that stops paying shows
    # here first); migration-concurrent drain: decode tokens delivered
    # DURING the drain window regress DOWN (the overlap eroding back
    # toward stop-the-world), per-step join stall UP, and lost
    # requests UP with NO noise floor — a single request dropped by an
    # async migration is a broken re-home, not jitter
    "decode_tp2_overlap_tokens_per_sec": "down",
    "decode_tp2_overlap_pct_of_hbm_roofline": "down",
    "moe_decode_ep2_overlap_tokens_per_sec": "down",
    "fleet_async_migration_decode_tokens": "down",
    "fleet_async_migration_stall_ms": "up",
    "fleet_async_migration_lost": "up",
    # disaggregated prefill/decode fleet + tiered KV (ISSUE 20): the
    # role-split fleet's TTFT tail regresses UP and its goodput /
    # throughput DOWN like every serve sibling; lost requests UP with
    # NO noise floor (a handoff that drops a request is a broken
    # re-home); handoffs regress DOWN — the rung's workload is built
    # to stream them, so a run with fewer is the prefill fleet
    # stalling its hand-offs, not jitter
    "serve_disagg_p50_ttft_ms": "up",
    "serve_disagg_p99_ttft_ms": "up",
    "serve_disagg_tokens_per_sec": "down",
    "serve_disagg_goodput": "down",
    "serve_disagg_lost": "up",
    "serve_disagg_handoffs": "down",
}

#: absolute-change floors so tiny counts/latencies don't trip the
#: relative gate on noise
_ABS_FLOOR_COUNT = 3.0
_ABS_FLOOR_US = 10.0


def extract_telemetry(doc: dict, prefix: str = "") -> Dict[str, dict]:
    """Every telemetry block in the JSON, keyed by its path — bench.py
    emits ``telemetry`` and ``decode_telemetry``, op_bench.py a
    top-level ``telemetry``."""
    out: Dict[str, dict] = {}
    if not isinstance(doc, dict):
        return out
    for k, v in doc.items():
        path = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict):
            if k == "telemetry" or k.endswith("_telemetry"):
                out[path] = v
            else:
                out.update(extract_telemetry(v, path))
    return out


def _scalar_blocks(doc: dict, metrics: Dict[str, str],
                   prefix: str = "") -> Dict[str, dict]:
    """Dicts anywhere in the JSON that carry a gated metric as a direct
    scalar key (bench.py's serving rungs live at the document root, or
    under a ``parsed`` wrapper in an archived record)."""
    out: Dict[str, dict] = {}
    if not isinstance(doc, dict):
        return out
    if any(isinstance(doc.get(m), (int, float)) for m in metrics):
        out[prefix or "<root>"] = doc
    for k, v in doc.items():
        if isinstance(v, dict) and k != "telemetry" \
                and not k.endswith("_telemetry"):
            out.update(_scalar_blocks(
                v, metrics, f"{prefix}.{k}" if prefix else k))
    return out


def _metric_value(block: dict, name: str) -> Optional[float]:
    """Find ``name`` in a telemetry block: counters, gauges, top-level
    scalars (vjp_cache_hit_rate), or histogram means."""
    for section in ("counters", "gauges"):
        v = block.get(section, {}).get(name)
        if v is not None:
            return float(v)
    v = block.get(name)
    if isinstance(v, (int, float)):
        return float(v)
    h = block.get("histograms", {}).get(name)
    if isinstance(h, dict) and h.get("count"):
        return float(h.get("avg", 0.0))
    return None


def _regressed(name: str, direction: str, prev: float, cur: float,
               tol: float) -> bool:
    if name.startswith(("lint", "alert", "usage")) \
            or name in ("moe.dropped_tokens",
                        "fleet_async_migration_lost",
                        "serve_disagg_lost"):
        # lint findings, alert fires, unattributed device time,
        # no-drop-mode dropped tokens, and requests lost across an
        # async migration must only go down between rounds — ANY
        # growth regresses, no noise floor (a single new finding /
        # alert / unattributed ms / dropped token / lost request is a
        # real defect, not measurement jitter)
        return cur > prev if direction == "up" else cur < prev
    floor = _ABS_FLOOR_US if name.endswith("_us") else _ABS_FLOOR_COUNT
    if direction == "up":
        return cur > max(prev * (1 + tol), prev + floor)
    # "down": rates in [0, 1] — relative drop with a small abs floor
    return cur < min(prev * (1 - tol), prev - 0.01)


def gate(prev_doc: dict, cur_doc: dict,
         metrics: Optional[Dict[str, str]] = None,
         tol: float = 0.10) -> Tuple[List[str], int]:
    """(regression lines, #compared). Same-path telemetry blocks are
    compared metric-by-metric; blocks present on only one side are
    skipped (a new rung is not a regression)."""
    metrics = metrics or DEFAULT_METRICS
    prev_blocks = extract_telemetry(prev_doc)
    cur_blocks = extract_telemetry(cur_doc)
    # scalar rung metrics (decode_*_tokens_per_sec, *_pct_of_hbm_
    # roofline) live OUTSIDE telemetry blocks — gate the dicts that
    # carry them too, so a throughput collapse fails as loudly
    for name, blk in _scalar_blocks(prev_doc, metrics).items():
        prev_blocks.setdefault(name, blk)
    for name, blk in _scalar_blocks(cur_doc, metrics).items():
        cur_blocks.setdefault(name, blk)
    bad: List[str] = []
    compared = 0
    for path in sorted(set(prev_blocks) & set(cur_blocks)):
        pb, cb = prev_blocks[path], cur_blocks[path]
        for name, direction in metrics.items():
            p, c = _metric_value(pb, name), _metric_value(cb, name)
            if p is None or c is None:
                continue
            compared += 1
            if _regressed(name, direction, p, c, tol):
                arrow = "+" if c > p else "-"
                delta = (100.0 * (c / p - 1.0)) if p else float("inf")
                bad.append(f"{path}:{name}: {p:g} -> {c:g} "
                           f"({arrow}{abs(delta):.0f}%, "
                           f"regress-{direction})")
    return bad, compared


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="gate the telemetry blocks of two BENCH_*/"
                    "OPBENCH_* JSONs (nonzero exit on regression)")
    ap.add_argument("prev", help="previous round's JSON")
    ap.add_argument("cur", help="current round's JSON")
    ap.add_argument("--tol", type=float, default=0.10,
                    help="relative regression tolerance (default 0.10)")
    ap.add_argument("--metrics", nargs="*", default=None,
                    help="explicit metric names to gate (direction "
                         "taken from the default table; unknown names "
                         "gate 'up')")
    args = ap.parse_args(argv)

    with open(args.prev) as f:
        prev_doc = json.load(f)
    with open(args.cur) as f:
        cur_doc = json.load(f)
    metrics = None
    if args.metrics:
        metrics = {m: DEFAULT_METRICS.get(m, "up") for m in args.metrics}
    bad, compared = gate(prev_doc, cur_doc, metrics, args.tol)
    if not compared:
        print("bench_gate: no comparable telemetry metrics found "
              "(missing telemetry blocks?)", file=sys.stderr)
        return 2
    if bad:
        print(f"bench_gate REGRESSIONS (> {100 * args.tol:.0f}%):")
        for line in bad:
            print(" ", line)
        return 1
    print(f"bench_gate: no telemetry regressions "
          f"({compared} metrics compared)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
