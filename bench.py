"""Benchmark: whole-step-compiled GPT training throughput on the real chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

North-star-shaped (BASELINE.md: GPT-3 1.3B pretraining tokens/sec/chip):
trains the largest GPT config from the ladder below that fits one chip,
in AMP O2 (bf16 params + fp32 master weights, the reference's O2
semantics) with per-block recompute and the whole step (fwd+bwd+AdamW)
compiled to one XLA program.

Honest accounting:
- value     = tokens/sec on the real chip
- mfu       = value * model_flops_per_token / chip peak bf16 FLOPs
              (flops/token = 6N + 12*L*s*d: dense params fwd+bwd plus
              attention scores/values matmuls)
- vs_baseline = mfu / 0.40 — the anchor is a FLOPs-derived target (40%
  MFU, a strong single-chip GPT utilization), NOT a previous round's own
  measurement. vs_baseline >= 1.0 means the chip is doing >= 40% of its
  peak math on model FLOPs.

Processes: a chip belongs to one process at a time. The parent started by
``python bench.py`` never initialises a JAX backend: it asks a short-lived
child whether there is a TPU (``--probe``), runs the tpu_lint preflight in
a child pinned to the CPU, then hands the chip to one rung child after
another and merges their JSON lines. Off-chip every entry exits non-zero
and prints no metric; tests drive the ``run_*`` rung functions directly.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

# (name, d_model, n_layers, n_heads, seq, batch, opt_kwargs)
# 1.3B memory/MFU recipe (ablations in bench_profile.json):
# - Adam fp32 moments alone are 10.4GB; with bf16 params + fp32 master
#   that overflows 16GB HBM -> bf16 moments (fp32 compute in the rule)
#   + master-free stochastic-rounding updates cut state to 7.8GB
# - which lets the step run with NO activation recompute (full remat
#   costs an extra forward, ~25% of the step)
# - bf16 cross-entropy (fp32 accumulation inside the reductions) avoids
#   materializing the [b*s, 51200] fp32 logits copy
_FAST = {"moment_dtype": "bfloat16", "stochastic_rounding": True,
         "no_master": True, "remat": "none", "ce_bf16": True}
LADDER = [
    ("gpt3-1.3b", 2048, 24, 16, 1024, 4, dict(_FAST)),
    ("gpt-760m", 1536, 24, 16, 1024, 8, dict(_FAST)),
    ("gpt-350m", 1024, 24, 16, 1024, 8, dict(_FAST)),
]
# canonical GPT-3 1.3B context (BASELINE configs[3]): same tokens/step
# as the s1024 rung (b*s = 4096); reported as the s2048_* keys
S2048 = ("gpt3-1.3b-s2048", 2048, 24, 16, 2048, 2, dict(_FAST))
VOCAB = 51200
TARGET_MFU = 0.40


def _chip_peak(device) -> float:
    """Peak bf16 FLOP/s of the chip (the one table in
    paddle_tpu/device/chip.py; an unknown device_kind raises)."""
    from paddle_tpu.device.chip import chip_spec

    return chip_spec(device).peak_bf16_flops


def _telemetry():
    """Runtime-telemetry block embedded into BENCH_*.json: the
    profiler.stats registry snapshot for THIS process (per-op dispatch
    counts, VJP-cache/jit-cache outcomes, compile-time histograms, pool
    gauges) plus the per-program cost-model roofline table. Each rung
    runs in its own subprocess, so the block describes exactly that
    rung's work."""
    from paddle_tpu.profiler import roofline, stats

    snap = stats.snapshot()
    ops = {k: v for k, v in snap["counters"].items()
           if k.startswith("op.")}
    out = {
        "op_calls_top": dict(sorted(ops.items(),
                                    key=lambda kv: -kv[1])[:20]),
        "counters": {k: v for k, v in snap["counters"].items()
                     if not k.startswith("op.")},
        "gauges": snap["gauges"],
        "histograms": snap["histograms"],
    }
    hr = stats.vjp_cache_hit_rate()
    if hr is not None:
        out["vjp_cache_hit_rate"] = round(hr, 4)
    rl = roofline.report()
    if rl:
        out["roofline"] = rl
    return out


def build_model(d_model, n_layers, n_heads, seq, recompute=True,
                remat="full"):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F

    if remat == "dots":
        import jax

        remat_policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        remat_policy = None
    if remat == "none":
        recompute = False

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.ln1 = nn.LayerNorm(d_model)
            self.qkv = nn.Linear(d_model, 3 * d_model)
            self.proj = nn.Linear(d_model, d_model)
            self.ln2 = nn.LayerNorm(d_model)
            self.fc1 = nn.Linear(d_model, 4 * d_model)
            self.fc2 = nn.Linear(4 * d_model, d_model)

        def forward(self, x):
            b, s, _ = x.shape
            h = self.ln1(x)
            qkv = self.qkv(h).reshape(
                [b, s, 3, n_heads, d_model // n_heads])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            att = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            x = x + self.proj(att.reshape([b, s, d_model]))
            return x + self.fc2(F.gelu(self.fc1(self.ln2(x))))

    class GPT(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(VOCAB, d_model)
            self.pos = nn.Embedding(seq, d_model)
            self.blocks = nn.LayerList([Block() for _ in range(n_layers)])
            self.norm = nn.LayerNorm(d_model)
            self.head = nn.Linear(d_model, VOCAB, bias_attr=False)

        def forward(self, ids, pos_ids):
            from paddle_tpu.distributed.fleet.recompute import recompute \
                as rc

            h = self.embed(ids) + self.pos(pos_ids)
            for blk in self.blocks:
                h = rc(blk, h, policy=remat_policy) if recompute else blk(h)
            return self.head(self.norm(h))

    return GPT()


def build_train_step(d_model, n_layers, n_heads, seq, batch,
                     opt_kwargs=None, seed=0):
    """The training recipe of a LADDER row as a ready ``TrainStep`` plus
    one fixed seeded batch: ``(model, step, ids, pos, labels)``. Shared
    by the timed rung below and by ``chip_smoke.py``'s train phase, so
    the smoke drives exactly the program the benchmark times."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    opt_kwargs = dict(opt_kwargs or {})
    master = not opt_kwargs.pop("no_master", False)
    remat = opt_kwargs.pop("remat", "full")
    ce_bf16 = opt_kwargs.pop("ce_bf16", False)
    paddle.seed(seed)
    model = build_model(d_model, n_layers, n_heads, seq, remat=remat)
    opt = paddle.optimizer.AdamW(
        1e-4, parameters=model.parameters(), weight_decay=0.01,
        **opt_kwargs)
    # AMP O2: bf16 params (norms stay fp32) + fp32 master weights
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16",
                                     master_weight=master)

    def loss_fn(logits, labels):
        # fp32 CE materializes a [b*s, 51200] fp32 logits copy (~1.7GB
        # at b8) — the bf16 path keeps logits in bf16 (log-softmax max-
        # subtraction is exact in bf16; the reduction accumulates fp32)
        flat = logits.reshape([-1, VOCAB])
        if not ce_bf16:
            flat = flat.astype("float32")
        return F.cross_entropy(flat, labels.reshape([-1]))

    step = paddle.jit.TrainStep(model, loss_fn, opt)

    rng = np.random.RandomState(seed)
    ids = paddle.to_tensor(rng.randint(0, VOCAB, (batch, seq)))
    pos = paddle.to_tensor(np.tile(np.arange(seq), (batch, 1)))
    labels = paddle.to_tensor(rng.randint(0, VOCAB, (batch, seq)))
    return model, step, ids, pos, labels


def run_config(name, d_model, n_layers, n_heads, seq, batch, steps,
               opt_kwargs=None):
    model, step, ids, pos, labels = build_train_step(
        d_model, n_layers, n_heads, seq, batch, opt_kwargs)

    loss = step([ids, pos], [labels])  # compile
    _ = float(loss.numpy())

    # Timing: steps chain through the donated parameter buffers, and the
    # final scalar FETCH forces execution. Two windows, best-of: the
    # first window can absorb host-settling noise right after heavy CPU
    # work.
    dt = float("inf")
    for _window in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step([ids, pos], [labels])
        final = float(loss.numpy())
        dt = min(dt, time.perf_counter() - t0)
    if not np.isfinite(final):
        raise RuntimeError(f"{name}: non-finite loss")

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tokens_per_sec = steps * batch * seq / dt
    flops_per_token = 6 * n_params + 12 * n_layers * seq * d_model
    # cost-model roofline for the compiled step (XLA's own flops/bytes
    # accounting, not the 6N+12Lsd estimate), from the honestly timed
    # best window — printed per program instead of a hand-waved %
    rl = step.roofline(dt / steps)
    roofline = rl.as_dict() if rl is not None else None
    if rl is not None:
        print(rl.format(), file=sys.stderr)
    return tokens_per_sec, n_params, flops_per_token, roofline


def _chip_hbm_bw(device) -> float:
    """HBM bytes/s of the chip — the decode roofline denominator (same
    table as ``_chip_peak``)."""
    from paddle_tpu.device.chip import chip_spec

    return chip_spec(device).hbm_bytes_per_s


def run_decode_bench(batch=32, prompt=128, new_tokens=129,
                     d_model=2048, n_layers=24, n_heads=16,
                     decode_chunk=None, quant=None, kv_dtype=None,
                     mp_degree=None):
    # Flagship-comparable serving rung: the decode model matches the
    # gpt3-1.3b training rung (d2048 L24). Round-4 redesign (each step
    # diagnosed by ablation + HLO inspection before PR 1):
    # - layer-FOLDED paged pool updated IN PLACE via fori_loop carry
    #   (the r3 scan xs->ys shuttle copied the whole pool every token:
    #   10.8ms/step of pure copy)
    # - XLA gather attention (the stock Pallas kernel imposes a cache
    #   layout the page scatter hates -> 2 full-pool layout copies per
    #   layer per token; measured 220 tok/s vs 1662)
    # - bf16 compute end-to-end + pre-transposed bf16 lm head with fp32
    #   accumulation; KV pool bf16
    # - batch 32 measured best (b16: 1662, b32: 2504, b64 regresses as
    #   KV gather reads outgrow the weight-stream amortization)
    # - decode_chunk: engine auto-picks 128 (one scan program for the
    #   whole generation: chunk-boundary pool relayout + host sync
    #   amortize; 64 -> 128 measured +7%)
    # - quant="int8" additionally halves weight reads via per-channel
    #   weight-only int8 (scales applied on matmul outputs)
    # - quant="a8w8" also quantizes ACTIVATIONS per token into
    #   int8 x int8 MXU matmuls with one accumulator dequant — removes
    #   the bf16-activation dequant round from the streamed weights
    """Serving decode throughput through inference.GenerationEngine
    (greedy, scan-chunked). Returns (tokens/sec, % of the HBM
    weight-bandwidth roofline).

    TPU targets for the next real-chip run (VERDICT r5 round-4 bar):
    int8/a8w8 decode >= 1.6x bf16 decode tokens/sec, and bf16 b32
    >= 50% of the weight-bandwidth roofline — the a8w8 rung exists
    precisely to close the int8 gap (weight-only int8 measured just
    1.18x bf16 because the skinny matmuls still computed bf16)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import FusedCausalLM, GenerationEngine

    paddle.seed(0)
    model = FusedCausalLM(
        vocab_size=VOCAB, embed_dim=d_model, num_heads=n_heads,
        dim_feedforward=4 * d_model, num_layers=n_layers,
        max_position=prompt + new_tokens + 1)
    st = model.stack
    for n in ("qkv_weight", "qkv_bias", "out_weight", "out_bias",
              "ffn1_weight", "ffn1_bias", "ffn2_weight", "ffn2_bias"):
        p = getattr(st, n)
        p._rebind(p._data.astype(jnp.bfloat16))
    engine = GenerationEngine(model, page_size=16,
                              max_length=prompt + new_tokens,
                              decode_chunk=decode_chunk,
                              kv_dtype=kv_dtype, quant=quant,
                              mp_degree=mp_degree)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, VOCAB, (batch, prompt))
    # warmup with the SAME token count: compiles prefill + every chunk-k
    engine.generate(ids, max_new_tokens=new_tokens)
    t0 = time.perf_counter()
    out = engine.generate(ids, max_new_tokens=new_tokens)
    dt = time.perf_counter() - t0
    assert out.shape == (batch, prompt + new_tokens)
    tps = batch * new_tokens / dt
    # honest roofline: every decode step must read the full weight
    # stream (stack + lm head) once from HBM; tokens/step = batch.
    # Under TP each chip streams only its 1/mp stack slice (the lm
    # head stays replicated), so the per-chip weight floor shrinks
    # accordingly — mp1-throughput preservation is gated on the
    # EXISTING rungs, this roofline is the per-chip TP bar.
    mp = mp_degree or 1
    weight_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in st._stack().values()) / mp + \
        int(np.prod(engine._head_t.shape)) * engine._head_t.dtype.itemsize
    import jax

    roofline_tps = batch * _chip_hbm_bw(jax.devices()[0]) / weight_bytes
    # cost-model roofline: the decode/prefill programs recorded XLA's
    # flops/bytes at compile time and the engine analyzed each synced
    # decode chunk, so this block carries MEASURED achieved bytes/s and
    # bandwidth utilization per program (vs the analytic weight-stream
    # % above, which only counts weight reads)
    from paddle_tpu.profiler import roofline as _rl

    cost_roofline = {k: v for k, v in _rl.report().items()
                     if k.startswith(("decode", "prefill"))}
    return tps, round(100 * tps / roofline_tps, 1), cost_roofline


def run_decode_spec_bench(batch=8, prompt=128, new_tokens=128,
                          d_model=2048, n_layers=24, n_heads=16,
                          spec_k=4):
    """Speculative-decoding amortization rung (ISSUE 12): the SAME
    greedy workload through ContinuousBatchingEngine twice — plain
    token-by-token decode, then speculative with a ScheduledDrafter
    replaying the recorded greedy streams (accept rate 1.0 by
    construction: the acceptance CEILING, isolating pure verify
    amortization — one streamed pass per k+1 tokens instead of per
    token). Returns (tps_spec, tps_plain, accept_rate, rounds).
    Greedy parity between the two runs is asserted, not assumed."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import (ContinuousBatchingEngine,
                                      FusedCausalLM, ScheduledDrafter)
    from paddle_tpu.profiler import stats

    def build_model():
        paddle.seed(0)
        model = FusedCausalLM(
            vocab_size=VOCAB, embed_dim=d_model, num_heads=n_heads,
            dim_feedforward=4 * d_model, num_layers=n_layers,
            max_position=prompt + new_tokens + 1)
        st = model.stack
        for n in ("qkv_weight", "qkv_bias", "out_weight", "out_bias",
                  "ffn1_weight", "ffn1_bias", "ffn2_weight",
                  "ffn2_bias"):
            p = getattr(st, n)
            p._rebind(p._data.astype(jnp.bfloat16))
        return model

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, VOCAB, (prompt,)) for _ in range(batch)]

    def drive(engine):
        rids = [engine.submit(p, max_new_tokens=new_tokens)
                for p in prompts]
        t0 = time.perf_counter()
        engine.run()
        dt = time.perf_counter() - t0
        by = {r.id: list(r.generated) for r in engine.finished}
        return dt, [by[r] for r in rids]

    kw = dict(max_batch=batch, page_size=16,
              max_length=prompt + new_tokens)
    plain = ContinuousBatchingEngine(build_model(), **kw)
    drive(plain)                      # warmup: compiles live here
    dt_plain, streams = drive(plain)

    expected = {np.asarray(p, np.int32).tobytes(): s
                for p, s in zip(prompts, streams)}
    drafter = ScheduledDrafter(
        lambda req: expected[np.asarray(req.prompt).tobytes()])
    spec = ContinuousBatchingEngine(
        build_model(), speculative=drafter, spec_k=spec_k, **kw)
    drive(spec)                       # warmup
    stats.reset()
    dt_spec, spec_streams = drive(spec)
    if spec_streams != streams:
        raise RuntimeError(
            "decode-spec rung: speculative tokens diverged from the "
            "plain greedy streams (parity violation)")
    drafted = stats.counter("serving.spec_drafted_tokens").value
    accepted = stats.counter("serving.spec_accepted_tokens").value
    rounds = stats.counter("serving.spec_rounds").value
    total = sum(len(s) for s in streams)
    return (total / dt_spec, total / dt_plain,
            (accepted / drafted) if drafted else None, int(rounds))


def build_moe_model(d_model, n_layers, n_heads, seq, num_experts,
                    top_k=2):
    """GPT with the dense FFN replaced by a NO-DROP MoELayer
    (capacity_factor=None → the ragged grouped-GEMM path, ISSUE 15)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.incubate.moe import MoELayer

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.ln1 = nn.LayerNorm(d_model)
            self.qkv = nn.Linear(d_model, 3 * d_model)
            self.proj = nn.Linear(d_model, d_model)
            self.ln2 = nn.LayerNorm(d_model)
            self.moe = MoELayer(d_model, num_experts=num_experts,
                                gate="gshard", top_k=top_k,
                                d_hidden=4 * d_model,
                                capacity_factor=None)

        def forward(self, x):
            b, s, _ = x.shape
            h = self.ln1(x)
            qkv = self.qkv(h).reshape(
                [b, s, 3, n_heads, d_model // n_heads])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            att = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            x = x + self.proj(att.reshape([b, s, d_model]))
            return x + self.moe(self.ln2(x))

    class GPTMoE(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(VOCAB, d_model)
            self.pos = nn.Embedding(seq, d_model)
            self.blocks = nn.LayerList([Block() for _ in range(n_layers)])
            self.norm = nn.LayerNorm(d_model)
            self.head = nn.Linear(d_model, VOCAB, bias_attr=False)

        def forward(self, ids, pos_ids):
            h = self.embed(ids) + self.pos(pos_ids)
            for blk in self.blocks:
                h = blk(h)
            return self.head(self.norm(h))

    return GPTMoE()


def run_moe_train_bench(d_model, n_layers, n_heads, seq, batch,
                        num_experts, top_k=2, steps=8):
    """No-drop MoE training rung: whole-step-compiled GPT-MoE, AMP O2.
    Returns (tokens/s, mfu, activated params, total params). MFU
    charges the ACTIVATED FLOPs (dense params + top_k/E of the expert
    FFN bank) — the honest MoE utilization accounting."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.profiler import stats as _stats

    paddle.seed(0)
    model = build_moe_model(d_model, n_layers, n_heads, seq,
                            num_experts, top_k)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 weight_decay=0.01,
                                 moment_dtype="bfloat16")
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16")

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, VOCAB]),
                               labels.reshape([-1]))

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, VOCAB, (batch, seq)))
    pos = paddle.to_tensor(np.tile(np.arange(seq), (batch, 1)))
    labels = paddle.to_tensor(rng.randint(0, VOCAB, (batch, seq)))

    # one EAGER forward first: stamps the data-dependent moe.* routing
    # telemetry (tokens_per_expert / imbalance / dropped_tokens) that
    # the traced step cannot — then assert the no-drop pin held
    drop0 = _stats.counter("moe.dropped_tokens").value
    model(ids, pos)
    if _stats.counter("moe.dropped_tokens").value != drop0:
        raise RuntimeError("moe-train rung: no-drop mode dropped "
                           "tokens (moe.dropped_tokens moved)")

    loss = step([ids, pos], [labels])  # compile
    _ = float(loss.numpy())
    t0 = time.perf_counter()
    for _i in range(steps):
        loss = step([ids, pos], [labels])
    final = float(loss.numpy())
    dt = time.perf_counter() - t0
    if not np.isfinite(final):
        raise RuntimeError("moe-train rung: non-finite loss")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # expert FFN bank: E * (w1 + b1 + w2 + b2) per block; only top_k/E
    # of it is activated per token
    dff = 4 * d_model
    bank = n_layers * num_experts * (2 * d_model * dff + dff + d_model)
    n_active = n_params - bank + bank * top_k // num_experts
    tps = steps * batch * seq / dt
    flops_per_token = 6 * n_active + 12 * n_layers * seq * d_model
    mfu = tps * flops_per_token / _chip_peak(jax.devices()[0])
    return tps, round(mfu, 4), n_active, n_params


def run_moe_decode_bench(batch=32, prompt=128, new_tokens=65,
                         d_model=1024, n_layers=12, n_heads=16,
                         num_experts=8, top_k=2, ep_degree=None):
    """MoE serving decode rung: FusedCausalLM with the expert-bank FFN
    through GenerationEngine (the no-drop ragged MoE FFN per layer).
    Returns (tokens/s, total stack params)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import FusedCausalLM, GenerationEngine

    paddle.seed(0)
    model = FusedCausalLM(
        vocab_size=VOCAB, embed_dim=d_model, num_heads=n_heads,
        dim_feedforward=4 * d_model, num_layers=n_layers,
        max_position=prompt + new_tokens + 1,
        moe_num_experts=num_experts, moe_top_k=top_k)
    st = model.stack
    for n, p in st.named_parameters():
        if "weight" in n or n.startswith(("moe_w", "gate")):
            p._rebind(p._data.astype(jnp.bfloat16))
    engine = GenerationEngine(model, page_size=16,
                              max_length=prompt + new_tokens,
                              ep_degree=ep_degree)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, VOCAB, (batch, prompt))
    engine.generate(ids, max_new_tokens=new_tokens)   # warmup/compile
    t0 = time.perf_counter()
    out = engine.generate(ids, max_new_tokens=new_tokens)
    dt = time.perf_counter() - t0
    assert out.shape == (batch, prompt + new_tokens)
    n_params = sum(int(np.prod(p.shape)) for _n, p in
                   st.named_parameters())
    return batch * new_tokens / dt, n_params


def run_bert_bench(batch=32, seq=512, steps=8):
    """BERT-base pretraining rung (BASELINE configs[2]): MLM+NSP whole-
    step compiled, AMP O2 bf16, single chip. Returns (tokens/s, mfu).
    batch 32 re-validated after the r5 RNG/CE fixes: b64 only paid when
    threefry dropout + gather-CE dominated the step (they amortize with
    batch); with hardware-RBG dropout masks and the fused closed-form
    CE, b32 measures 90.7k tok/s vs b64's 79.9k (tools/bert_profile)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.text.models import (BertForPretraining,
                                        BertPretrainingCriterion,
                                        bert_base)

    paddle.seed(0)
    # attention-probs dropout off → flash attention path (the modern
    # BERT recipe; dropout inside attention forces a materialized
    # [b,h,s,s] softmax that cost 6x in an earlier-round chip record, removed
    # in PR 24 — not measured on today's code)
    model = BertForPretraining(
        bert_base(max_position_embeddings=seq,
                  attention_probs_dropout_prob=0.0))
    crit = BertPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 weight_decay=0.01,
                                 moment_dtype="bfloat16")
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16")
    step = paddle.jit.TrainStep(model, crit, opt)

    rng = np.random.RandomState(0)
    vocab = 30522
    ids = paddle.to_tensor(rng.randint(0, vocab, (batch, seq)))
    types = paddle.to_tensor(rng.randint(0, 2, (batch, seq)))
    mlm = paddle.to_tensor(np.where(
        rng.rand(batch, seq) < 0.15,
        rng.randint(0, vocab, (batch, seq)), -100))
    nsp = paddle.to_tensor(rng.randint(0, 2, (batch,)))
    # full-length sequences → no attention_mask → flash path (an
    # all-ones mask is a bias operand that blocks the flash kernel)
    args, labels = [ids, types], [mlm, nsp]

    loss = step(args, labels)  # compile
    _ = float(loss.numpy())
    t0 = time.perf_counter()
    for _i in range(steps):
        loss = step(args, labels)
    final = float(loss.numpy())
    dt = time.perf_counter() - t0
    if not np.isfinite(final):
        raise RuntimeError("bert bench: non-finite loss")
    n_params = sum(int(np.prod(p.shape))
                   for _n, p in model.named_parameters())
    tps = steps * batch * seq / dt
    d_model, n_layers = 768, 12
    flops_per_token = 6 * n_params + 12 * n_layers * seq * d_model
    mfu = tps * flops_per_token / _chip_peak(jax.devices()[0])
    rl = step.roofline(dt / steps)
    if rl is not None:
        print(rl.format(), file=sys.stderr)
    return tps, round(mfu, 4), (rl.as_dict() if rl else None)


def run_attn_varlen_bench():
    """Varlen flash-attention rung (ISSUE 13): a long packed batch
    through the segment-aware block-skipping kernel
    (nn/functional/flash_varlen.py). Returns (tokens/s,
    peak_bytes, total_tokens, backend). ``peak_bytes`` is the compiled
    program's argument+temp+output footprint from XLA's memory
    analysis — the number that was O(T²) on the dense path (a 32k-token
    pack would need a 64 GiB [h, T, T] fp32 intermediate; the varlen
    path stays O(T·d)). Gated by bench_gate: tokens/s regresses DOWN,
    peak bytes UP."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.flash_varlen import (
        flash_varlen_packed)

    h, d, dtype = 16, 128, jnp.bfloat16
    lens, iters = [4096] * 8, 20              # T = 32768 packed
    T = int(sum(lens))
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(lens)])
                     .astype(np.int32))
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(T, h, d), dtype)

    fn = jax.jit(lambda q, k, v, cu: flash_varlen_packed(
        q, k, v, cu, cu, causal=True))
    fn(q, q, q, cu).block_until_ready()       # compile outside timing
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(q, q, q, cu)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    if not np.isfinite(np.asarray(out[:8], np.float32)).all():
        raise RuntimeError("attn-varlen bench: non-finite output")
    mem = fn.lower(q, q, q, cu).compile().memory_analysis()
    peak = int(mem.temp_size_in_bytes + mem.argument_size_in_bytes
               + mem.output_size_in_bytes)
    return iters * T / dt, peak, T, "pallas"


#: the 1.3B serving geometry every serve_bench rung shares
_SERVE_1P3B = ("--d-model", "2048", "--layers", "24", "--heads", "16",
               "--vocab", "51200", "--bf16", "--page-size", "16")


def _serve_bench(argv):
    """A serving rung IN THIS PROCESS: this rung process holds the chip,
    so ``tools/serve_bench.py`` is called, not spawned (a child could
    not get the device). It prints its own JSON line and exits non-zero
    when any request ended in a state other than ``ok``."""
    import os

    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import serve_bench

    rc = serve_bench.main(["--no-lint", "--seed", "0", *argv])
    if rc:
        raise SystemExit(rc)


def _run_one(name):
    """Run a single ladder rung (used in a fresh subprocess so a failed
    bigger config leaves no stale HBM buffers behind)."""
    import jax

    peak = _chip_peak(jax.devices()[0])
    cfg = [c for c in LADDER if c[0] == name][0]
    _, d, L, h, s, b, ok = cfg
    tps, n_params, fpt, roofline = run_config(name, d, L, h, s, b,
                                              steps=10, opt_kwargs=ok)
    from paddle_tpu.nn.functional.attention import last_attention_backend

    mfu = tps * fpt / peak
    print(json.dumps({
        "metric": "gpt_train_tokens_per_sec_tpu",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / TARGET_MFU, 3),
        "model": name,
        "n_params": n_params,
        "mfu": round(mfu, 4),
        "roofline": roofline,
        "target_mfu": TARGET_MFU,
        "attention_backend": last_attention_backend(),
        "amp": "O2-bf16",
        "optimizer_state": ("bf16-moments+stochastic-rounding"
                            if cfg[6].get("stochastic_rounding")
                            else ("bf16-moments+fp32-master"
                                  if cfg[6].get("moment_dtype")
                                  else "fp32")),
        "cross_entropy": "bf16-logits-fp32-acc" if cfg[6].get("ce_bf16")
        else "fp32",
        "remat": cfg[6].get("remat", "full"),
        "telemetry": _telemetry(),
    }))


def _run_secondary(kind):
    """One serving/model rung in THIS process (spawned fresh by main so
    the training rung's HBM is fully released first)."""
    if kind == "--decode":
        tps, pct, cost_rl = run_decode_bench()
        print(json.dumps({"decode_tokens_per_sec": round(tps, 1),
                          "decode_batch": 32,
                          "decode_pct_of_hbm_roofline": pct,
                          "decode_roofline": cost_rl,
                          "decode_telemetry": _telemetry()}))
    elif kind == "--decode-int8":
        tps, pct, cost_rl = run_decode_bench(quant="int8")
        print(json.dumps({"decode_int8_tokens_per_sec": round(tps, 1),
                          "decode_int8_pct_of_hbm_roofline": pct,
                          "decode_int8_roofline": cost_rl}))
    elif kind == "--decode-a8w8":
        # full A8W8: dynamic per-token int8 activations into the
        # int8 x int8 streamed matmuls (the rung that must land the
        # >=1.6x-bf16 target the weight-only rung missed)
        tps, pct, cost_rl = run_decode_bench(quant="a8w8")
        print(json.dumps({"decode_a8w8_tokens_per_sec": round(tps, 1),
                          "decode_a8w8_pct_of_hbm_roofline": pct,
                          "decode_a8w8_roofline": cost_rl,
                          "decode_a8w8_telemetry": _telemetry()}))
    elif kind == "--decode-bf16-grouped":
        # GROUPED bf16 weight-stream decode (the loop every dense
        # stack runs): the fused O+LN2+FFN tail kernel plus in-tail
        # next-layer QKV — ONE streamed call per layer.
        # TPU targets for the next chip run (ISSUE r6 / VERDICT r5 #1):
        #   - >= 50% of the weight-bandwidth roofline
        # (the earlier-round chip records these bars were set against
        # were removed in PR 24; PERF.md holds what was read since)
        # gated by tools/bench_gate.py (direction "down").
        tps, pct, cost_rl = run_decode_bench()
        print(json.dumps(
            {"decode_bf16_grouped_tokens_per_sec": round(tps, 1),
             "decode_bf16_grouped_pct_of_hbm_roofline": pct,
             "decode_bf16_grouped_roofline": cost_rl,
             "decode_bf16_grouped_telemetry": _telemetry()}))
    elif kind == "--decode-tp":
        # TENSOR-PARALLEL decode rung (ISSUE 10): the mp-sharded
        # FusedMultiTransformer over every available chip — per-chip
        # weight streams shrink to 1/mp, two psums per layer ride the
        # ICI. The roofline denominator is the PER-CHIP weight slice,
        # so the target stays the same >=50%-of-weight-roofline bar as
        # the single-chip grouped rung; mp1 throughput preservation is
        # gated by bench_gate on the existing decode_* rungs, which
        # this change leaves untouched.
        import jax

        n = len(jax.devices())
        if n < 2:
            print(json.dumps({"decode_tp_skipped":
                              f"needs >= 2 devices, have {n}"}))
            return
        mp = 1 << (n.bit_length() - 1)  # largest power of two <= n
        tps, pct, cost_rl = run_decode_bench(mp_degree=mp)
        print(json.dumps(
            {f"decode_tp{mp}_tokens_per_sec": round(tps, 1),
             f"decode_tp{mp}_pct_of_hbm_roofline": pct,
             "decode_tp_mp_degree": mp,
             "decode_tp_roofline": cost_rl,
             "decode_tp_telemetry": _telemetry()}))
    elif kind == "--decode-tp-overlap":
        # ring-overlap TP decode rung (ISSUE 19): the SAME mp2 decode
        # workload with FLAGS_tp_overlap=ring — each layer's two
        # reduce seams run as chunked ppermute rings interleaved with
        # the chunk GEMMs instead of one blocking psum, so the ICI
        # hop hides behind the weight-stream math. Keys are pinned to
        # tp2 (the ring's win shrinks as P outgrows the interconnect
        # depth; tp2 is the shape the S-OVERLAP census pins). Gated
        # by bench_gate: tokens/s DOWN.
        import jax

        n = len(jax.devices())
        if n < 2:
            print(json.dumps({"decode_tp2_overlap_skipped":
                              f"needs >= 2 devices, have {n}"}))
            return
        import paddle_tpu as _p

        _p.set_flags({"tp_overlap": "ring"})
        tps, pct, cost_rl = run_decode_bench(mp_degree=2)
        print(json.dumps(
            {"decode_tp2_overlap_tokens_per_sec": round(tps, 1),
             "decode_tp2_overlap_pct_of_hbm_roofline": pct,
             "decode_tp2_overlap_roofline": cost_rl,
             "decode_tp2_overlap_telemetry": _telemetry()}))
    elif kind == "--moe-decode-ep-overlap":
        # double-buffered EP decode rung (ISSUE 19): the MoE decode
        # workload at ep2 with FLAGS_ep_overlap on — the all_to_all
        # exchange splits into two half-capacity buffers so dispatch1
        # rides the ICI while expert FFN0 runs. Gated by bench_gate:
        # tokens/s DOWN.
        import jax

        n = len(jax.devices())
        if n < 2:
            print(json.dumps({"moe_decode_ep2_overlap_skipped":
                              f"needs >= 2 devices, have {n}"}))
            return
        import paddle_tpu as _p

        _p.set_flags({"ep_overlap": True})
        tps, n_params = run_moe_decode_bench(ep_degree=2)
        print(json.dumps(
            {"moe_decode_ep2_overlap_tokens_per_sec": round(tps, 1),
             "moe_decode_ep2_overlap_params": n_params,
             "moe_decode_ep2_overlap_telemetry": _telemetry()}))
    elif kind == "--fleet":
        # fleet serving rung with the decode-concurrent drain (ISSUE
        # 19): serve_bench --fleet 2 --drain-async — replica 0 drains
        # mid-load under FLAGS_migrate_async, pages stream while both
        # endpoints keep decoding (fleet_* + fleet_async_migration_*
        # keys; gate: decode tokens DOWN, stall-ms UP).
        _serve_bench(["--streams", "4", "--fleet", "2", "--drain-async",
                      *_SERVE_1P3B, "--prompt-mix", "128,512,1024",
                      "--prefill-chunk", "256", "--max-new", "64",
                      "--rate", "64"])
    elif kind == "--fleet-disagg":
        # disaggregated prefill/decode rung (ISSUE 20): serve_bench
        # --fleet 2 --disagg drives the same prefill-heavy skewed
        # workload symmetric-then-disaggregated and pins disagg <=
        # symmetric TTFT p99 with goodput held (serve_disagg_* +
        # fleet_spill_* keys; gate: TTFT UP = regression, goodput /
        # tokens_per_sec DOWN = regression). TPU targets (v5e-8, 2
        # replicas, prompt mix 2048,8192,16384, rate 32):
        # serve_disagg_p99_ttft_ms <= 0.7 * fleet_p99_ttft_ms with
        # serve_disagg_tokens_per_sec >= 0.95 * fleet_tokens_per_sec.
        _serve_bench(["--streams", "8", "--fleet", "2", "--disagg",
                      *_SERVE_1P3B, "--prompt-mix", "2048,8192,16384",
                      "--prefill-chunk", "256", "--max-new", "64",
                      "--rate", "32"])
    elif kind == "--decode-spec":
        # speculative decoding at the acceptance ceiling (ISSUE 12):
        # replayed-greedy drafts -> accept rate 1.0, so the rung
        # measures pure verify amortization — the weight stack read
        # once per (k+1)-token window. Parity is asserted inside.
        # TPU target (ROADMAP item 1): decode_spec_vs_plain >= 1.5
        # on this acceptance-friendly workload, gated by bench_gate.
        tps, tps_plain, rate, rounds = run_decode_spec_bench()
        print(json.dumps(
            {"decode_spec_tokens_per_sec": round(tps, 1),
             "decode_spec_plain_tokens_per_sec": round(tps_plain, 1),
             "decode_spec_vs_plain": round(tps / tps_plain, 3)
             if tps_plain else None,
             "decode_spec_accept_rate": rate,
             "decode_spec_rounds": rounds,
             "decode_spec_telemetry": _telemetry()}))
    elif kind == "--attn-varlen":
        # varlen / long-context attention rung (ISSUE 13): the packed
        # block-skipping kernel on a 32k-token pack — throughput plus
        # the O(T·d) peak-bytes pin, gated by bench_gate (tokens/s
        # DOWN, peak bytes UP)
        tps, peak, total, backend = run_attn_varlen_bench()
        print(json.dumps(
            {"attn_varlen_tokens_per_sec": round(tps, 1),
             "attn_varlen_peak_bytes": peak,
             "attn_varlen_total_tokens": total,
             "attn_varlen_backend": backend,
             "attn_varlen_telemetry": _telemetry()}))
    elif kind == "--serve-long":
        # long-context serving rung: chunked prefill over the paged
        # pool routed through the in-place varlen kernel (no per-chunk
        # dense gather) — serve_long_* keys, gated by bench_gate
        _serve_bench(["--streams", "8", "--long-context", *_SERVE_1P3B,
                      "--prompt-mix", "2048,8192,16384",
                      "--prefill-chunk", "512", "--max-new", "32",
                      "--rate", "8"])
    elif kind == "--decode-int8kv":
        # best-throughput serving config: int8 weights + int8 KV cache
        # (cache-KV quant pays once KV traffic rivals the weight
        # stream) at batch 64
        tps, _pct, _rl = run_decode_bench(batch=64, quant="int8",
                                          kv_dtype="int8")
        print(json.dumps(
            {"decode_int8kv_b64_tokens_per_sec": round(tps, 1)}))
    elif kind == "--serve":
        # serving-frontend SLO rung: Poisson-load TTFT/TPOT/throughput
        # through paddle_tpu.serving (tools/serve_bench.py owns the
        # load generator; gated by bench_gate — ttft regresses UP,
        # tokens/s DOWN): the 1.3B serving shape at a saturating rate.
        _serve_bench(["--streams", "8", *_SERVE_1P3B,
                      "--prompt-mix", "128,512,1024",
                      "--prefill-chunk", "256", "--max-new", "64",
                      "--rate", "64"])
    elif kind == "--moe-train":
        # no-drop MoE training rung (ISSUE 15 / ROADMAP item 4): the
        # ragged grouped-GEMM MoE FFN in a whole-compiled train step.
        # A ~1B-param 8-expert config. Gated by bench_gate: tokens/s
        # and MFU regress DOWN, moe.dropped_tokens regresses UP with
        # NO noise floor.
        tps, mfu, n_active, n_params = run_moe_train_bench(
            d_model=1024, n_layers=12, n_heads=16, seq=1024,
            batch=4, num_experts=8)
        print(json.dumps(
            {"moe_train_tokens_per_sec": round(tps, 1),
             "moe_train_mfu": mfu,
             "moe_train_params": n_params,
             "moe_train_activated_params": n_active,
             "moe_train_telemetry": _telemetry()}))
    elif kind == "--moe-decode":
        # MoE serving decode rung: the expert-bank FusedCausalLM
        # through GenerationEngine (no-drop ragged MoE FFN per layer);
        # EP-sharded decode is exercised by dryrun_multichip's MoE
        # phase — this rung is the single-chip throughput number.
        tps, n_params = run_moe_decode_bench()
        print(json.dumps(
            {"moe_decode_tokens_per_sec": round(tps, 1),
             "moe_decode_params": n_params,
             "moe_decode_telemetry": _telemetry()}))
    elif kind == "--bert":
        tps, mfu, roofline = run_bert_bench()
        print(json.dumps({"bert_train_tokens_per_sec": round(tps, 1),
                          "bert_mfu": mfu,
                          "bert_roofline": roofline}))
    elif kind == "--s2048":
        import jax

        name, d, L, h, s, b, ok = S2048
        tps, n_params, fpt, roofline = run_config(name, d, L, h, s, b,
                                                  steps=10, opt_kwargs=ok)
        mfu = tps * fpt / _chip_peak(jax.devices()[0])
        print(json.dumps({"s2048_tokens_per_sec": round(tps, 1),
                          "s2048_mfu": round(mfu, 4),
                          "s2048_batch": b,
                          "s2048_roofline": roofline}))


#: every secondary rung, in the order the merged JSON line carries them
SECONDARY_KINDS = ("--s2048", "--decode", "--decode-int8",
                   "--decode-a8w8", "--decode-bf16-grouped",
                   "--decode-tp", "--decode-tp-overlap",
                   "--decode-spec", "--decode-int8kv", "--serve",
                   "--serve-long", "--fleet", "--fleet-disagg",
                   "--attn-varlen", "--moe-train", "--moe-decode",
                   "--moe-decode-ep-overlap", "--bert")

def _sub(argv, timeout):
    """One rung in a fresh child process: a chip belongs to one process
    at a time, so the parent never touches JAX and each rung gets the
    device to itself (a failed bigger config leaves no stale HBM
    buffers behind)."""
    import os
    import subprocess

    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + argv,
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        # run() has killed and reaped the child: the chip is free again
        out, err = None, f"timeout after {timeout}s"
    else:
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("{")]
        if proc.returncode == 0 and lines:
            out, err = json.loads(lines[-1]), None
        else:
            out, err = None, f"rc={proc.returncode}: {proc.stderr[-300:]}"
    # nothing else is shown until the merged line: say how far it got
    # (a rung's scalars; its telemetry blocks wait for the merged line)
    seen = err if out is None else json.dumps(
        {k: v for k, v in out.items() if not isinstance(v, (dict, list))})
    print(f"bench: {' '.join(argv)} "
          f"{'FAILED' if out is None else 'ok'} "
          f"({time.perf_counter() - t0:.0f}s): {seen}",
          file=sys.stderr, flush=True)
    return out, err


def _accumulate(result, kinds):
    """Run each secondary rung in its own subprocess, merging every
    emitted key into ``result`` (errors land as ``<rung>_error``)."""
    for kind in kinds:
        # s2048's flash-attention bwd compile is the long one; the run
        # itself is seconds
        extra, err = _sub([kind], 2400 if kind == "--s2048" else 1500)
        if extra is None:
            key = kind.strip("-").replace("-", "_")
            result[f"{key}_error"] = err
        else:
            result.update(extra)
    return result


def _require_chip():
    """Every rung measures the chip: a process that finds none exits
    non-zero and prints no metric (a CPU run yields counts and
    correctness, never a rate — tests drive the rung functions)."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"bench: needs the TPU, JAX found platform {d.platform!r} "
            f"({d.device_kind}) — nothing measured")
    return d


def _lint_preflight(no_lint):
    """tpu_lint preflight (ISSUE 7): never spend chip time on a program
    the static analyzer already knows is broken. The lint builds live
    models, so it runs in a CHILD pinned to the CPU backend — the
    parent stays off JAX and the chip stays free for the rungs."""
    import os
    import subprocess

    if no_lint or os.environ.get("PADDLE_TPU_NO_LINT"):
        return
    print("bench: tpu_lint preflight...", file=sys.stderr)
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "tpu_lint.py")
    proc = subprocess.run([sys.executable, tool],
                          env=dict(os.environ, JAX_PLATFORMS="cpu",
                                   JAX_ENABLE_COMPILATION_CACHE="false"),
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode:
        print(proc.stdout[-4000:] + proc.stderr[-2000:], file=sys.stderr)
        raise SystemExit(
            "bench: REFUSING to start — tpu_lint preflight failed "
            "(fix or waive the findings, or rerun with --no-lint)")


def main():
    no_lint = "--no-lint" in sys.argv
    if no_lint:
        sys.argv.remove("--no-lint")

    # ---- rung processes: one rung, this process owns the chip ----
    if "--probe" in sys.argv:
        d = _require_chip()
        print(json.dumps({"platform": d.platform,
                          "device_kind": d.device_kind}))
        return
    if "--config" in sys.argv:
        _require_chip()
        _run_one(sys.argv[sys.argv.index("--config") + 1])
        return
    for kind in SECONDARY_KINDS:
        if kind in sys.argv:
            _require_chip()
            _run_secondary(kind)
            return

    # ---- the parent: never initialises a JAX backend. It asks a
    # short-lived child whether there is a chip, lints in a CPU child,
    # then hands the chip to one rung child after another ----
    probe, err = _sub(["--probe"], 300)
    if probe is None:
        raise SystemExit(f"bench: needs the TPU — probe failed ({err})")
    _lint_preflight(no_lint)
    for (name, *_rest) in LADDER:
        result, err = _sub(["--config", name], 3000)
        if result is None:
            print(f"bench: {name} failed ({err})", file=sys.stderr)
            continue
        # secondary rungs each get a FRESH process (and a fresh chip —
        # the training rung's buffers die with its process)
        print(json.dumps(_accumulate(result, SECONDARY_KINDS)))
        return
    raise SystemExit("bench: all ladder configs failed")


if __name__ == "__main__":
    main()
