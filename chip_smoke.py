#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu starts on the chip.

Drives the two paths the ROADMAP judges, at the full width of the GPT-3
1.3B configuration the repo carries (``bench.LADDER[0]``: d_model 2048,
24 layers, 16 heads, ffn 8192, vocab 51200), through the entry points a
user calls, in ONE process (a chip belongs to one process):

  serve   FusedCausalLM (bf16 stacks) behind ``ServingEngine`` with default
          flags (paged bf16 pool, page 16, chunked prefill 256, grouped
          decode tail): 8 seeded requests, prompts 128..1024 tokens, 64 new
          tokens each, through ``submit()``/``run()``. Every request must
          end ``ok`` with 64 tokens; the prefill and decode programs that
          ran must contain ``tpu_custom_call``; one two-chunk prefill and
          one decode step are then repeated through the XLA reference
          paths on the same weights and inputs and the logits compared.
  train   ``bench.build_train_step(*LADDER[0])`` (b4 x s1024, the _FAST
          optimizer recipe) through ``TrainStep``: 3 steps on one fixed
          seeded batch, loss finite every step and lower at step 3.

Order: serve first, then its buffers are freed, then train. The PJRT
``peak_bytes_in_use`` counter is a process-wide high-water mark that cannot
be reset; train's peak is the larger one, so this order leaves each phase's
line with its own peak. The script checks that the serve phase's buffers
are really gone before train starts.

``--chips 4`` (run by hand; the driver runs one chip) runs ONLY the
tensor-parallel serving path and what it is compared with:
``ServingEngine(mp_degree=4)`` answering the same 8 requests against the
one-chip engine on device 0, plus the placement assertions.

``--rehearse`` runs the same control flow at toy widths on whatever backend
JAX has (CPU, Pallas interpret / XLA fallbacks). It exists to find wrong
arguments before chip time is spent; its last line says ``"ok": false`` —
a run that saw no chip never prints ``"ok": true``.

Every phase prints one JSON line and is a hard failure: nothing here turns
an exception into ``ok``. The last line of stdout is the contract's
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything is generated from ``--seed``; nothing outside the checkout is
read, and nothing is written except JAX's compile cache (see
``paddle_tpu.device.setup_compile_cache``).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

#: bench.LADDER[0] / the decode rung's geometry
REAL = dict(vocab=51200, d_model=2048, n_heads=16, n_layers=24,
            n_requests=8, new_tokens=64, len_quantum=128,
            train_seq=1024, train_batch=4, ref_tokens=512)
#: --rehearse: same control flow, toy widths
TOY = dict(vocab=512, d_model=128, n_heads=4, n_layers=2,
           n_requests=8, new_tokens=8, len_quantum=16,
           train_seq=64, train_batch=2, ref_tokens=64, prefill_chunk=32)

#: the XLA reference the kernel path is checked against: every kernel
#: entry takes its plain-XLA form while ``device.chip.on_tpu`` answers
#: False (``reference_check`` patches it for the length of the trace);
#: the chunked prefill's attend has a dense-gather form besides
REFERENCE_FLAGS = {"FLAGS_prefill_attention_backend": "gather"}
#: max |logit difference| allowed between the kernel path and the XLA
#: reference (and between TP=4 and one chip). Random-weight logits here
#: have a standard deviation of about 0.9; a wrong kernel moves them by
#: O(1). bf16 activations through 24 layers, with the kernels'
#: single-pass f32 dots against XLA's, land well inside this.
LOGIT_TOL = 0.125
DECODE_CHUNK = 16


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def device_info():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def mem(device=None) -> dict:
    import jax

    d = device or jax.devices()[0]
    return d.memory_stats() or {}


class Compiles:
    """Seconds spent in XLA backend compiles, summed from JAX's own
    monitoring events — the ``compile_s`` of each phase line."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            self.total += secs

    def take(self) -> float:
        t, self.total = self.total, 0.0
        return round(t, 2)


# ----------------------------------------------------------------- serve

def build_lm(cfg, seed):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import FusedCausalLM

    paddle.seed(seed)
    n_req, q, new = cfg["n_requests"], cfg["len_quantum"], cfg["new_tokens"]
    model = FusedCausalLM(
        vocab_size=cfg["vocab"], embed_dim=cfg["d_model"],
        num_heads=cfg["n_heads"], dim_feedforward=4 * cfg["d_model"],
        num_layers=cfg["n_layers"], max_position=n_req * q + new + 1)
    st = model.stack
    for n in ("qkv_weight", "qkv_bias", "out_weight", "out_bias",
              "ffn1_weight", "ffn1_bias", "ffn2_weight", "ffn2_bias"):
        p = getattr(st, n)
        p._rebind(p._data.astype(jnp.bfloat16))
    return model


def make_prompts(cfg, seed):
    """8 prompts whose lengths are a seeded permutation of
    q, 2q, .., 8q (128..1024 at the real size): the whole range, and
    only two chunk-program sizes (256 and the 128 tail)."""
    rng = np.random.RandomState(seed)
    n, q = cfg["n_requests"], cfg["len_quantum"]
    lens = rng.permutation(np.arange(1, n + 1) * q)
    return [rng.randint(0, cfg["vocab"], int(L)).tolist() for L in lens]


def build_engine(model, cfg, mp_degree=None):
    from paddle_tpu.serving import ServingEngine, SLOConfig

    n, q, new = cfg["n_requests"], cfg["len_quantum"], cfg["new_tokens"]
    # the real size keeps every default (chunked prefill 256); the toy
    # shrinks the chunk with its prompts so multi-chunk prefill still runs
    slo = SLOConfig(prefill_chunk=cfg["prefill_chunk"]) \
        if "prefill_chunk" in cfg else None
    return ServingEngine(model, slo=slo, max_batch=n, page_size=16,
                         max_length=n * q + new,
                         decode_chunk=min(DECODE_CHUNK, new),
                         mp_degree=mp_degree)


def answer(engine, prompts, new_tokens):
    """submit()/run(); every request must end ``ok`` with all its tokens
    (the scheduler contains step failures per request, so a broken
    program shows up here as state ``error`` and an empty stream)."""
    ids = [engine.submit(p, max_new_tokens=new_tokens) for p in prompts]
    done = {r.id: r for r in engine.run()}
    bad = [(i, getattr(done.get(i), "state", "missing"),
            len(getattr(done.get(i), "generated", ())),
            repr(getattr(done.get(i), "error", None)))
           for i in ids
           if i not in done or done[i].state != "ok"
           or len(done[i].generated) != new_tokens]
    if bad:
        raise RuntimeError(f"requests not served (id, state, n_tokens, "
                           f"error): {bad}")
    return [list(done[i].generated) for i in ids]


def program_texts(engine) -> dict:
    """name -> compiled HLO text of every prefill-chunk and decode program
    the engine actually ran, read from its AotProgram handles."""
    progs = list(engine._chunk_jit.values()) \
        + list(engine._gen._decode_k_jit.values())
    out = {}
    for prog in progs:
        for n, exe in enumerate(prog._exes.values()):
            out[f"{prog.name}#{n}"] = exe.as_text()
    return out


def require_kernels(engine, on_chip: bool) -> list:
    texts = program_texts(engine)
    names = sorted(texts)
    if not any(n.startswith("serve.prefill") for n in names) \
            or not any(n.startswith("decode") for n in names):
        raise RuntimeError(f"expected prefill and decode programs, "
                           f"found {names}")
    if on_chip:
        quiet = [n for n in names if "tpu_custom_call" not in texts[n]]
        if quiet:
            raise RuntimeError(
                f"programs without a Pallas kernel (a quiet reference "
                f"path): {quiet}")
    return names


def _decode_logits_fn(engine):
    """One decode step returning LOGITS (the engine's own decode program
    returns picked tokens only): the same decode_raw + lm head calls as
    ``GenerationEngine._decode_k_fn``'s scan body."""
    from paddle_tpu.incubate.nn.fused_transformer import PagedKV

    g, st = engine._gen, engine.model.stack

    def fn(weights, embed, head_t, lnf_s, lnf_b, tok, lens, ck, cv,
           tables):
        x = embed[tok].astype(g._cdtype)
        h, _ = st.decode_raw(weights, x, PagedKV(ck, cv), tables, lens,
                             g._cos, g._sin, a8w8=g._a8w8, tp=g._tp)
        return g._logits(h, head_t, lnf_s, lnf_b)

    return fn


def _model_operands(engine):
    g = engine._gen
    return (g._weights(), g._embed(), g._head_t, *g._lnf())


def prefill_logits(engine, chunk_fn, tokens, key, ck, cv, extra_tokens=0):
    """Chunked prefill of ``tokens`` under page key ``key`` through
    ``chunk_fn`` (the engine's compiled chunk program or a re-jitted
    reference); returns (last chunk's logits, ck, cv)."""
    import jax.numpy as jnp

    c = engine.slo.prefill_chunk
    mgr = engine._mgr
    if key not in mgr._owned:
        need = mgr.pages_needed(len(tokens) + extra_tokens)
        if not engine._evict_for(need):
            raise RuntimeError(f"pool cannot free {need} pages")
        mgr.grow(key, need)
    tables = mgr.block_tables([key], engine._pages_per_seq)
    logits = None
    for pos in range(0, len(tokens), c):
        part = tokens[pos:pos + c]
        cs = engine._chunk_size(len(part))
        ids = np.zeros((1, cs), np.int32)
        ids[0, :len(part)] = part
        logits, ck, cv = chunk_fn(cs)(
            *_model_operands(engine), jnp.asarray(ids),
            jnp.asarray([pos], jnp.int32),
            jnp.asarray([len(part)], jnp.int32), ck, cv, tables)
    return np.asarray(logits, np.float32), ck, cv


def compare_logits(what, got, ref) -> dict:
    """Hard check: max |got - ref| <= LOGIT_TOL, and the argmax agrees
    in every row whose reference top-2 margin exceeds the observed
    difference (a closer pair is a tie at this precision)."""
    diff = float(np.max(np.abs(got - ref)))
    top2 = np.sort(ref, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    same = np.argmax(got, -1) == np.argmax(ref, -1)
    rec = {"max_abs_diff": round(diff, 5),
           "ref_std": round(float(ref.std()), 4),
           "argmax_match": int(same.sum()), "rows": int(same.size),
           "min_ref_margin": round(float(margin.min()), 5)}
    if not np.all(np.isfinite(got)) or diff > LOGIT_TOL:
        raise RuntimeError(f"{what}: logits disagree {rec} "
                           f"(tolerance {LOGIT_TOL})")
    if np.any(~same & (margin > 2 * diff)):
        raise RuntimeError(f"{what}: argmax differs outside a tie {rec}")
    return rec


def reference_check(engine, cfg, seed) -> dict:
    """One two-chunk prefill and one decode step: the default (kernel)
    programs against the same raw functions re-traced with the platform
    probe answering False (and REFERENCE_FLAGS), same weights, same
    tokens, same page tables."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.device import chip

    rng = np.random.RandomState(seed + 1)
    tokens = rng.randint(0, cfg["vocab"], cfg["ref_tokens"]).tolist()
    key = ("slot", 0)          # every request finished: slot 0 is free
    n_slots = engine.max_batch

    # kernel path: the engine's own compiled chunk programs and pool
    lk, engine._ck, engine._cv = prefill_logits(
        engine, engine._get_chunk_prefill, tokens, key,
        engine._ck, engine._cv, extra_tokens=DECODE_CHUNK)
    dec = _decode_logits_fn(engine)
    tables = engine._mgr.block_tables(
        [("slot", i) for i in range(n_slots)], engine._pages_per_seq,
        allow_missing=True)
    tok = np.zeros((n_slots,), np.int32)
    tok[0] = int(np.argmax(lk[0]))
    lens = np.zeros((n_slots,), np.int32)
    lens[0] = len(tokens)
    dec_args = (*_model_operands(engine), jnp.asarray(tok),
                jnp.asarray(lens), engine._ck, engine._cv, tables)
    dk = np.asarray(jax.jit(dec)(*dec_args), np.float32)

    # reference path: the probe and the flags are read at trace time, so
    # the same raw functions traced again while ``chip.on_tpu`` answers
    # False are the XLA programs (the assignment that
    # analysis/sites.py::_force_tpu_routing makes the other way).
    # Each gets a FRESH function object: jit caches traces by function,
    # and a cache hit would hand back the kernel program (the first chip
    # run of this script caught exactly that). Both are compiled ahead of
    # time so their text can be checked: a reference holds no kernel.
    old = paddle.get_flags(list(REFERENCE_FLAGS))
    paddle.set_flags(REFERENCE_FLAGS)
    probe, chip.on_tpu = chip.on_tpu, lambda: False
    try:
        ref_exes = {}

        def ref_chunk(cs):
            def call(*a):
                if cs not in ref_exes:
                    ref_exes[cs] = jax.jit(
                        lambda *b: engine._chunk_prefill_fn(*b),
                        donate_argnums=(8, 9)).lower(*a).compile()
                return ref_exes[cs](*a)
            return call

        rk = jnp.zeros_like(engine._ck)
        rv = jnp.zeros_like(engine._cv)
        lr, rk, rv = prefill_logits(engine, ref_chunk, tokens, key, rk, rv)
        del rk, rv
        # decode reference on the SAME pool the kernel step read
        ref_exes["decode"] = jax.jit(_decode_logits_fn(engine)) \
            .lower(*dec_args).compile()
        dr = np.asarray(ref_exes["decode"](*dec_args), np.float32)
    finally:
        chip.on_tpu = probe
        paddle.set_flags(old)
    engine._mgr.free(key)
    kernels_in_ref = [str(n) for n, exe in ref_exes.items()
                      if "tpu_custom_call" in exe.as_text()]
    if kernels_in_ref:
        raise RuntimeError(f"XLA reference programs {kernels_in_ref} "
                           f"contain a Pallas kernel — not a reference")
    return {"prefill": compare_logits("prefill chunk", lk, lr),
            # row 0 is the live sequence; idle rows read the scratch page
            "decode": compare_logits("decode step", dk[:1], dr[:1])}


def phase_serve(cfg, seed, compiles, on_chip):
    t0 = time.perf_counter()
    model = build_lm(cfg, seed)
    engine = build_engine(model, cfg)
    tokens = answer(engine, make_prompts(cfg, seed), cfg["new_tokens"])
    programs = require_kernels(engine, on_chip)
    ref = reference_check(engine, cfg, seed)
    emit({"phase": "serve", "ok": True, "compile_s": compiles.take(),
          "wall_s": round(time.perf_counter() - t0, 2),
          "device_kind": device_info()["kind"],
          "peak_bytes_in_use": mem().get("peak_bytes_in_use"),
          "requests": len(tokens), "tokens_each": cfg["new_tokens"],
          "programs": programs, "kernel_vs_xla": ref,
          "logit_tol": LOGIT_TOL})


# ----------------------------------------------------------------- train

def phase_train(cfg, seed, compiles):
    import bench

    t0 = time.perf_counter()
    name, d, L, h, seq, batch, opt = bench.LADDER[0]
    if cfg is not REAL:
        d, L, h = cfg["d_model"], cfg["n_layers"], cfg["n_heads"]
        seq, batch = cfg["train_seq"], cfg["train_batch"]
        bench.VOCAB = cfg["vocab"]
    model, step, ids, pos, labels = bench.build_train_step(
        d, L, h, seq, batch, opt, seed=seed)
    losses = [float(step([ids, pos], [labels]).numpy()) for _ in range(3)]
    if not all(np.isfinite(losses)) or not losses[2] < losses[0]:
        raise RuntimeError(f"train: losses {losses} — expected finite and "
                           f"lower at step 3 than at step 1")
    emit({"phase": "train", "ok": True, "compile_s": compiles.take(),
          "wall_s": round(time.perf_counter() - t0, 2),
          "device_kind": device_info()["kind"],
          "peak_bytes_in_use": mem().get("peak_bytes_in_use"),
          "config": name if cfg is REAL else "toy",
          "batch": batch, "seq": seq, "losses": losses})


def require_freed(what, limit_bytes=1 << 30):
    """The previous phase's buffers must be gone before the next one is
    sized against the whole chip."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()
    used = mem().get("bytes_in_use")
    if used is not None and used > limit_bytes:
        raise RuntimeError(f"{what}: {used} bytes still in use on the "
                           f"device after the phase was dropped")


# ------------------------------------------------------------------ tp=4

def require_spread(name, arr, n):
    devs = {s.device for s in arr.addressable_shards}
    sizes = {s.data.nbytes for s in arr.addressable_shards}
    if len(devs) != n or len(sizes) != 1 \
            or next(iter(sizes)) * n != arr.nbytes:
        raise RuntimeError(
            f"{name}: expected {n} equal shards on {n} devices, found "
            f"{len(devs)} devices, shard bytes {sorted(sizes)} of "
            f"{arr.nbytes}")


def first_divergence(a, b):
    for j, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return j
    return None


def phase_tp4(cfg, seed, compiles, on_chip):
    import jax

    t0 = time.perf_counter()
    n = 4
    devices = jax.devices()
    if len(devices) != n:
        raise RuntimeError(f"--chips 4 needs 4 devices, found "
                           f"{len(devices)}")
    prompts = make_prompts(cfg, seed)
    model = build_lm(cfg, seed)
    one = build_engine(model, cfg)
    tok1 = answer(one, prompts, cfg["new_tokens"])
    require_kernels(one, on_chip)

    before = [mem(d).get("bytes_in_use") for d in devices]
    tp = build_engine(model, cfg, mp_degree=n)
    tok4 = answer(tp, prompts, cfg["new_tokens"])
    programs = require_kernels(tp, on_chip)

    # placement: every stacked weight and the KV pool on 4 devices
    for wname, arr in tp._gen._weights().items():
        if arr.ndim >= 3:                      # [L, K, N] weight stacks
            require_spread(wname, arr, n)
    require_spread("kv_pool.k", tp._ck, n)
    require_spread("kv_pool.v", tp._cv, n)
    after = [mem(d).get("bytes_in_use") for d in devices]
    added = None
    if all(v is not None for v in before + after):
        # what the TP engine added per device (device 0 also holds the
        # one-chip engine it is compared with, so totals cannot balance)
        added = [a - b for a, b in zip(after, before)]
        mean = sum(added) / n
        if min(added) <= 0 or max(added) > 1.5 * mean:
            raise RuntimeError(f"TP engine bytes per device {added}: not "
                               f"within 1.5x of the mean {mean}")
    elif on_chip:
        raise RuntimeError("device.memory_stats() reports no "
                           "bytes_in_use on the chip")

    # tokens: equal, or at a request's first divergence both engines'
    # teacher-forced logits agree within LOGIT_TOL and the two picked
    # tokens are a tie at that precision
    diverged = []
    for r, (a, b) in enumerate(zip(tok1, tok4)):
        j = first_divergence(a, b)
        if j is None:
            continue
        ctx = prompts[r] + a[:j]
        # teacher-forced context may not be a chunk multiple: the tail
        # chunk program compiles here
        l1, one._ck, one._cv = prefill_logits(
            one, one._get_chunk_prefill, ctx, ("smoke", r),
            one._ck, one._cv)
        one._mgr.free(("smoke", r))
        l4, tp._ck, tp._cv = prefill_logits(
            tp, tp._get_chunk_prefill, ctx, ("smoke", r),
            tp._ck, tp._cv)
        tp._mgr.free(("smoke", r))
        rec = compare_logits(f"tp4 vs one chip, request {r} step {j}",
                             l4, l1)
        # logits no further apart than ``diff`` can swap two tokens at
        # most 2 * diff apart — the same bound compare_logits holds the
        # argmax to
        gap = float(abs(l1[0, a[j]] - l1[0, b[j]]))
        if gap > 2 * rec["max_abs_diff"]:
            raise RuntimeError(
                f"request {r} diverges at step {j}: one chip picked "
                f"{a[j]}, tp4 picked {b[j]}, {gap} apart in the one-chip "
                f"logits — not a tie")
        diverged.append({"request": r, "step": j, "tie_gap": round(gap, 5),
                         **rec})
    emit({"phase": "serve_tp4", "ok": True, "compile_s": compiles.take(),
          "wall_s": round(time.perf_counter() - t0, 2),
          "device_kind": device_info()["kind"],
          "peak_bytes_in_use": [mem(d).get("peak_bytes_in_use")
                                for d in devices],
          "requests": len(tok4), "tokens_each": cfg["new_tokens"],
          "requests_token_identical": len(tok4) - len(diverged),
          "diverged_within_tol": diverged, "logit_tol": LOGIT_TOL,
          "tp_bytes_added_per_device": added, "programs": programs})


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the TP=4 serving path and its one-chip "
                         "comparison (run by hand on four chips)")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on any backend; never prints ok:true")
    args = ap.parse_args(argv)

    info = device_info()
    on_chip = info["platform"] == "tpu"
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: needs a TPU, JAX found {info} — nothing run",
              file=sys.stderr)
        return 2
    if on_chip and args.rehearse:
        print("chip_smoke: --rehearse is for a machine without the chip",
              file=sys.stderr)
        return 2
    cfg = TOY if args.rehearse else REAL
    if args.rehearse:
        # XLA:CPU reloads its cached executables with a screen of
        # machine-feature errors, and nothing a chip run could reuse
        import jax

        jax.config.update("jax_enable_compilation_cache", False)
    compiles = Compiles()
    if args.chips == 4:
        phase_tp4(cfg, args.seed, compiles, on_chip)
    else:
        phase_serve(cfg, args.seed, compiles, on_chip)
        require_freed("serve")
        phase_train(cfg, args.seed, compiles)
    # only a run on the chip is a result
    emit({"ok": on_chip, "device": info} if on_chip
         else {"ok": False, "rehearsal": "passed", "device": info})
    return 0


if __name__ == "__main__":
    sys.exit(main())
