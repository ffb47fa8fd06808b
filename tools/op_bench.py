"""Op-level benchmark harness + eager-dispatch microbenchmark.

TPU-native equivalent of the reference's op benchmark CI gate
(reference: tools/ci_op_benchmark.sh:1 runs benchmark/api tests per PR;
tools/check_op_benchmark_result.py compares logs and flags
regressions). Here:

  python tools/op_bench.py                  # writes OPBENCH_r{N}.json
  python tools/op_bench.py --compare A B    # gate: >10% regressions

Measures, for ~30 representative ops: EAGER latency (the full
dispatch + device round-trip a user pays per op outside jit — the cost
the reference's PHI eager dispatch exists to minimize, phi/README.md
§1.2) and JIT latency (the op inside a cached compiled program). Also
reports the raw Python dispatch overhead (eager_apply bookkeeping on
top of a bare jax call) and tape overhead (requires-grad dispatch).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REPS = 30
WARMUP = 5


def _block(out):
    """Block on device completion for jax arrays AND paddle Tensors —
    jax.block_until_ready silently no-ops on non-pytree Tensor objects,
    which would time async dispatch enqueue instead of execution."""
    import jax

    if isinstance(out, (list, tuple)):
        for o in out:
            _block(o)
        return
    data = getattr(out, "_data", out)
    jax.block_until_ready(data)


def _median_us(fn, reps=REPS, warmup=WARMUP):
    for _ in range(warmup):
        out = fn()
    _block(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        _block(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _suite():
    """(name, fn, tensor_args) for ~30 representative ops over realistic
    shapes. fn takes Tensors as POSITIONAL args so the jit measurement
    can pass them as program arguments — zero-arg jitted programs
    (inputs baked as constants) were measured to degrade dispatch in
    the earlier rounds' chip runs."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    paddle.seed(0)
    rng = np.random.RandomState(0)

    def t(*shape, dtype="float32"):
        return paddle.to_tensor(rng.randn(*shape).astype(dtype))

    a = t(256, 256)
    b = t(256, 256)
    big = t(1024, 1024)
    big2 = t(1024, 1024)
    v = t(65536)
    img = t(8, 16, 32, 32)
    logits = t(128, 1000)
    labels = paddle.to_tensor(rng.randint(0, 1000, (128,)))
    idx = paddle.to_tensor(rng.randint(0, 256, (64,)))
    q = t(4, 128, 8, 64)

    conv_w = t(32, 16, 3, 3)
    ln_w, ln_b = t(1024), t(1024)

    ops = [
        ("add", lambda a, b: a + b, (a, b)),
        ("multiply", lambda a, b: a * b, (a, b)),
        ("matmul_256", lambda a, b: a @ b, (a, b)),
        ("matmul_1024", lambda x, y: x @ y, (big, big2)),
        ("sum", lambda v: v.sum(), (v,)),
        ("mean_axis", lambda x: x.mean(axis=1), (big,)),
        ("max_reduce", lambda x: x.max(), (big,)),
        ("exp", lambda v: v.exp(), (v,)),
        ("sqrt", lambda v: v.abs().sqrt(), (v,)),
        ("relu", lambda x: F.relu(x), (big,)),
        ("gelu", lambda x: F.gelu(x), (big,)),
        ("sigmoid", lambda x: F.sigmoid(x), (big,)),
        ("softmax", lambda l: F.softmax(l, axis=-1), (logits,)),
        ("log_softmax", lambda l: F.log_softmax(l, axis=-1), (logits,)),
        ("cross_entropy", lambda l, y: F.cross_entropy(l, y),
         (logits, labels)),
        ("layer_norm", lambda x, w, b: F.layer_norm(x, [1024], w, b),
         (big, ln_w, ln_b)),
        ("reshape", lambda x: x.reshape([256, 4096]), (big,)),
        ("transpose", lambda x: x.transpose([1, 0]), (big,)),
        ("concat", lambda a, b: paddle.concat([a, b], axis=0), (a, b)),
        ("split", lambda x: paddle.split(x, 4, axis=0), (big,)),
        ("slice", lambda x: x[128:512, 128:512], (big,)),
        ("gather", lambda a, i: paddle.gather(a, i), (a, idx)),
        ("index_select", lambda a, i: paddle.index_select(a, i),
         (a, idx)),
        ("where", lambda a, b: paddle.where(a > 0, a, b), (a, b)),
        ("cast", lambda x: x.astype("bfloat16"), (big,)),
        ("clip", lambda x: x.clip(-1.0, 1.0), (big,)),
        ("cumsum", lambda v: v.cumsum(), (v,)),
        ("argmax", lambda l: l.argmax(axis=-1), (logits,)),
        ("sort", lambda v: paddle.sort(v), (v,)),
        ("conv2d", lambda x, w: F.conv2d(x, w, padding=1),
         (img, conv_w)),
        ("max_pool2d", lambda x: F.max_pool2d(x, 2), (img,)),
        ("sdp_attention", lambda q: F.scaled_dot_product_attention(
            q, q, q, is_causal=True), (q,)),
    ]
    return ops


def _taped_backward_us(fn, targs, reps=10, warmup=3):
    """Median forward+backward latency through the taped (requires-grad)
    dispatch — the path the aval-keyed VJP cache amortizes. None for ops
    without a differentiable float input (or whose output can't reduce
    to a scalar loss)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle

    leafs = []
    any_diff = False
    for t in targs:
        diff = jnp.issubdtype(t._data.dtype, jnp.inexact)
        any_diff = any_diff or diff
        leafs.append(paddle.to_tensor(np.asarray(t._data),
                                      stop_gradient=not diff))
    if not any_diff:
        return None

    def run():
        out = fn(*leafs)
        if isinstance(out, (list, tuple)):
            out = out[0]
        out.sum().backward()
        for leaf in leafs:
            leaf.clear_grad()
        return out

    try:
        return _median_us(run, reps=reps, warmup=warmup)
    except Exception:
        return None


def run_bench():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    results = {}
    for name, fn, targs in _suite():
        arrays = [t._data for t in targs]
        eager_us = _median_us(lambda: fn(*targs))

        def jit_wrap(*arrs, f=fn):
            out = f(*[Tensor(x) for x in arrs])
            return out[0]._data if isinstance(out, (list, tuple)) \
                else out._data

        jit_fn = jax.jit(jit_wrap)
        jit_us = _median_us(lambda: jit_fn(*arrays))
        taped_us = _taped_backward_us(fn, targs)
        results[name] = {"eager_us": round(eager_us, 1),
                         "jit_us": round(jit_us, 1),
                         "taped_backward_us": (None if taped_us is None
                                               else round(taped_us, 1))}

    # ---- dispatch overhead decomposition (phi/README.md §1.2) ----
    # baseline = a pre-compiled jax program call: the true floor for one
    # device op. (Bare eager jnp.add is not taken as the floor: this
    # framework's eager dispatch wraps ops in cached jit computations,
    # FLAGS_eager_jit_ops, so a compiled call is what it competes with.)
    x = jnp.ones((8,), jnp.float32)
    jadd = jax.jit(jnp.add)
    jadd(x, x)
    base_us = _median_us(lambda: jadd(x, x), reps=200)
    t0 = paddle.to_tensor(np.ones((8,), np.float32))
    nograd_us = _median_us(lambda: t0 + t0, reps=200)
    tg = paddle.to_tensor(np.ones((8,), np.float32), stop_gradient=False)

    def taped():
        with_grad = tg + tg
        return with_grad

    tape_us = _median_us(taped, reps=200)
    overhead = {
        "bare_jax_us": round(base_us, 1),
        "eager_dispatch_us": round(nograd_us, 1),
        "eager_dispatch_overhead_us": round(nograd_us - base_us, 1),
        "taped_dispatch_us": round(tape_us, 1),
        "tape_overhead_us": round(tape_us - nograd_us, 1),
    }
    # runtime telemetry for the whole bench run (profiler.stats): VJP
    # trace-cache outcomes + compile-time histograms — the hit rate here
    # is what the taped_dispatch_us number is made of
    from paddle_tpu.profiler import stats

    snap = stats.snapshot()
    telemetry = {
        "counters": {k: v for k, v in snap["counters"].items()
                     if not k.startswith("op.")},
        "histograms": snap["histograms"],
        "total_op_dispatches": sum(
            v for k, v in snap["counters"].items()
            if k.startswith("op.")),
    }
    hr = stats.vjp_cache_hit_rate()
    if hr is not None:
        telemetry["vjp_cache_hit_rate"] = round(hr, 4)
    fhr = stats.fwd_cache_hit_rate()
    if fhr is not None:
        telemetry["fwd_cache_hit_rate"] = round(fhr, 4)
    return {
        "backend": jax.default_backend(),
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
        "reps": REPS,
        "dispatch": overhead,
        "ops": results,
        "telemetry": telemetry,
    }


def compare(prev_path: str, cur_path: str, tol: float = 0.10) -> int:
    """Exit non-zero when any op's eager or jit latency regressed by
    more than ``tol`` vs the previous round (the
    check_op_benchmark_result.py gate)."""
    with open(prev_path) as f:
        prev = json.load(f)
    with open(cur_path) as f:
        cur = json.load(f)
    if prev.get("backend") != cur.get("backend"):
        print(f"op_bench: backend changed "
              f"({prev.get('backend')} -> {cur.get('backend')}); "
              "comparison skipped")
        return 0
    bad = []
    # dispatch overheads are gated too (taped dispatch in particular:
    # the r5 vjp-trace cache took it 753us -> ~50us; a revert must fail)
    for k in ("eager_dispatch_us", "taped_dispatch_us"):
        p, c = prev["dispatch"].get(k), cur["dispatch"].get(k)
        if p and c and c > max(p * (1 + tol), p + 10.0):
            bad.append(f"dispatch.{k}: {p} -> {c} us "
                       f"(+{100 * (c / p - 1):.0f}%)")
    for name, c in cur["ops"].items():
        p = prev["ops"].get(name)
        if not p:
            continue
        for k in ("eager_us", "jit_us", "taped_backward_us"):
            pv, cv = p.get(k), c.get(k)
            if pv is None or cv is None:  # column absent in older rounds
                continue
            # guard tiny-latency noise with a 5us floor
            if cv > max(pv * (1 + tol), pv + 5.0):
                bad.append(f"{name}.{k}: {pv} -> {cv} us "
                           f"(+{100 * (cv / pv - 1):.0f}%)")
    if bad:
        print("op_bench REGRESSIONS (>10%):")
        for line in bad:
            print(" ", line)
        return 1
    print(f"op_bench: no regressions vs {os.path.basename(prev_path)} "
          f"({len(cur['ops'])} ops)")
    return 0


def _next_round_path(repo: str) -> str:
    rounds = [int(m.group(1)) for f in glob.glob(
        os.path.join(repo, "OPBENCH_r*.json"))
        if (m := re.search(r"OPBENCH_r(\d+)\.json$", f))]
    return os.path.join(repo, f"OPBENCH_r{max(rounds, default=0) + 1:02d}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compare", nargs=2, metavar=("PREV", "CUR"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.compare:
        sys.exit(compare(*args.compare))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = args.out or _next_round_path(repo)
    res = run_bench()
    with open(out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps({"wrote": out,
                      "dispatch": res["dispatch"],
                      "n_ops": len(res["ops"])}))
    # auto-gate vs the previous round's file when present
    prevs = sorted(p for p in glob.glob(
        os.path.join(repo, "OPBENCH_r*.json")) if p != out)
    if prevs:
        sys.exit(compare(prevs[-1], out))


if __name__ == "__main__":
    main()
