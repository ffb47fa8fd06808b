"""Driver ``train``: one ``paddle.jit.TrainStep`` driven from the seed.

Set-up builds ONE object, the compiled step with its state, drives it
through its first ``CHECK_STEPS`` steps through the same call and the same
feed as the window (a fresh seeded batch every step, made ahead by a host
thread), reads what ``correct`` compares, and hands that same object to the
window. The rate is the tokens of every step completed in the window over
the window's seconds; the window ends when the last step has finished on
the device.

``correct`` (after the window, the program freed): the plain reference
follows the first steps from the same seed and feed. Compared, each with a
limit of its own in the configuration's file: every step's loss, the norm of
the first gradient as the optimizer got it (from its first moment after one
step), and the norm of the parameters' change after the steps; the norms by
the worst leaf, as the gap between the program's norm and the reference's
over the reference's norm of that leaf or of the median leaf, whichever is
larger. What has no gradient to speak of in the reference moves under Adam
by round-off alone (a key's bias under softmax) and is left out of the
change by a rule on the reference's first gradient: whole leaves whose
gradient norm is under a thousandth of the median leaf's, and, inside a 1-D
leaf, the elements under a thousandth of the median leaf's per-element size
(a QKV bias is one leaf whose key third has no gradient).
"""
from __future__ import annotations

import collections
import gc
import os
import queue
import statistics
import threading
import time

import numpy as np

from benchmark.harness import load_module

CHECK_STEPS = 3
IN_FLIGHT = 2


def worst_leaf_gap(got: dict, want: dict, skip=()):
    """max over leaves of |got - want| / max(want, median of want)."""
    med = statistics.median(want.values())
    worst, at = 0.0, None
    for k, w in want.items():
        if k in skip:
            continue
        g = abs(got[k] - w) / max(w, med, 1e-30)
        if not g <= worst:          # also catches NaN
            worst, at = g, k
    return worst, at


def readings(losses, grad_norms, change_norms, change_vecs, grad_vecs=None,
             sizes=None):
    return {"losses": list(losses), "grad": dict(grad_norms),
            "change": dict(change_norms), "change_vec": dict(change_vecs),
            "grad_vec": grad_vecs, "size": sizes}


def compare(got, want):
    """The numbers ``correct`` compares, program (or control) against the
    reference: {name: value}."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        out[f"loss_gap.{i + 1}"] = abs(a - b) / abs(b) \
            if np.isfinite(a) else float("inf")
    out["grad_norm_gap"], _ = worst_leaf_gap(got["grad"], want["grad"])
    # norms cannot see rounding (zero-mean noise adds in quadrature): the
    # first gradient of all 1-D leaves as ONE vector, program against
    # reference, by the norm of their difference over the reference's norm
    keys = sorted(want["grad_vec"])
    a = np.concatenate([got["grad_vec"][k] for k in keys])
    b = np.concatenate([want["grad_vec"][k] for k in keys])
    out["grad_vector_gap"] = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    med = statistics.median(want["grad"].values())
    still = {k for k, g in want["grad"].items() if g < 1e-3 * med}
    per_elem = statistics.median(g / np.sqrt(want["size"][k])
                                 for k, g in want["grad"].items())
    c_got, c_want = dict(got["change"]), dict(want["change"])
    for k, gv in want["grad_vec"].items():
        keep = np.abs(gv) >= 1e-3 * per_elem
        c_got[k] = float(np.linalg.norm(got["change_vec"][k][keep]))
        c_want[k] = float(np.linalg.norm(want["change_vec"][k][keep]))
    out["change_norm_gap"], _ = worst_leaf_gap(c_got, c_want, skip=still)
    return out


class Driver:
    def __init__(self, cell, seed, seconds, tracer):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.tracer = tracer
        self.cfg, self.mix = cell.config, cell.traffic
        self.tr = dict(self.cfg["training"], **self.mix.get("training", {}))
        here = cell.bench_dir
        fam = self.tr["family"]
        self.sut = load_module(os.path.join(here, "models", fam + ".py"),
                               "bench_model_" + fam)
        self.ref = load_module(os.path.join(here, "reference", fam + ".py"),
                               "bench_reference_" + fam)
        self.b, self.s = int(self.tr["batch"]), int(self.tr["seq"])
        self.model = self.step = self.opt = None
        self.t_open = self.t_close = None
        self.steps = []                 # (t_call, t_returned) per step
        self.n_window = 0
        self.got = None
        self.last_loss = None
        self._feed = None
        self._memory = {}

    # ------------------------------------------------------------ feed

    def _start_feed(self):
        q = queue.Queue(maxsize=8)
        stop = threading.Event()
        vocab = self.cfg["vocab_size"]

        def work():
            i = 0
            while not stop.is_set():
                item = self.ref.batch(self.seed, i, self.b, self.s, vocab)
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        pass
                i += 1

        th = threading.Thread(target=work, daemon=True)
        th.start()
        self._feed = (q, stop, th)

    def _call(self):
        """The window's own call: next batch of the feed through the step."""
        import paddle_tpu as paddle

        ids, labels = self._feed[0].get()
        t0 = time.monotonic()
        with self.tracer.span("train_step_call"):
            loss = self.step([paddle.to_tensor(ids), self._pos],
                             [paddle.to_tensor(labels)])
        self.steps.append((t0, time.monotonic()))
        return loss

    # ------------------------------------------------------------ set-up

    def setup(self):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle

        self.model, self.step, self.opt = self.sut.build_train_step(
            self.cfg, self.tr, self.seed, self.cell.bench_dir)
        self._pos = paddle.to_tensor(np.tile(np.arange(self.s), (self.b, 1)))
        self._start_feed()
        names = [n for n, _ in self.model.named_parameters()]
        params = [p for _, p in self.model.named_parameters()]

        @jax.jit
        def norms(arrays):
            return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                    for a in arrays]

        @jax.jit
        def diff_norms(now, start):
            return [jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(now, start)]

        losses, grad = [], None
        b1 = float(self.tr["optimizer"]["beta1"])
        for i in range(CHECK_STEPS):
            losses.append(float(self._call().numpy()))
            if i == 0:
                m1 = [self.opt._accumulators[id(p)]["moment1"]
                      for p in params]
                grad = {n: float(v) / (1.0 - b1)
                        for n, v in zip(names, norms(m1))}
                gvecs = {n: np.asarray(a, np.float32) / (1.0 - b1)
                         for n, a in zip(names, m1) if a.ndim == 1}
                del m1
        w0 = self.ref.make_weights(self.seed, self.cfg, self.s)
        change = {n: float(v) for n, v in zip(
            names, diff_norms([p._data for p in params],
                              [w0[n] for n in names]))}
        vecs = {n: np.asarray(p._data, np.float32)
                - np.asarray(w0[n], np.float32)
                for n, p in zip(names, params) if p._data.ndim == 1}
        del w0
        self.got = readings(losses, grad, change, vecs, gvecs)

    # ------------------------------------------------------------ window

    def window(self):
        pending = collections.deque()
        gc.collect()
        gc.freeze()
        self.t_open = time.monotonic()
        self.tracer.start()
        end = self.t_open + self.seconds
        loss = None
        while time.monotonic() < end:
            loss = self._call()
            pending.append(loss)
            self.n_window += 1
            if len(pending) > IN_FLIGHT:
                with self.tracer.span("wait_step"):
                    pending.popleft()._data.block_until_ready()
            self.tracer.poll()
        with self.tracer.span("wait_step"):
            while pending:
                pending.popleft()._data.block_until_ready()
        self.t_close = time.monotonic()
        self.tracer.stop()
        self.last_loss = float(loss.numpy())
        gc.unfreeze()
        exes = getattr(self.step._compiled, "_exes", {})
        for exe in exes.values():
            m = exe.memory_analysis()
            if m is not None:
                self._memory[self.step._program_name] = int(
                    m.argument_size_in_bytes + m.temp_size_in_bytes)

    def program_memory(self):
        return self._memory

    def release(self):
        import jax

        q, stop, th = self._feed
        stop.set()
        th.join()
        self._feed = None
        self.model = self.step = self.opt = self._pos = None
        gc.collect()
        jax.clear_caches()
        gc.collect()

    # ----------------------------------------------------------- correct

    def reference_readings(self, mode="f32"):
        """The reference (or, in another mode, the control or a planted
        fault) through the same first steps on the same feed."""
        half = mode == "half_batch"      # planted faults, not precisions
        frozen = mode == "frozen_state"
        t = self.ref.Trainer(self.seed, self.cfg, self.tr, frozen=frozen,
                             mode="f32" if half or frozen else mode)
        losses, grad = [], None
        for i in range(CHECK_STEPS):
            ids, labels = self.ref.batch(self.seed, i, self.b, self.s,
                                         self.cfg["vocab_size"])
            if half:
                ids, labels = ids[:self.b // 2], labels[:self.b // 2]
            loss, g = t.step(ids, labels, want_grad_norms=(i == 0))
            losses.append(loss)
            grad = g if i == 0 else grad
        norms, vecs = t.changes(self.seed)
        sizes = {k: int(np.prod(a.shape)) for k, a in t.w.items()}
        return readings(losses, grad, norms, vecs, t.grad_vecs, sizes)

    def check(self):
        limits = self.cfg["correct"]["training"]
        self._want = self.reference_readings()
        values = compare(self.got, self._want)
        compared = {k: {"value": float(v), "limit": float(limits[k])}
                    for k, v in values.items() if k in limits}
        compared["final_loss_finite"] = {
            "value": 0.0 if np.isfinite(self.last_loss) else 1.0,
            "limit": 0.0}
        ok = all(c["value"] <= c["limit"] for c in compared.values())
        return {"correct": ok, "compared": compared}

    def control(self, mode):
        """What the control (``fp8``) or a planted fault (``half_batch``,
        ``frozen_state``) reads, put in the program's place: {name: value}. After check()."""
        return {k: float(v) for k, v in compare(
            self.reference_readings(mode), self._want).items()}

    # ------------------------------------------------------------- facts

    def facts(self):
        tokens = self.n_window * self.b * self.s
        wall = self.t_close - self.t_open
        return {"attempted": self.n_window,
                "failed": 0 if np.isfinite(self.last_loss) else self.n_window,
                "train_tok_s": tokens / wall, "window_wall_s": wall,
                "steps": self.steps, "n_window": self.n_window,
                "tokens_per_step": self.b * self.s, "seq": self.s,
                "t_open": self.t_open, "t_close": self.t_close}
