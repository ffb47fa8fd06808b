"""Driver ``serve``: one engine behind ``submit()`` / ``step()``, loaded by an
open loop (arrivals on a schedule, from a submitter thread) or a closed
loop (``clients`` callers, each sending its next request when the last one
completes). The mix's file decides which, and every length and rate.

Stamps are the harness's own, on ``time.monotonic`` (the serving clock of
the program, so its ``t_admitted`` reads on the same axis): due, sent, every
token through ``on_token``. TTFT runs from the time a request was DUE.
Tails are over all requests due in the window; the rate is every token
emitted in the window over the window's seconds.
"""
from __future__ import annotations

import collections
import gc
import os
import threading
import time

import numpy as np

from benchmark import traffic_gen
from benchmark.harness import load_module
from benchmark.readers import percentile

DRAIN_LIMIT_S = 60.0


class Driver:
    def __init__(self, cell, seed, seconds, tracer):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.tracer = tracer
        self.cfg, self.mix = cell.config, cell.traffic
        self.lead_in = float(self.mix.get("lead_in_s", 0.0))
        self.open_loop = self.mix["arrivals"]["kind"] != "closed"
        here = cell.bench_dir
        fam = self.cfg["family"]
        self.sut = load_module(os.path.join(here, "models", fam + ".py"),
                               "bench_model_" + fam)
        self.ref = load_module(os.path.join(here, "reference", fam + ".py"),
                               "bench_reference_" + fam)
        self.engine = self.model = None
        self.reqs = []              # every Req sent, in sending order
        self.steps = []             # (t_start, t_end, action) per step()
        self.t_open = self.t_close = None
        self._stats0 = self._stats1 = None
        self._memory = {}
        self._journal = []

    # ------------------------------------------------------------ set-up

    def setup(self):
        self.model, self.engine = self.sut.build_engine(self.cfg, self.seed)
        self._warm()

    def _warm(self):
        """Every shape the window can use: each prefill chunk size the
        engine forms from this mix's prompt lengths, and the decode chunk
        at the engine's batch. Through submit()/run(), as a user would."""
        eng = self.engine
        rng = np.random.RandomState(12345)
        vocab = self.cfg["vocab_size"]
        chunk = eng.slo.prefill_chunk
        bucket = eng.prompt_bucket
        lo = int(self.mix["prompt_len"].get("min", 1))
        sizes = sorted({min(-(-n // bucket) * bucket, chunk)
                        for n in range(max(lo, 1), chunk + 1)})
        k = eng.decode_chunk
        for c in sizes:
            # c - 1 tokens pad to a chunk of c; a second request one chunk
            # longer drives the full chunk followed by that tail
            for n in (c - 1, chunk + c - 1):
                if lo <= n:
                    eng.submit(rng.randint(0, vocab, n).tolist(),
                               max_new_tokens=k + 1)
        done = eng.run()
        bad = [r.id for r in done if r.state != "ok"]
        if bad:
            raise RuntimeError(f"warm-up requests not served: {bad}")

    # ------------------------------------------------------------ window

    def _on_token(self, rq):
        def cb(obj, tok, rq=rq, steps=self.steps):
            if rq.obj is None:
                rq.obj = obj
            rq.token_t.append(time.monotonic())
            rq.token_step.append(len(steps))
            rq.tokens.append(int(tok))
        return cb

    def _submit(self, rq, now):
        from paddle_tpu.serving import ServerOverloaded

        rq.sent = now
        try:
            rq.rid = self.engine.submit(rq.prompt.tolist(),
                                        max_new_tokens=rq.n_out,
                                        on_token=self._on_token(rq))
        except ServerOverloaded:
            rq.state = "shed"
        if rq.rid is not None:
            self._by_rid[rq.rid] = rq
        self.reqs.append(rq)

    def _submitter(self, plan, t0, stop, errors):
        """The open loop's generator thread: sleeps until each request is
        due and submits it, whatever the engine is doing."""
        try:
            for rq in plan:
                rq.due = t0 + rq.due
                while True:
                    wait = rq.due - time.monotonic()
                    if wait <= 0 or stop.is_set():
                        break
                    with self.tracer.span("gen_sleep"):
                        time.sleep(min(wait, 0.05))
                if stop.is_set():
                    return
                with self.tracer.span("submit"):
                    self._submit(rq, time.monotonic())
        except BaseException as e:          # surfaced on the main thread
            errors.append(e)

    def _step(self):
        eng = self.engine
        t0 = time.monotonic()
        if eng.has_work:
            n_log = len(eng.action_log)
            with self.tracer.span("engine.step"):
                done = eng.step()
            action = eng.action_log[-1] if len(eng.action_log) > n_log \
                else "none"
            t1 = time.monotonic()
            self.steps.append((t0, t1, action))
            for r in done:
                rq = self._by_rid.get(r.id)
                if rq is not None:
                    rq.state, rq.done_t = r.state, t1
            return done
        with self.tracer.span("idle_wait"):
            time.sleep(0.0005)
        return []

    def window(self):
        from paddle_tpu.profiler import stats

        eng = self.engine
        vocab = self.cfg["vocab_size"]
        span = self.lead_in + self.seconds
        self._by_rid = {}
        errors, stop = [], threading.Event()
        if self.open_loop:
            plan = traffic_gen.open_loop(self.mix, vocab, span, self.seed)
        else:
            clients = traffic_gen.closed_loop(self.mix, vocab, self.seed)
        gc.collect()
        gc.freeze()
        t_start = time.monotonic() + 0.05
        self.t_open = t_start + self.lead_in
        self.t_close = self.t_open + self.seconds
        thread = None
        if self.open_loop:
            thread = threading.Thread(
                target=self._submitter, args=(plan, t_start, stop, errors),
                daemon=True)
            thread.start()
        else:
            nxt = [0] * len(clients)
            for k, seq in enumerate(clients):
                self._submit(seq[0], time.monotonic())
                nxt[k] = 1
        opened = False
        while True:
            now = time.monotonic()
            if not opened and now >= self.t_open:
                opened = True
                self._stats0 = stats.sample_values("serv")
                self.tracer.start()
            if now >= self.t_close:
                break
            if errors:
                raise errors[0]
            done = self._step()
            if opened:
                self.tracer.poll()
            if not self.open_loop:
                for r in done:
                    rq = self._by_rid.get(r.id)
                    if rq is None:
                        continue
                    k = rq.client
                    if nxt[k] >= len(clients[k]):
                        raise RuntimeError(
                            "closed loop ran out of requests: raise "
                            "requests_per_client in the mix's file")
                    self._submit(clients[k][nxt[k]], time.monotonic())
                    nxt[k] += 1
        self.tracer.stop()
        self._stats1 = stats.sample_values("serv")
        stop.set()
        if thread is not None:
            thread.join()
        # every request that was due in the window is waited for: one that
        # comes late is late, not missing
        if self.open_loop:
            limit = time.monotonic() + DRAIN_LIMIT_S
            while time.monotonic() < limit and any(
                    r.state is None for r in self._due_in_window()):
                self._step()
        jr = eng.journal
        self._journal = jr.events() if jr is not None else []
        self._memory = self.sut.program_memory(eng)
        gc.unfreeze()

    def _due_in_window(self):
        if self.open_loop:
            return [r for r in self.reqs
                    if self.t_open <= r.due < self.t_close]
        return [r for r in self.reqs if r.sent < self.t_close
                and (r.done_t is None or r.done_t >= self.t_open)]

    def _unserved(self):
        """Requests that never came or ended badly. In a closed loop the
        ones still in flight at the close are neither."""
        return [r for r in self._due_in_window() if r.state != "ok"
                and (self.open_loop or r.state is not None)]

    def program_memory(self):
        return self._memory

    def release(self):
        """Drop the program's state so that the reference has the chip."""
        import jax

        for r in self.reqs:
            r.admitted = getattr(r.obj, "t_admitted", None)
            r.obj = None
        self._by_rid = {}
        self.engine = self.model = None
        gc.collect()
        jax.clear_caches()
        gc.collect()

    # ----------------------------------------------------------- correct

    def _sample(self):
        """Finished requests to check: the longest, and ``check_requests``
        more drawn from the seed."""
        done = [r for r in self.reqs if r.state == "ok"
                and r.done_t is not None and r.done_t >= self.t_open
                and len(r.tokens) == r.n_out]
        if not done:
            return []
        done.sort(key=lambda r: r.idx)
        longest = max(done, key=lambda r: len(r.prompt) + r.n_out)
        rng = np.random.RandomState((self.seed + 7) % (2 ** 32))
        n = min(int(self.mix.get("check_requests", 12)), len(done))
        picked = [done[i] for i in rng.permutation(len(done))[:n]]
        if longest not in picked:
            picked[0] = longest
        return picked

    def check(self, mode="f32"):
        """The widest gap by which a served token's reference score lies
        below the reference's best, over the sampled requests. ``mode``
        other than ``f32`` reads the control instead: the token that the
        lower precision puts first, at the same positions."""
        import jax.numpy as jnp

        ref = self.ref
        limit = float(self.cfg["correct"]["served_token_gap_limit"])
        sample = self._sample()
        w = ref.make_weights(self.seed, self.cfg)
        heads = int(self.cfg["n_heads"])
        widest, n_tok = 0.0, 0
        for rq in sample:
            p, n = len(rq.prompt), len(rq.tokens)
            ids = np.concatenate([rq.prompt, np.asarray(rq.tokens[:-1],
                                                        np.int32)])
            pad = -(-len(ids) // 512) * 512
            ids = np.pad(ids, (0, pad - len(ids))).astype(np.int32)
            rows = jnp.arange(p - 1, p - 1 + n, dtype=jnp.int32)
            lg = ref.logits(w, jnp.asarray(ids), heads=heads, mode="f32")
            if mode == "f32":
                toks = jnp.asarray(rq.tokens, jnp.int32)
            else:
                toks = ref.argmax_rows(
                    ref.logits(w, jnp.asarray(ids), heads=heads, mode=mode),
                    rows)
            g = np.asarray(ref.gaps(lg, rows, toks))
            if not np.all(np.isfinite(g)):
                widest = float("inf")
            widest = max(widest, float(g.max()))
            n_tok += n
        unserved = len(self._unserved())
        compared = {
            "served_token_gap": {"value": widest, "limit": limit},
            "tokens_checked": {"value": float(n_tok), "limit": 1.0},
            "requests_unserved": {"value": float(unserved), "limit": 0.0},
        }
        ok = bool(sample) and widest <= limit and n_tok >= 1 \
            and unserved == 0
        return {"correct": ok, "compared": compared}

    def control(self, mode):
        """What the control (``int8``, ``fp8``) reads at the same positions
        of the same requests: {name: value}."""
        return {k: c["value"] for k, c in self.check(mode)["compared"].items()}

    # ------------------------------------------------------------- facts

    def facts(self):
        due = self._due_in_window()
        ok = [r for r in due if r.state == "ok" and r.token_t]
        bad = self._unserved()
        t0, t1 = self.t_open, self.t_close
        f = {"requests": self.reqs, "due": due, "steps": self.steps,
             "journal": self._journal, "t_open": t0, "t_close": t1,
             "stats0": self._stats0, "stats1": self._stats1,
             "open_loop": self.open_loop,
             "attempted": len(due), "failed": len(bad)}
        # every token of every step, weighted by the share of the step's
        # interval that lies inside the window: a decode chunk hands over
        # 32 x 16 tokens in one burst, and counting bursts by their stamp
        # alone moves the rate by 1.3% when a window edge crosses one
        per_step = collections.Counter(
            s for r in self.reqs for s in r.token_step)
        tokens = 0.0
        for i, n in per_step.items():
            a, b = self.steps[i][0], self.steps[i][1]
            inside = min(b, t1) - max(a, t0)
            if inside > 0:
                tokens += n * inside / (b - a)
        f["serve_tok_s"] = tokens / self.seconds
        start = (lambda r: r.due) if self.open_loop else (lambda r: r.sent)
        ttft = [(r.token_t[0] - start(r)) * 1e3 for r in ok]
        tpot = [(r.token_t[-1] - r.token_t[0]) / (len(r.token_t) - 1) * 1e3
                for r in ok if len(r.token_t) > 1]
        if ttft:
            f["ttft_p95_ms"] = percentile(ttft, 95)
            f["ttft_p50_ms"] = percentile(ttft, 50)
        if tpot:
            f["tpot_p95_ms"] = percentile(tpot, 95)
            f["tpot_p50_ms"] = percentile(tpot, 50)
        return f
