"""The one general traffic generator. A mix is a data file under
``benchmark/traffic/``; this module turns its parameters and a seed into
requests.

Every seed gets the SAME multiset of prompt lengths, output lengths and
arrival gaps: sizes are the distribution's quantiles at (i + 0.5) / n,
shuffled, not n random draws. So the work in a run does not depend on the
seed. The ORDER is drawn from the run's seed, unless the mix's file fixes it
with ``schedule_seed`` (a replayed schedule: where a window holds only a
few tens of requests, the order decides the tails, and the run's seed then
draws the token ids and the weights alone).
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _normal_quantiles(q) -> np.ndarray:
    return np.array([NormalDist().inv_cdf(float(p)) for p in q])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of the distribution ``spec``
    names, clipped to [min, max], in ascending order."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = _normal_quantiles(q)
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", math.inf)
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def arrival_times(spec: dict, span_s: float, rng) -> np.ndarray:
    """Arrival offsets in [0, span_s): the gaps are the quantiles of the
    gap distribution, shuffled, and scaled so that they fill the span."""
    n = max(int(round(float(spec["rate_rps"]) * span_s)), 1)
    q = (np.arange(n) + 0.5) / n
    if spec["kind"] == "poisson":
        gaps = -np.log1p(-q)
    elif spec["kind"] == "gamma":            # bursty: cv > 1
        cv = float(spec.get("cv", 1.0))
        k = 1.0 / (cv * cv)
        # Wilson-Hilferty quantiles of a gamma of shape k
        z = _normal_quantiles(q)
        gaps = np.maximum(k * (1 - 1 / (9 * k) + z / (3 * math.sqrt(k))) ** 3,
                          1e-6)
    elif spec["kind"] == "uniform":
        gaps = np.ones(n)
    else:
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    gaps = rng.permutation(gaps)
    t = np.cumsum(gaps)
    return t * (span_s / t[-1]) - gaps[0] * (span_s / t[-1]) / 2.0


class Req:
    """One request of a run, with the harness's own stamps (seconds on
    ``time.monotonic``)."""

    __slots__ = ("idx", "prompt", "n_out", "due", "client", "sent", "rid",
                 "token_t", "token_step", "tokens", "obj", "state", "done_t",
                 "admitted")

    def __init__(self, idx, prompt, n_out, due=None, client=None):
        self.idx, self.prompt, self.n_out = idx, prompt, int(n_out)
        self.due, self.client = due, client
        self.sent = self.rid = self.obj = self.state = self.done_t = None
        self.admitted = None
        self.token_t, self.token_step, self.tokens = [], [], []


def _order_rng(mix, seed):
    """The stream that orders sizes and gaps: the mix's own where it fixes a
    schedule, else the run's."""
    s = mix.get("schedule_seed")
    return np.random.RandomState((seed + 1 if s is None else int(s))
                                 % (2 ** 32))


def _prompts(lens, vocab, rng):
    return [rng.randint(0, vocab, int(n)).astype(np.int32) for n in lens]


def open_loop(mix: dict, vocab: int, span_s: float, seed: int) -> list:
    """Requests of an open loop over ``span_s`` seconds (lead-in included),
    ordered by their due offset."""
    rng = np.random.RandomState(seed % (2 ** 32))
    order = _order_rng(mix, seed)
    due = arrival_times(mix["arrivals"], span_s, order)
    n = len(due)
    plen = order.permutation(quantile_lengths(mix["prompt_len"], n))
    olen = order.permutation(quantile_lengths(mix["output_len"], n))
    prompts = _prompts(plen, vocab, rng)
    return [Req(i, prompts[i], olen[i], due=float(due[i])) for i in range(n)]


def closed_loop(mix: dict, vocab: int, seed: int) -> list:
    """Per-client request sequences of a closed loop: ``clients`` lists of
    requests. Every round (one request of each client) holds the same
    multiset of sizes, dealt to the clients by the seed; each client's first
    request has its output cut to a staggered fraction so that the slots do
    not finish together."""
    rng = np.random.RandomState(seed % (2 ** 32))
    order = _order_rng(mix, seed)
    c = int(mix["arrivals"]["clients"])
    per = int(mix["arrivals"].get("requests_per_client", 8))
    stagger = order.permutation((np.arange(c) + 1.0) / c)
    lo = int(mix["output_len"].get("min", 1))
    out = [[] for _ in range(c)]
    for j in range(per):
        # every round is the same multiset of sizes, dealt anew
        plen = order.permutation(quantile_lengths(mix["prompt_len"], c))
        olen = order.permutation(quantile_lengths(mix["output_len"], c))
        prompts = _prompts(plen, vocab, rng)
        for k in range(c):
            n_out = int(olen[k])
            if j == 0:
                n_out = max(int(round(n_out * stagger[k])), min(lo, 16))
            out[k].append(Req(j * c + k, prompts[k], n_out, client=k))
    return out
