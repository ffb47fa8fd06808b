"""A prefill chunk that is not its prompt's last is not waited for: the
serving engine keeps its result and the next program's dispatch waits for
it. The token streams and the pick counters are those of an engine that
waits for every chunk, at most one chunk is ever outstanding after a step,
and a fault that surfaces in the wait is charged to the deferred chunk's
own request, whichever step waited."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.layer_pattern import (
    ATTENTION, MAMBA, AttentionSpec, LayerPattern, MambaSpec, MoESpec)
from paddle_tpu.inference import FusedCausalLM
from paddle_tpu.inference.hybrid import HybridCausalLM
from paddle_tpu.profiler import stats
from paddle_tpu.serving import ServingEngine, SLOConfig
from paddle_tpu.serving.faults import FaultInjector, InjectedFault

VOCAB = 96
CHUNK = 16
#: one token, one chunk, two chunks, four chunks (the last bucket-padded)
LENS = (1, 9, 30, 53)


def _uniform():
    paddle.seed(7)
    return FusedCausalLM(vocab_size=VOCAB, embed_dim=32, num_heads=4,
                         dim_feedforward=64, num_layers=2,
                         max_position=128)


def _hybrid():
    """Mamba-2 + NoPE attention over routed experts, two of four held:
    a toy of the pattern-built cells (weights scaled up so mixers and
    experts move the logits)."""
    paddle.seed(3)
    m = HybridCausalLM(VOCAB, LayerPattern(
        d_model=64, period=(MAMBA, ATTENTION, MAMBA), n_periods=1,
        attention=AttentionSpec(4, 2, 16, scale=1 / 16.0, rope_theta=None),
        mamba=MambaSpec(num_heads=8, head_dim=16, d_state=16,
                        chunk_size=16),
        moe=MoESpec(4, 2, 32, shared_dim=48, experts_held=(0, 2)),
        norm="rmsnorm", gated=True, bias=False, activation="silu",
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=16.0))
    st = m.stack
    st.m_A_log._rebind(jnp.log(jnp.linspace(0.05, 1.0, 8))[None, :]
                       .repeat(2, 0).astype(jnp.float32))
    st.m_dt_bias._rebind(jnp.full((2, 8), -3.0, jnp.float32))
    for n in ("m_in", "m_out", "qkv_weight", "out_weight", "e_w1", "e_w2",
              "s_w1", "s_w2", "f_router"):
        p = getattr(st, n)
        p._rebind(p._data * 6.0)
    return m


_MODELS = {"uniform": _uniform, "hybrid": _hybrid}


class _WaitsForEvery(ServingEngine):
    """Waits for every chunk, as the engine did before chunks were
    deferred: the streams and counters the deferred engine must match."""

    def _last_chunk(self, stt, n):
        return True


def _engine(model, cls=ServingEngine, chunk=CHUNK, **kw):
    return cls(model, max_batch=kw.pop("max_batch", 3), page_size=4,
               max_length=96, decode_chunk=2, prompt_bucket=8,
               slo=SLOConfig(prefill_chunk=chunk), **kw)


def _prompts(seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n) for n in LENS]


def _drive(eng, prompts, n=6):
    """Submit, then step to the end; after every step at most one chunk
    is outstanding, and only while ``_deferred`` holds it."""
    waited = []
    settle = eng._settle
    eng._settle = lambda out: (waited.append(1), settle(out))
    base = stats.counter("serving.prefill.deferred").value
    ids = [eng.submit(list(p), max_new_tokens=n) for p in prompts]
    while eng.has_work:
        eng.step()
        outstanding = (stats.counter("serving.prefill.deferred").value
                       - base - len(waited))
        assert outstanding == (eng._deferred is not None)
    done = {r.id: r for r in eng.finished}
    assert all(done[i].state == "ok" for i in ids)
    return [list(done[i].generated) for i in ids]


def _dense_greedy(model, prompt, n):
    """Reference: a full re-forward each token, argmax of the last
    position."""
    seq = np.asarray(prompt, np.int64).reshape(1, -1)
    for _ in range(n):
        nxt = model(paddle.to_tensor(seq)).numpy()[:, -1].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    return list(seq[0, len(prompt):])


def _counters():
    snap = stats.snapshot("serv")["counters"]
    return {k: v for k, v in snap.items()
            if k.startswith(("serving.moe.", "serve.prefill_",
                             "serving.prefill.deferred"))}


@pytest.mark.parametrize("kind", sorted(_MODELS))
def test_streams_and_counters_equal_waiting_for_every_chunk(kind):
    """Served tokens equal those of an engine that waits for every chunk,
    those of one-chunk prefills and, for the uniform model, a dense
    re-forward; ``serving.prefill.deferred`` counts
    chunks minus prompts; the pick counters' totals after the drain are
    the same as with every chunk waited for."""
    m, prompts = _MODELS[kind](), _prompts()
    stats.reset()
    got = _drive(_engine(m), prompts)
    deferred = _counters()
    stats.reset()
    want = _drive(_engine(m, _WaitsForEvery), prompts)
    waited = _counters()
    assert got == want
    chunks = sum(-(-n // CHUNK) for n in LENS)
    assert deferred["serve.prefill_chunks"] == chunks
    assert deferred["serving.prefill.deferred"] == chunks - len(LENS)
    assert waited.get("serving.prefill.deferred", 0) == 0
    deferred.pop("serving.prefill.deferred")
    assert deferred == waited
    if kind == "hybrid":
        assert deferred["serving.moe.picks_here"] > 0
    whole = _drive(_engine(m, chunk=64), prompts)
    assert got == whole
    if kind == "uniform":
        assert got == [_dense_greedy(m, p, 6) for p in prompts]


def _served_with(kind, inj):
    """A short prompt decodes while a three-chunk one prefills, so a
    decode step waits for the long prompt's deferred chunks."""
    m = _MODELS[kind]()
    short, long_ = [np.arange(1, 7) % VOCAB,
                    np.random.RandomState(1).randint(0, VOCAB, 40)]
    eng = _engine(m, faults=inj)
    ids = [eng.submit(list(p), max_new_tokens=6) for p in (short, long_)]
    fired_in = []
    while eng.has_work:
        n0 = len(inj.fired)
        eng.step()
        fired_in += [eng.action_log[-1]] * (len(inj.fired) - n0)
    done = {r.id: r for r in eng.finished}
    ref = _drive(_engine(m, _WaitsForEvery), [short, long_])
    return eng, ids, done, ref, fired_in


def _fault_rids(eng):
    return [e["rid"] for e in eng.journal.events() if e["ev"] == "fault"]


@pytest.mark.parametrize("kind", sorted(_MODELS))
def test_a_transient_fault_in_the_wait_retries_the_chunks_request(kind):
    before = stats.counter("serving.prefill_requeues").value
    inj = FaultInjector().add("prefill.wait", kind="raise", at=0)
    eng, (a, b), done, ref, fired_in = _served_with(kind, inj)
    assert _fault_rids(eng) == [b]
    assert fired_in == ["decode"]      # a decode step waited
    assert [done[a].state, done[b].state] == ["ok", "ok"]
    assert [list(done[a].generated), list(done[b].generated)] == ref
    retries = [e for e in eng.journal.events() if e["ev"] == "retry"]
    assert [(e["rid"], e["phase"]) for e in retries] == [(b, "prefill")]
    assert eng._decode_retries == 0
    assert stats.counter("serving.prefill_requeues").value == before + 1


def test_a_persistent_fault_in_the_wait_errors_only_the_chunks_request():
    """Every wait for a deferred chunk raises: the long prompt exhausts
    its retries and errors out; the short prompt, whose one chunk is
    never deferred, and the decode steps that waited are untouched."""
    before = stats.counter("serving.request_errors").value
    inj = FaultInjector().add("prefill.wait", kind="raise", every=1,
                              times=-1)
    eng, (a, b), done, ref, fired_in = _served_with("uniform", inj)
    assert set(_fault_rids(eng)) == {b}
    assert "decode" in fired_in
    assert done[a].state == "ok" and list(done[a].generated) == ref[0]
    assert done[b].state == "error"
    assert isinstance(done[b].error, InjectedFault)
    assert stats.counter("serving.request_errors").value == before + 1
    assert not eng._prefilling and eng._deferred is None
    assert not [k for k in eng._mgr._owned if k[0] == "prefill"]
