"""``family: granite_hybrid``: the plain float32 reference against the
program at toy widths on the CPU. Prefill in chunks and then decode through
the paged pool and the recurrent state must agree with the reference's ONE
full pass, logit by logit; prompt lengths straddle a prefill chunk (32), a
bucket pad (8) and the SSD chunk (16). The reference imports nothing of the
program; the fp8 control reads over the cell's kind of limit; a half of the
experts is visibly not the whole layer."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
import granite_toy
from benchmark.models import granite_hybrid as sut
from benchmark.reference import granite_hybrid as ref
from paddle_tpu.incubate.nn.fused_transformer import PagedKV
from paddle_tpu.incubate.nn.hybrid_stack import RecurrentState

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def cfg():
    return granite_toy.config()


@pytest.fixture(scope="module")
def built(cfg):
    return sut.build_engine(cfg, SEED)


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(bench_toy.REPO, "benchmark", "reference",
                        "granite_hybrid.py")
    tree = ast.parse(open(path).read())
    mods = {n.module or "" for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)} \
        | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
           for a in n.names}
    assert not any(m.startswith(("paddle_tpu", "benchmark")) for m in mods)
    assert mods <= {"__future__", "functools", "math", "typing", "jax",
                    "jax.numpy"}


def test_weights_come_from_the_seed_alone(cfg):
    D = ref.dims(cfg)
    k1, k2 = ref.seed_key(SEED), ref.seed_key(SEED - 2 ** 31)
    a = ref.mixer_weights(ref.layer_key(k1, 1), D, "mamba")
    b = ref.mixer_weights(ref.layer_key(k1, 1), D, "mamba")
    c = ref.mixer_weights(ref.layer_key(k2, 1), D, "mamba")
    assert all(np.array_equal(a[n], b[n]) for n in a)
    assert not np.array_equal(a["in"], c["in"])
    # matrices hold bf16 values; the recurrence's own initialisation
    w = np.asarray(a["in"])
    assert np.array_equal(w, np.asarray(jnp.asarray(w).astype(jnp.bfloat16)
                                        .astype(jnp.float32)))
    A = np.exp(np.asarray(a["A_log"]))
    assert A.min() >= 1.0 and A.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(a["dt_bias"])))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    # the program's bank is the reference's experts, one by one
    bank1, bank2 = ref.expert_bank(ref.layer_key(k1, 2), D)
    w1, w2 = ref.expert_weights(ref.layer_key(k1, 2), D, D.held_first + 3)
    assert np.array_equal(bank1[3], w1) and np.array_equal(bank2[3], w2)


def _through_the_cache(model, eng, ids, n_prompt):
    """Logits of every position from ``n_prompt - 1`` on, the way the engine
    computes them: the prompt in bucketed chunks that carry the state, then
    one decode step a token, all through the pool and the slot's state."""
    g, st = eng._gen, model.stack
    W, embed = g._weights(), g._embed()
    norm, _ = g._lnf()
    slot, chunk, bucket = 1, eng.slo.prefill_chunk, eng.prompt_bucket
    eng._mgr.allocate(("slot", slot), len(ids) + 1)
    tables = eng._mgr.block_tables(
        [("slot", i) for i in range(eng.max_batch)], eng._pages_per_seq,
        allow_missing=True)
    ck, cv, rs = eng._ck, eng._cv, eng._rs
    ssm, conv = jnp.zeros_like(rs.ssm[:, slot]), \
        jnp.zeros_like(rs.conv[:, slot])
    rows, pos = [], 0
    while pos < n_prompt:
        n = min(chunk, n_prompt - pos)
        c = min(-(-n // bucket) * bucket, chunk)
        piece = np.zeros((1, c), np.int32)
        piece[0, :n] = ids[pos: pos + n]
        h, cache, (ssm, conv), _ = st.prefill_chunk_raw(
            W, g._embed_rows(embed, jnp.asarray(piece)), PagedKV(ck, cv),
            (ssm, conv), tables[slot: slot + 1],
            jnp.asarray([pos], jnp.int32), jnp.asarray([n], jnp.int32))
        ck, cv = cache.k, cache.v
        pos += n
        last = h[0, n - 1]
    rows.append(g._logits(last[None], embed, norm)[0])
    rs = RecurrentState(rs.ssm.at[:, slot].set(ssm),
                        rs.conv.at[:, slot].set(conv))
    active = jnp.arange(eng.max_batch) == slot
    for t in range(n_prompt, len(ids)):
        tok = jnp.zeros((eng.max_batch,), jnp.int32).at[slot].set(ids[t])
        lens = jnp.zeros((eng.max_batch,), jnp.int32).at[slot].set(t)
        h, cache, rs, _ = st.decode_raw(
            W, g._embed_rows(embed, tok), PagedKV(ck, cv), rs, tables,
            lens, active)
        ck, cv = cache.k, cache.v
        rows.append(g._logits(h, embed, norm)[slot])
    eng._mgr.free(("slot", slot))
    return np.asarray(jnp.stack(rows)), rs


@pytest.mark.parametrize("n_prompt", [7, 16, 33, 70])
def test_prefill_then_decode_agrees_with_one_full_pass(cfg, built, n_prompt):
    """Both sides run float32 on the same bf16-valued weights and differ by
    summation order and by the chunked form of the scan: the logits agree
    to a thousandth of their deviation (measured a tenth of that). A scale of 1/sqrt(16)
    for 1/16, a softmax over all experts, a padded row that advances the
    state or a tail of padded rows each move them by 1e-3 or more."""
    model, eng = built
    ids = np.random.RandomState(n_prompt).randint(
        0, cfg["vocab_size"], n_prompt + 9).astype(np.int32)
    got, rs = _through_the_cache(model, eng, ids, n_prompt)
    want = np.asarray(ref.logits(ref.make_weights(SEED, cfg),
                                 jnp.asarray(ids)))[n_prompt - 1:]
    assert want.std() > 2e-4
    assert np.max(np.abs(got - want)) < 1e-3 * want.std()
    # idle slots saw every decode step and kept their (zero) state
    assert float(jnp.abs(rs.ssm[:, 0]).max()) == 0.0
    assert float(jnp.abs(rs.ssm[:, 1]).max()) > 0.0


def test_served_tokens_lie_on_the_references_best(cfg, built):
    _, eng = built
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (5, 37, 70, 33, 16, 64)]
    ids = [eng.submit(p.tolist(), max_new_tokens=9) for p in prompts]
    done = {r.id: r for r in eng.run()}
    w = ref.make_weights(SEED, cfg)
    for rid, p in zip(ids, prompts):
        toks = np.asarray(done[rid].generated, np.int32)
        assert done[rid].state == "ok" and len(toks) == 9
        lg = ref.logits(w, jnp.asarray(np.concatenate([p, toks[:-1]])))
        rows = jnp.arange(len(p) - 1, len(p) - 1 + len(toks))
        assert float(ref.gaps(lg, rows, jnp.asarray(toks)).max()) \
            < 1e-3 * float(lg.std())


def test_the_control_reads_over_the_limit(cfg):
    """float8 linear layers at the same positions: the token the control
    puts first lies further below the reference's best than the limit
    allows, on every seed."""
    limit = cfg["correct"]["served_token_gap_limit"]
    for seed in (1, 2, 3):
        w = ref.make_weights(seed, cfg)
        ids = jnp.asarray(np.random.RandomState(seed).randint(
            0, cfg["vocab_size"], 96).astype(np.int32))
        rows = jnp.arange(32, 96)
        lg = ref.logits(w, ids)
        ctl = ref.argmax_rows(ref.logits(w, ids, mode="fp8"), rows)
        assert float(ref.gaps(lg, rows, ctl).max()) > limit


def test_half_the_experts_is_not_the_layer_and_two_halves_are(cfg):
    """The reference's own share test: experts 0..3 and 4..7 with the shared
    MLP counted once add up to all 8."""
    D = ref.dims(dict(cfg, experts_held=[0, 8]))
    key = ref.layer_key(ref.seed_key(SEED), 0)
    fw = ref.ffn_weights(key, D)
    x = jnp.asarray(np.random.RandomState(0).randn(24, D.d), jnp.float32)
    whole = ref.moe(x, key, fw, D, "f32")
    lo = ref.moe(x, key, fw, D, "f32", first=0, count=4)
    hi = ref.moe(x, key, fw, D, "f32", first=4, count=4)
    np.testing.assert_allclose(lo + hi, whole, atol=1e-6)
    assert float(jnp.abs(lo - whole).max()) > 1e-4
    gates, idx = ref.route(x, fw["router"], D, "f32")
    assert idx.shape == (24, 3) and int(idx.max()) <= 7
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)


def test_program_share_equals_reference_share(cfg):
    """The program told it holds experts 4..7 computes the reference's
    second half (a pick of 0..3 adds nothing, on both sides)."""
    c = dict(cfg, experts_held=[4, 4])
    model, eng = sut.build_engine(c, SEED)
    ids = np.random.RandomState(5).randint(0, c["vocab_size"], 30) \
        .astype(np.int32)
    got, _ = _through_the_cache(model, eng, ids, 21)
    want = np.asarray(ref.logits(ref.make_weights(SEED, c),
                                 jnp.asarray(ids)))[20:]
    assert np.max(np.abs(got - want)) < 1e-3 * want.std()
    other = np.asarray(ref.logits(ref.make_weights(SEED, cfg),
                                  jnp.asarray(ids)))[20:]
    assert np.max(np.abs(other - want)) > 1e-2 * want.std()
