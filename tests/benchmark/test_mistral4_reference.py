"""``family: mistral4_mla``: the plain float32 reference against the program
at toy widths on the CPU. Prefill in chunks (bucketed, at starts that are no
multiple of a page) and then decode through the latent pool must agree with
the reference's ONE full pass, logit by logit; positions lie past the toy
``original_max_position_embeddings`` (16), so the YaRN blend and the query
temperature act. The reference imports nothing of the program; its absorbed
form equals its expanded form; the four shares of the experts, the shared
expert counted once, add up to the uncut layer."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
import mistral4_toy
from benchmark.models import mistral4_mla as sut
from benchmark.reference import mistral4_mla as ref
from paddle_tpu.nn.functional.mla_attention import LatentKV

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def cfg():
    return mistral4_toy.config()


@pytest.fixture(scope="module")
def built(cfg):
    return sut.build_engine(cfg, SEED)


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(bench_toy.REPO, "benchmark", "reference",
                        "mistral4_mla.py")
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
    a = ref.attention_weights(ref.layer_key(k1, 1), D)
    b = ref.attention_weights(ref.layer_key(k1, 1), D)
    c = ref.attention_weights(ref.layer_key(k2, 1), D)
    assert all(np.array_equal(a[n], b[n]) for n in a)
    assert not np.array_equal(a["uq"], c["uq"])
    w = np.asarray(a["ukv"])                    # matrices hold bf16 values
    assert np.array_equal(w, np.asarray(jnp.asarray(w).astype(jnp.bfloat16)
                                        .astype(jnp.float32)))
    bank1, bank2 = ref.expert_bank(ref.layer_key(k1, 1), D)
    w1, w2 = ref.expert_weights(ref.layer_key(k1, 1), D, D.held_first + 3)
    assert np.array_equal(bank1[3], w1) and np.array_equal(bank2[3], w2)
    # the program's layout is the reference's matrices, moved not changed
    pw = sut.program_attention_weights(a, D)
    ukv = np.asarray(a["ukv"]).reshape(D.kv_rank, D.heads, D.nope + D.v)
    assert np.array_equal(pw["l_uk"][2], ukv[:, 2, :D.nope].T)
    assert np.array_equal(pw["l_uv"][1], ukv[:, 1, D.nope:])
    assert pw["l_dkv"].shape == (D.d, 128) \
        and not np.asarray(pw["l_dkv"][:, D.kv_rank + D.rope:]).any()


def _through_the_cache(model, eng, ids, n_prompt, shift=0):
    """Logits of every position from ``n_prompt - 1`` on, the way the engine
    computes them: the prompt in bucketed chunks (the first one ``shift``
    tokens short, so the later ones start inside a page), then one decode
    step a token, all through the latent pool."""
    g, st = eng._gen, model.stack
    W, embed, head = g._weights(), g._embed(), g._head_t
    norm, _ = g._lnf()
    slot, chunk, bucket = 1, eng.slo.prefill_chunk, eng.prompt_bucket
    eng._mgr.allocate(("slot", slot), len(ids) + 1)
    tables = eng._mgr.block_tables(
        [("slot", i) for i in range(eng.max_batch)], eng._pages_per_seq,
        allow_missing=True)
    prefill = jax.jit(lambda x, pool, tbl, start, n: st.prefill_chunk_raw(
        W, x, LatentKV(pool), None, tbl, start, n, g._cos, g._sin))
    decode = jax.jit(lambda x, pool, lens, active: st.decode_raw(
        W, x, LatentKV(pool), None, tables, lens, active, g._cos, g._sin))
    pool, pos, rows = eng._ck, 0, []
    while pos < n_prompt:
        n = min(chunk - (shift if pos == 0 else 0), n_prompt - pos)
        c = min(-(-n // bucket) * bucket, chunk)
        piece = np.zeros((1, c), np.int32)
        piece[0, :n] = ids[pos: pos + n]
        h, cache, _, _ = prefill(
            g._embed_rows(embed, jnp.asarray(piece)), pool,
            tables[slot: slot + 1], jnp.asarray([pos], jnp.int32),
            jnp.asarray([n], jnp.int32))
        pool = cache.rows
        pos += n
        last = h[0, n - 1]
    rows.append(g._logits(last[None], head, norm)[0])
    active = jnp.arange(eng.max_batch) == slot
    for t in range(n_prompt, len(ids)):
        tok = jnp.zeros((eng.max_batch,), jnp.int32).at[slot].set(ids[t])
        lens = jnp.zeros((eng.max_batch,), jnp.int32).at[slot].set(t)
        h, cache, _, _ = decode(g._embed_rows(embed, tok), pool, lens,
                                active)
        pool = cache.rows
        rows.append(g._logits(h, head, norm)[slot])
    eng._mgr.free(("slot", slot))
    return np.asarray(jnp.stack(rows))


def _reference_rows(cfg, ids, rows, **kw):
    """The reference's logits at ``rows`` of ``ids``, computed over 96
    positions whatever the length (causal: what follows changes nothing),
    so that every test shares one compile."""
    full = np.zeros(96, np.int32)
    full[:len(ids)] = ids
    return ref.logits(ref.make_weights(SEED, cfg), jnp.asarray(full), **kw) \
        .rows(jnp.asarray(rows))


@pytest.mark.parametrize("n_prompt,shift", [(33, 0), (70, 0), (45, 3)])
def test_prefill_then_decode_agrees_with_one_full_pass(cfg, built, n_prompt,
                                                       shift):
    """Both sides run float32 on the same bf16-valued weights and differ by
    summation order and by the absorbed form: the logits agree to a
    ten-thousandth of their deviation."""
    model, eng = built
    ids = np.random.RandomState(n_prompt).randint(
        0, cfg["vocab_size"], n_prompt + 4).astype(np.int32)
    got = _through_the_cache(model, eng, ids, n_prompt, shift)
    want = np.asarray(_reference_rows(cfg, ids,
                                      np.arange(n_prompt - 1, len(ids))))
    assert want.std() > 0.05
    assert np.abs(got - want).max() < 1e-4 * want.std()


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_named_fault_shows_in_the_layer(cfg, fault):
    """Past position 16 the blend, the ``m^2``, the temperature and the
    latent's norm all act: leaving one out moves the attention's output by
    far more than the program differs from the reference."""
    D = ref.dims(cfg)
    aw = ref.attention_weights(ref.layer_key(ref.seed_key(SEED), 0), D)
    x = jnp.asarray(np.random.RandomState(3).randn(64, D.d), jnp.float32)
    good = np.asarray(ref.latent_attention(x, aw, D, "f32"))[32:]
    bad = np.asarray(ref.latent_attention(x, aw, D, "f32", fault))[32:]
    assert np.abs(good - bad).max() > 1e-2 * good.std()


def test_the_rotary_table_and_the_temperature(cfg):
    D = ref.dims(cfg)
    plain = np.asarray(ref.yarn_inv_freq(D, blend=False))
    blend = np.asarray(ref.yarn_inv_freq(D))
    assert np.allclose(plain, 10000.0 ** (-np.arange(0, 8, 2) / 8))
    # the fastest pair keeps its frequency, the slowest is divided by factor
    assert blend[0] == plain[0] and np.isclose(blend[-1], plain[-1] / 8)
    assert np.all(blend <= plain) and np.all(blend >= plain / 8 * 0.999)
    a = np.asarray(ref.query_temperature(jnp.asarray([0, 15, 16, 47, 48]),
                                         D))
    assert np.allclose(a, [1, 1, 1 + 0.3 * np.log(2), 1 + 0.3 * np.log(3),
                           1 + 0.3 * np.log(4)])
    m = 0.1 * np.log(8) + 1
    assert np.isclose(D.softmax_scale, 16 ** -0.5 * m * m)
    # adjacent pairs rotate, norms are kept
    x = jnp.asarray(np.random.RandomState(0).randn(5, 8), jnp.float32)
    y = ref.rope_interleaved(x, jnp.arange(5) + 20, ref.yarn_inv_freq(D))
    assert np.allclose(np.asarray(x[:, 0:2] ** 2).sum(-1),
                       np.asarray(y[:, 0:2] ** 2).sum(-1), rtol=1e-5)
    # the program's table is the reference's
    from paddle_tpu.incubate.nn.layer_pattern import YarnSpec
    from paddle_tpu.nn.functional.mla_attention import yarn_inv_freq

    assert np.allclose(
        np.asarray(yarn_inv_freq(8, 10000.0, YarnSpec(8, 16, 4, 1, 1, 1))),
        blend)


def test_absorbed_equals_expanded(cfg):
    D = ref.dims(cfg)
    aw = ref.attention_weights(ref.layer_key(ref.seed_key(SEED), 0), D)
    x = jnp.asarray(np.random.RandomState(1).randn(50, D.d), jnp.float32)
    a = np.asarray(ref.latent_attention(x, aw, D, "f32"))
    b = np.asarray(ref.latent_attention_absorbed(x, aw, D))
    assert np.abs(a - b).max() < 1e-4 * np.abs(a).max()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The cell's cut at toy widths: 8 routed experts over four chips (2 / 2
    / 2 / 2). What every chip computes alike (the shared expert) counted
    once, the shares of the routed experts summed: the uncut layer."""
    cfg = mistral4_toy.config(experts_held=[0, 8], n_routed_experts=8)
    D = ref.dims(cfg)
    key = ref.layer_key(ref.seed_key(SEED), 1)
    fw = ref.ffn_weights(key, D)
    x = jnp.asarray(np.random.RandomState(2).randn(24, D.d), jnp.float32)
    whole = ref.moe(x, key, fw, D, "f32")
    shares = [ref.moe(x, key, fw, D, "f32", first=f, count=2)
              for f in (0, 2, 4, 6)]
    assert all(float(jnp.abs(s).max()) > 0 for s in shares)
    assert np.allclose(np.asarray(sum(shares)), np.asarray(whole),
                       atol=1e-5 * float(jnp.abs(whole).max()))
    shared = ref.gated(x, fw["s_w1"], fw["s_w2"], "f32")
    layer = whole + shared
    assert not np.allclose(np.asarray(sum(shares) + 4 * shared),
                           np.asarray(layer), atol=1e-3)
    # the program's layer, told it holds experts 2..3, computes that share
    from paddle_tpu.nn.functional.moe_gated import (moe_gated_stream,
                                                    route_topk_softmax)

    gates, idx = route_topk_softmax(x, fw["router"], D.top_k)
    w1, w2 = jax.vmap(lambda e: ref.expert_weights(key, D, e))(
        2 + jnp.arange(2))
    got = moe_gated_stream(x, gates, idx, w1[None], w2[None], 0, (2, 2))
    assert np.allclose(np.asarray(got), np.asarray(shares[1]), atol=1e-5)


def test_fp8_control_reads_far_over_the_limit(cfg):
    """The comparison that decides ``correct``, on the reference rounded
    through float8: the token it puts first lies far below the float32
    reference's best (the program's own reading: the rehearsal)."""
    ids = np.random.RandomState(9).randint(0, cfg["vocab_size"],
                                           64).astype(np.int32)
    full = np.zeros(96, np.int32)
    full[:64] = ids
    w, rows = ref.make_weights(SEED, cfg), jnp.arange(39, 63)
    f32 = ref.logits(w, jnp.asarray(full))
    best = ref.argmax_rows(f32, rows)
    assert float(ref.gaps(f32, rows, best).max()) == 0.0
    fp8 = ref.argmax_rows(ref.logits(w, jnp.asarray(full), mode="fp8"), rows)
    assert float(ref.gaps(f32, rows, fp8).max()) \
        > 10 * cfg["correct"]["served_token_gap_limit"]
