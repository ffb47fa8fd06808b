"""``family: mimo_v2_flash``: the plain float32 reference against the program
at toy widths on the CPU. Prefill in chunks longer and shorter than the toy
window (8 rows), at starts that are no multiple of a page and over bucketed
tails, then decode through both caches (the full layers' packed pool, the
window layers' ring) well past the window's edge: the program must agree
with the reference's ONE full pass, logit by logit. The reference imports
nothing of the program; each named fault moves the logits; the sixteen
shares of the experts, what every chip computes alike counted once, add up
to the uncut layer."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
import mimo_toy
from benchmark.models import mimo_v2_flash as sut
from benchmark.reference import mimo_v2_flash as ref
from paddle_tpu.nn.functional.mixed_attention import PackedKV, WindowRing

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def cfg():
    return mimo_toy.config()


@pytest.fixture(scope="module")
def built(cfg):
    return sut.build_engine(cfg, SEED)


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(bench_toy.REPO, "benchmark", "reference",
                        "mimo_v2_flash.py")
    tree = ast.parse(open(path).read())
    mods = {n.module or "" for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)} \
        | {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
           for a in n.names}
    assert not any(m.startswith(("paddle_tpu", "benchmark")) for m in mods)
    assert mods <= {"__future__", "functools", "math", "typing", "jax",
                    "jax.numpy"}


def test_the_description_is_the_published_layer_list(cfg):
    from paddle_tpu.incubate.nn.layer_pattern import ATTENTION, WINDOW

    p = sut.pattern(cfg)
    A, W = ATTENTION, WINDOW
    assert p.kinds() == (A, W, W, W, W, A, W)
    assert (p.dense_lead, p.n_moe, p.n_attention, p.n_window) == (1, 6, 2, 5)
    assert p.moe.routing == "sigmoid_bias"
    full = sut.pattern(dict(cfg, num_hidden_layers=48))
    assert full.kinds() == tuple(A if k == 0 else W
                                 for k in cfg["hybrid_layer_pattern"])


def test_weights_come_from_the_seed_alone(cfg):
    D = ref.dims(cfg)
    k1, k2 = ref.seed_key(SEED), ref.seed_key(SEED - 2 ** 31)
    a = ref.attention_weights(ref.layer_key(k1, 1), D, True)
    b = ref.attention_weights(ref.layer_key(k1, 1), D, True)
    c = ref.attention_weights(ref.layer_key(k2, 1), D, True)
    assert all(np.array_equal(a[n], b[n]) for n in a)
    assert not np.array_equal(a["q"], c["q"])
    assert "sink" in a and "sink" not in ref.attention_weights(
        ref.layer_key(k1, 0), D, False)
    bank1, bank2 = ref.expert_bank(ref.layer_key(k1, 1), D)
    w1, w2 = ref.expert_weights(ref.layer_key(k1, 1), D, D.held_first + 3)
    assert np.array_equal(bank1[3], w1) and np.array_equal(bank2[3], w2)
    pw = sut.program_attention_weights(a)
    assert np.array_equal(pw["qkv"][:, :D.heads * D.dk], a["q"])
    assert np.array_equal(pw["qkv"][:, -D.kv_window * D.dv:], a["v"])


def _through_the_caches(model, eng, ids, n_prompt, chunk, shift):
    """Logits of every position from ``n_prompt - 1`` on, the way the engine
    computes them: the prompt in bucketed chunks of at most ``chunk`` (the
    first one ``shift`` tokens short, so the later ones start inside a
    page), then one decode step a token, through the packed pool and the
    window ring of slot 1."""
    g, st = eng._gen, model.stack
    W, embed, head = g._weights(), g._embed(), g._head_t
    norm, _ = g._lnf()
    slot, bucket = 1, eng.prompt_bucket
    eng._mgr.allocate(("slot", slot), len(ids) + 1)
    tables = eng._mgr.block_tables(
        [("slot", i) for i in range(eng.max_batch)], eng._pages_per_seq,
        allow_missing=True)
    prefill = jax.jit(
        lambda x, pool, ring, tbl, start, n: st.prefill_chunk_raw(
            W, x, PackedKV(pool), ring, tbl, start, n, g._cos, g._sin,
            slot=jnp.asarray([slot]), rope_w=g._rope_w))
    decode = jax.jit(lambda x, pool, ring, lens, active: st.decode_raw(
        W, x, PackedKV(pool), ring, tables, lens, active, g._cos, g._sin,
        rope_w=g._rope_w))
    pool, ring, pos, rows = eng._ck, eng._rs, 0, []
    while pos < n_prompt:
        n = min(chunk - (shift if pos == 0 else 0), n_prompt - pos)
        c = -(-n // bucket) * bucket
        piece = np.zeros((1, c), np.int32)
        piece[0, :n] = ids[pos: pos + n]
        h, cache, ring, _ = prefill(
            g._embed_rows(embed, jnp.asarray(piece)), pool, ring,
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
        h, cache, ring, _ = decode(g._embed_rows(embed, tok), pool, ring,
                                   lens, active)
        pool = cache.rows
        rows.append(g._logits(h, head, norm)[slot])
    eng._mgr.free(("slot", slot))
    assert isinstance(ring, WindowRing)
    return np.asarray(jnp.stack(rows))


def _reference_rows(cfg, ids, rows, **kw):
    """The reference's logits at ``rows`` of ``ids``, computed over 96
    positions whatever the length (causal: what follows changes nothing),
    so that every test shares one compile."""
    full = np.zeros(96, np.int32)
    full[:len(ids)] = ids
    return ref.logits(ref.make_weights(SEED, cfg), jnp.asarray(full), **kw) \
        .rows(jnp.asarray(rows))


@pytest.mark.parametrize("n_prompt,chunk,shift", [
    (33, 32, 0),     # a chunk longer than the window, a bucketed tail
    (70, 32, 3),     # starts inside a page, the tail crosses the window
    (45, 6, 1),      # chunks shorter than the window
])
def test_prefill_then_decode_agrees_with_one_full_pass(cfg, built, n_prompt,
                                                       chunk, shift):
    """Both sides run float32 on the same bf16-valued weights and differ by
    summation order: the logits agree to a ten-thousandth of their
    deviation, through 14 decode steps past the prompt (past the window's
    edge in the ring)."""
    model, eng = built
    ids = np.random.RandomState(n_prompt).randint(
        0, cfg["vocab_size"], n_prompt + 14).astype(np.int32)
    got = _through_the_caches(model, eng, ids, n_prompt, chunk, shift)
    want = np.asarray(_reference_rows(cfg, ids,
                                      np.arange(n_prompt - 1, len(ids))))
    assert want.std() > 0.05
    assert np.abs(got - want).max() < 1e-4 * want.std()


EXPERT_FAULTS = ("bias_in_gates", "no_bias_in_choice", "no_experts")


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_named_fault_moves_the_logits(cfg, fault):
    """Past the toy window every attention fault of the comparison's list
    changes the reference's logits by far more than the program differs
    from it. The faults of the routed experts move the logits by less than
    a hundredth (the experts' W_d is small on purpose: section 2 of
    PERF.md); they move the experts' output itself by far more, and
    ``test_the_router_picks_by_bias_and_weights_by_score`` and
    ``test_the_sixteen_shares_add_up_to_the_uncut_layer`` hold the program
    to the form."""
    ids = np.random.RandomState(4).randint(0, cfg["vocab_size"],
                                           80).astype(np.int32)
    rows = np.arange(20, 80)
    good = np.asarray(_reference_rows(cfg, ids, rows))
    bad = np.asarray(_reference_rows(cfg, ids, rows, mode=fault))
    moved = np.abs(good - bad).max() / good.std()
    if fault not in EXPERT_FAULTS:
        assert moved > 1e-2
        return
    assert moved > 1e-4
    D = ref.dims(cfg)
    key = ref.layer_key(ref.seed_key(SEED), 1)
    fw = ref.ffn_weights(key, D, False)
    x = jnp.asarray(np.random.RandomState(6).randn(64, D.d), jnp.float32)
    y = np.asarray(ref.moe(x, key, fw, D, "f32"))
    yf = np.asarray(ref.moe(x, key, fw, D, "f32", fault=fault))
    assert np.abs(y - yf).max() > 1e-2 * np.abs(y).max()


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The cell's cut at toy widths: 32 routed experts over sixteen chips (2
    a chip). Attention, the norm and the router are what every chip
    computes alike (counted once); the shares of the routed experts,
    summed, are the uncut layer's experts."""
    cfg = mimo_toy.config(experts_held=[0, 32], n_routed_experts=32,
                          router_width=32)
    D = ref.dims(cfg)
    key = ref.layer_key(ref.seed_key(SEED), 1)
    x = jnp.asarray(np.random.RandomState(2).randn(96, D.d), jnp.float32)
    aw = ref.attention_weights(key, D, True)
    h = x + ref.attention(ref._rms(x, aw["norm"], D.eps), aw, D, True, "f32")
    fw = ref.ffn_weights(key, D, False)
    xn = ref._rms(h, fw["norm"], D.eps)
    whole = ref.moe(xn, key, fw, D, "f32")
    shares = [ref.moe(xn, key, fw, D, "f32", first=f, count=2)
              for f in range(0, 32, 2)]
    assert sum(float(jnp.abs(s).max()) > 0 for s in shares) >= 4
    assert np.allclose(np.asarray(sum(shares)), np.asarray(whole),
                       atol=1e-5 * float(jnp.abs(whole).max()))
    uncut = ref._layer(x, key, D=D, window=True, dense=False, mode="f32")
    assert np.allclose(np.asarray(h + sum(shares)), np.asarray(uncut),
                       atol=1e-5 * float(jnp.abs(uncut).max()))
    # the program's layer, told it holds experts 2..3, computes that share
    from paddle_tpu.nn.functional.moe_gated import (moe_gated_stream,
                                                    route_topk_sigmoid)

    gates, idx = route_topk_sigmoid(xn, fw["router"], fw["bias"], D.top_k)
    w1, w2 = jax.vmap(lambda e: ref.expert_weights(key, D, e))(
        2 + jnp.arange(2))
    got = moe_gated_stream(xn, gates, idx, w1[None], w2[None], 0, (2, 2))
    assert np.allclose(np.asarray(got), np.asarray(shares[1]), atol=1e-6)


def test_the_router_picks_by_bias_and_weights_by_score():
    x = jnp.asarray(np.random.RandomState(0).randn(6, 16), jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).randn(16, 8), jnp.float32)
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 50.0])
    from paddle_tpu.nn.functional.moe_gated import route_topk_sigmoid

    gates, idx = route_topk_sigmoid(x, w, bias, 2, scaling=2.0)
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(w))))
    assert np.all(np.asarray(idx)[:, 0] == 7)         # the bias picks it
    picked = np.take_along_axis(s, np.asarray(idx), 1)
    assert np.allclose(np.asarray(gates),
                       2.0 * picked / picked.sum(1, keepdims=True),
                       rtol=1e-5)


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
