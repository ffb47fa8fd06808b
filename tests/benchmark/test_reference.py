"""The plain float32 reference against the program, at toy size on the CPU:
the full forward pass, then chunked prefill and decode through the paged
cache behind ``ServingEngine``; and the control, which has to read wider
than the program does."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark.models import fused_causal_lm as sut
from benchmark.reference import fused_causal_lm as ref

VOCAB = 2048


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(bench_toy.REPO, "benchmark", "configs",
                           "gpt3-1.3b.json")) as f:
        c = json.load(f)
    c.update(d_model=128, n_layers=2, n_heads=4, head_dim=32, d_ff=512,
             vocab_size=VOCAB, serving=bench_toy.TOY_SERVING)
    return c


def test_weights_come_from_the_seed_alone(cfg):
    a, b = ref.make_weights(2 ** 31 + 17, cfg), ref.make_weights(2 ** 31 + 17,
                                                                  cfg)
    c = ref.make_weights(17, cfg)
    assert all(np.array_equal(np.asarray(a[k], np.float32),
                              np.asarray(b[k], np.float32)) for k in a)
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(c["embed"]))
    assert a["qkv_weight"].dtype == jnp.bfloat16
    # the embedding is float32 but holds bf16 values: program and reference
    # then read the same numbers whichever way they cast
    e = np.asarray(a["embed"])
    assert np.array_equal(e, np.asarray(jnp.asarray(e).astype(jnp.bfloat16)
                                        .astype(jnp.float32)))


def test_full_forward_agrees(cfg):
    """``FusedCausalLM.forward`` (no cache) on the seeded weights against
    the reference. Both run float32 arithmetic on the same bf16-valued
    weights here, so they differ by summation order only: logits of
    deviation 0.2 agree to 2e-4 (measured 3e-5). A wrong rotary convention,
    QKV layout, GELU variant or epsilon moves them by 1e-2 or more."""
    model, _ = sut.build_engine(cfg, 5)
    ids = np.random.RandomState(0).randint(0, VOCAB, 96).astype(np.int32)
    got = np.asarray(model(jnp.asarray(ids)[None])._data, np.float32)[0]
    want = np.asarray(ref.logits(ref.make_weights(5, cfg), jnp.asarray(ids),
                                 heads=cfg["n_heads"]))
    assert want.std() > 0.1
    assert np.max(np.abs(got - want)) < 2e-4


def _serve(engine, prompts, n_out):
    for p in prompts:
        engine.submit(p.tolist(), max_new_tokens=n_out)
    done = sorted(engine.run(), key=lambda r: r.id)
    assert all(r.state == "ok" for r in done)
    return [list(r.generated) for r in done]


def _widest_gap(cfg, seed, prompts, served, mode="f32"):
    w = ref.make_weights(seed, cfg)
    widest = 0.0
    for p, toks in zip(prompts, served):
        ids = jnp.asarray(np.concatenate([p, toks[:-1]]).astype(np.int32))
        rows = jnp.arange(len(p) - 1, len(p) - 1 + len(toks))
        lg = ref.logits(w, ids, heads=cfg["n_heads"])
        if mode != "f32":
            toks = ref.argmax_rows(
                ref.logits(w, ids, heads=cfg["n_heads"], mode=mode), rows)
        widest = max(widest, float(np.max(np.asarray(
            ref.gaps(lg, rows, jnp.asarray(toks, jnp.int32))))))
    return widest


def test_served_tokens_agree_and_the_control_does_not(cfg):
    """Prompts of 40..100 tokens go through chunked prefill (chunks of 32
    and padded tails) and then 120 decode steps through the paged cache, in
    bf16. Each served token's reference score may lie below the reference's
    best only by what bf16 activations move a logit: under 0.02 here on
    logits of deviation 0.2 (measured 0.0 over 480 tokens: no near-tie was
    flipped). The controls are read at the same positions: at this width
    int8 flips none either, fp8 does (measured 0.035), so fp8 is the control
    that a test of this size can hold; on the chip at the real width both
    read wider than the program (PERF.md, section 2)."""
    seed = 9
    _, engine = sut.build_engine(cfg, seed)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32)
               for n in (40, 64, 77, 100)]
    served = _serve(engine, prompts, 120)
    program = _widest_gap(cfg, seed, prompts, served)
    int8 = _widest_gap(cfg, seed, prompts, served, "int8")
    fp8 = _widest_gap(cfg, seed, prompts, served, "fp8")
    assert program < 0.02
    assert fp8 >= int8 >= program
    assert fp8 > 3 * max(program, 1e-3)


def test_an_altered_token_reads_far_off(cfg):
    seed = 9
    _, engine = sut.build_engine(cfg, seed)
    prompts = [np.random.RandomState(2).randint(0, VOCAB, 50)
               .astype(np.int32)]
    served = _serve(engine, prompts, 12)
    served[0][5] = (served[0][5] + 1) % VOCAB
    assert _widest_gap(cfg, seed, prompts, served) > 0.1
