"""BERT-base DP anchor (BASELINE configs[2]; VERDICT r3 missing #3).

Reference exemplar: test/legacy_test/test_dist_base.py:962 — a DP
pretraining run whose 2-proc gradients/params match the single-proc
run over the same global batch.
"""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.text.models import (BertForPretraining,
                                    BertPretrainingCriterion, bert_tiny)


def _batch(rng, b=4, s=16, vocab=128):
    ids = rng.randint(0, vocab, (b, s))
    types = rng.randint(0, 2, (b, s))
    mask = np.ones((b, s), np.int64)
    mlm_labels = np.where(rng.rand(b, s) < 0.15,
                          rng.randint(0, vocab, (b, s)), -100)
    nsp = rng.randint(0, 2, (b,))
    return ids, types, mask, mlm_labels, nsp


class TestBertModel:
    def test_shapes_and_pooler(self):
        paddle.seed(0)
        model = bert_tiny()
        rng = np.random.RandomState(0)
        ids, types, mask, _, _ = _batch(rng)
        seq, pooled = model(paddle.to_tensor(ids),
                            paddle.to_tensor(types),
                            paddle.to_tensor(mask))
        assert list(seq.shape) == [4, 16, 32]
        assert list(pooled.shape) == [4, 32]

    def test_attention_mask_zeroes_pad_influence(self):
        paddle.seed(0)
        model = bert_tiny()
        model.eval()
        rng = np.random.RandomState(1)
        ids, types, _, _, _ = _batch(rng)
        full = np.ones((4, 16), np.int64)
        half = full.copy()
        half[:, 8:] = 0
        ids2 = ids.copy()
        ids2[:, 8:] = rng.randint(0, 128, (4, 8))  # junk in masked tail
        s1, _ = model(paddle.to_tensor(ids), paddle.to_tensor(types),
                      paddle.to_tensor(half))
        s2, _ = model(paddle.to_tensor(ids2), paddle.to_tensor(types),
                      paddle.to_tensor(half))
        np.testing.assert_allclose(s1.numpy()[:, :8],
                                   s2.numpy()[:, :8], atol=1e-5)

    def test_mlm_head_tied_and_criterion_masking(self):
        paddle.seed(0)
        model = BertForPretraining(bert_tiny())
        crit = BertPretrainingCriterion()
        rng = np.random.RandomState(2)
        ids, types, mask, mlm, nsp = _batch(rng)
        mlm_logits, nsp_logits = model(
            paddle.to_tensor(ids), paddle.to_tensor(types),
            paddle.to_tensor(mask))
        assert list(mlm_logits.shape) == [4, 16, 128]
        assert list(nsp_logits.shape) == [4, 2]
        loss = crit(mlm_logits, nsp_logits, paddle.to_tensor(mlm),
                    paddle.to_tensor(nsp))
        assert np.isfinite(float(loss.numpy()))
        # all-unmasked labels: loss reduces to NSP CE alone
        no_mlm = np.full_like(mlm, -100)
        loss2 = crit(mlm_logits, nsp_logits, paddle.to_tensor(no_mlm),
                     paddle.to_tensor(nsp))
        ref_nsp = F.cross_entropy(nsp_logits,
                                  paddle.to_tensor(nsp.reshape(-1)))
        np.testing.assert_allclose(float(loss2.numpy()),
                                   float(ref_nsp.numpy()), rtol=1e-5)

    def test_decoder_bias_gets_eager_tape_grad(self):
        """ADVICE r4 regression: the MLM decoder bias must be a
        trainable leaf on the eager autograd tape (the DataParallel /
        hapi path), not just under jit.TrainStep."""
        paddle.seed(0)
        model = BertForPretraining(bert_tiny())
        crit = BertPretrainingCriterion()
        rng = np.random.RandomState(4)
        ids, types, mask, mlm, nsp = _batch(rng)
        mlm_logits, nsp_logits = model(
            paddle.to_tensor(ids), paddle.to_tensor(types),
            paddle.to_tensor(mask))
        loss = crit(mlm_logits, nsp_logits, paddle.to_tensor(mlm),
                    paddle.to_tensor(nsp))
        loss.backward()
        g = model.decoder_bias.grad
        assert g is not None
        assert float(np.abs(g.numpy()).sum()) > 0

    def test_pretraining_converges_in_train_step(self):
        paddle.seed(0)
        model = BertForPretraining(bert_tiny())
        crit = BertPretrainingCriterion()
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        # TrainStep calls loss_fn(*outs, *labels) == crit's signature
        step = paddle.jit.TrainStep(model, crit, opt)
        rng = np.random.RandomState(3)
        ids, types, mask, mlm, nsp = _batch(rng)
        args = [paddle.to_tensor(ids), paddle.to_tensor(types),
                paddle.to_tensor(mask)]
        labels = [paddle.to_tensor(mlm), paddle.to_tensor(nsp)]
        l0 = float(step(args, labels).numpy())
        for _ in range(30):
            loss = step(args, labels)
        assert float(loss.numpy()) < l0 * 0.7, \
            (l0, float(loss.numpy()))


WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.text.models import (BertForPretraining,
                                        BertPretrainingCriterion,
                                        bert_tiny)

    dist.init_parallel_env()
    rank = dist.get_rank()

    paddle.seed(0)
    # dropout off: parity compares exact trajectories across RNG streams
    model = BertForPretraining(bert_tiny(hidden_dropout_prob=0.0))
    model = dist.DataParallel(model)
    crit = BertPretrainingCriterion()
    opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())

    rng = np.random.RandomState(10)
    for step in range(4):
        # global batch 8: rank r takes rows [4r:4r+4]
        ids = rng.randint(0, 128, (8, 16))
        types = rng.randint(0, 2, (8, 16))
        mask = np.ones((8, 16), np.int64)
        mlm = np.where(rng.rand(8, 16) < 0.15,
                       rng.randint(0, 128, (8, 16)), -100)
        nsp = rng.randint(0, 2, (8,))
        sl = slice(4 * rank, 4 * rank + 4)
        ml, nl = model(paddle.to_tensor(ids[sl]),
                       paddle.to_tensor(types[sl]),
                       paddle.to_tensor(mask[sl]))
        loss = crit(ml, nl, paddle.to_tensor(mlm[sl]),
                    paddle.to_tensor(nsp[sl]))
        loss.backward()          # DataParallel hook averages grads
        opt.step()
        opt.clear_grad()

    w = np.asarray(model._layers.bert.pooler_dense.weight._data)
    np.save(os.environ["BERT_OUT"] + f".{rank}.npy", w)
    print(f"RANK{rank}_OK")
""")

SINGLE = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.text.models import (BertForPretraining,
                                        BertPretrainingCriterion,
                                        bert_tiny)

    paddle.seed(0)
    model = BertForPretraining(bert_tiny(hidden_dropout_prob=0.0))
    crit = BertPretrainingCriterion()
    opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())

    rng = np.random.RandomState(10)
    for step in range(4):
        ids = rng.randint(0, 128, (8, 16))
        types = rng.randint(0, 2, (8, 16))
        mask = np.ones((8, 16), np.int64)
        mlm = np.where(rng.rand(8, 16) < 0.15,
                       rng.randint(0, 128, (8, 16)), -100)
        nsp = rng.randint(0, 2, (8,))
        # average of the two half-batch losses == DP-averaged gradient
        total = None
        for sl in (slice(0, 4), slice(4, 8)):
            ml, nl = model(paddle.to_tensor(ids[sl]),
                           paddle.to_tensor(types[sl]),
                           paddle.to_tensor(mask[sl]))
            part = crit(ml, nl, paddle.to_tensor(mlm[sl]),
                        paddle.to_tensor(nsp[sl])) * 0.5
            total = part if total is None else total + part
        total.backward()
        opt.step()
        opt.clear_grad()

    np.save(os.environ["BERT_OUT"] + ".single.npy",
            np.asarray(model.bert.pooler_dense.weight._data))
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def test_bert_dp_two_proc_parity(tmp_path):
    """BASELINE configs[2]: BERT pretraining, data parallel, end-to-end
    — 2-proc DP trajectory matches the equivalent single-proc run."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_base = str(tmp_path / "w")
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "BERT_OUT": out_base,
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"rank {rank} failed:\n{err[-3000:]}"
        assert f"RANK{rank}_OK" in out

    single = tmp_path / "single.py"
    single.write_text(SINGLE)
    env = dict(os.environ)
    env.update({"BERT_OUT": out_base,
                "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", "")})
    r = subprocess.run([sys.executable, str(single)], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]

    w0 = np.load(out_base + ".0.npy")
    w1 = np.load(out_base + ".1.npy")
    ws = np.load(out_base + ".single.npy")
    np.testing.assert_allclose(w0, w1, rtol=0, atol=0)  # ranks agree
    np.testing.assert_allclose(w0, ws, rtol=1e-4, atol=1e-6)
