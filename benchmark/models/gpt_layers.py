"""The system under test for ``training.family: gpt_layers``: the repo's
``nn.Layer`` GPT (as ``bench.build_model`` writes it: learned positions,
untied head) under ``paddle.amp.decorate`` O2 and ``paddle.jit.TrainStep``
with the optimizer recipe the configuration file states, and the benchmark's
own seeded weights."""
from __future__ import annotations

import os

from benchmark.harness import load_module


def build_model(cfg, seq):
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F

    d, heads, vocab = cfg["d_model"], cfg["n_heads"], cfg["vocab_size"]
    dff = cfg["d_ff"]

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.ln1 = nn.LayerNorm(d)
            self.qkv = nn.Linear(d, 3 * d)
            self.proj = nn.Linear(d, d)
            self.ln2 = nn.LayerNorm(d)
            self.fc1 = nn.Linear(d, dff)
            self.fc2 = nn.Linear(dff, d)

        def forward(self, x):
            b, s, _ = x.shape
            qkv = self.qkv(self.ln1(x)).reshape([b, s, 3, heads, d // heads])
            att = F.scaled_dot_product_attention(
                qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], is_causal=True)
            x = x + self.proj(att.reshape([b, s, d]))
            return x + self.fc2(F.gelu(self.fc1(self.ln2(x))))

    class GPT(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(vocab, d)
            self.pos = nn.Embedding(seq, d)
            self.blocks = nn.LayerList([Block()
                                        for _ in range(cfg["n_layers"])])
            self.norm = nn.LayerNorm(d)
            self.head = nn.Linear(d, vocab, bias_attr=False)

        def forward(self, ids, pos_ids):
            h = self.embed(ids) + self.pos(pos_ids)
            for blk in self.blocks:
                h = blk(h)
            return self.head(self.norm(h))

    return GPT()


def build_train_step(cfg, tr, seed, bench_dir):
    """(model, step, optimizer): the recipe of ``tr`` (the configuration's
    ``training`` group) as a ready ``TrainStep``."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    ref = load_module(os.path.join(bench_dir, "reference",
                                   tr["family"] + ".py"),
                      "bench_reference_" + tr["family"])
    o = tr["optimizer"]
    vocab = cfg["vocab_size"]
    paddle.seed(seed & 0x7FFFFFFF)
    model = build_model(cfg, tr["seq"])
    opt = paddle.optimizer.AdamW(
        o["lr"], beta1=o["beta1"], beta2=o["beta2"], epsilon=o["epsilon"],
        parameters=model.parameters(), weight_decay=o["weight_decay"],
        moment_dtype=o["moment_dtype"],
        stochastic_rounding=o["stochastic_rounding"])
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype=tr["amp_dtype"],
                                     master_weight=o["master_weight"])
    w = ref.make_weights(seed, cfg, tr["seq"])
    for name, p in model.named_parameters():
        if p._data.dtype != w[name].dtype or p._data.shape != w[name].shape:
            raise RuntimeError(
                f"{name}: the program stores {p._data.dtype}{p._data.shape}, "
                f"the reference assumes {w[name].dtype}{w[name].shape}")
        p._rebind(w[name])
    del w

    def loss_fn(logits, labels):
        flat = logits.reshape([-1, vocab])
        if not tr["ce_bf16"]:
            flat = flat.astype("float32")
        return F.cross_entropy(flat, labels.reshape([-1]))

    return model, paddle.jit.TrainStep(model, loss_fn, opt), opt
