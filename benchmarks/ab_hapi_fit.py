#!/usr/bin/env python
"""hapi Model.fit: eager-per-batch vs prepare(jit=True) (VERDICT r4
item 9) — measure the gap on one family so the default is a recorded
decision, not a guess. Runs BERT-base MLM-sized batches through
Model.train_batch both ways on the current backend."""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(jit):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.models import BertConfig, BertForMaskedLM

    paddle.seed(0)
    on_tpu = __import__("jax").default_backend() == "tpu"
    cfg = (BertConfig(vocab_size=30522, hidden_size=768,
                      num_hidden_layers=12, num_attention_heads=12,
                      intermediate_size=3072,
                      max_position_embeddings=512) if on_tpu else
           BertConfig(vocab_size=1024, hidden_size=128,
                      num_hidden_layers=2, num_attention_heads=2,
                      intermediate_size=256, max_position_embeddings=128))
    B, S, steps, windows = (32, 128, 8, 3) if on_tpu else (4, 32, 3, 1)

    class MLMNet(nn.Layer):
        def __init__(self):
            super().__init__()
            self.bert = BertForMaskedLM(cfg)

        def forward(self, ids):
            out = self.bert(ids)
            return out[0] if isinstance(out, tuple) else out

    class MLMLoss(nn.Layer):
        def forward(self, logits, labels):
            return nn.functional.cross_entropy(
                logits.reshape([-1, cfg.vocab_size]),
                labels.reshape([-1]))

    net = MLMNet()
    if on_tpu:
        net.to(dtype="bfloat16")
    model = Model(net)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=net.parameters(),
                                 multi_precision=True)
    model.prepare(optimizer=opt, loss=MLMLoss(), jit=jit)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))

    model.train_batch([ids], [ids])      # compile/warm
    model.train_batch([ids], [ids])
    best = None
    for _ in range(windows):
        t0 = time.time()
        for _ in range(steps):
            (lv,) = model.train_batch([ids], [ids])
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    return {"jit": jit, "seqs_per_s": round(B * steps / best, 1),
            "last_loss": round(lv, 4)}


def main():
    a = run(False)
    b = run(True)
    out = {"eager": a, "jit": b,
           "speedup": round(b["seqs_per_s"] / a["seqs_per_s"], 2)}
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "AB_HAPI_FIT.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
