"""The control and the planted faults, at a size a test run can hold.

The control is the plain reference put in the program's place and computed
in the nearest precision below the configuration's (fp8 for bfloat16): it
has to read at least three times what the program reads.  Each fault breaks
the timed path underneath a whole run and `correct` has to come out false.
"""

import json

import numpy as np
import pytest

from conftest import run_tiny


def _bf16_root(tiny_root):
    """The tiny configuration as the cells state theirs: bfloat16."""
    import os

    root, bench = tiny_root
    path = os.path.join(root, "chipbench", "configs", "tiny-dense.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["dtype"] = "bfloat16"
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root, bench


def test_training_control_reads_above_the_program(tiny_root):
    import importlib
    import os

    from chipbench import harness

    root, bench = _bf16_root(tiny_root)
    cell = harness.Cell(root, bench, "tiny.tiny-train")
    rows = []
    make = lambda seed: harness.Context(
        cell, seed, 0.0, False, {}, os.path.join(root, "w"), lambda m: None)
    drv = importlib.import_module("chipbench.drivers.train_steps")
    drv.calibrate(make, [2_200_000_001, 11, 12], 3, rows.append)
    by = lambda kind, key: [r[key] for r in rows if r["kind"] == kind]
    lower = max(by("program", "grad_norm_gap"))
    assert min(by("control_fp8", "grad_norm_gap")) >= 3 * lower
    assert min(by("fault_half_batch", "grad_norm_gap")) >= 10 * lower
    assert min(by("fault_half_batch", "change_norm_gap")) >= 10 * max(
        by("program", "change_norm_gap"))


def test_fault_step_returns_its_state_unchanged(tiny_root, monkeypatch):
    import paddle_tpu as paddle

    real = paddle.jit.TrainStep.__call__

    def frozen(self, *batch):
        import jax.numpy as jnp

        sd = self.model.state_dict()
        saved = {n: jnp.copy(t._data) for n, t in sd.items()}
        loss = real(self, *batch)
        for n, t in sd.items():
            t._data = saved[n]
        return loss

    monkeypatch.setattr(paddle.jit.TrainStep, "__call__", frozen)
    result, _ = run_tiny(tiny_root, "tiny.tiny-train")
    assert result["correct"] is False
    assert result["compared"]["change_norm_gap"]["value"] > 0.9


def test_fault_half_of_the_batch_left_out(tiny_root, monkeypatch):
    import paddle_tpu as paddle

    real = paddle.jit.TrainStep.__call__
    monkeypatch.setattr(
        paddle.jit.TrainStep, "__call__",
        lambda self, ids, labels: real(self, ids[:1], labels[:1]))
    result, _ = run_tiny(tiny_root, "tiny.tiny-train")
    assert result["correct"] is False
    assert result["compared"]["grad_norm_gap"]["value"] > 0.1


def test_fault_a_served_token_altered(tiny_root, monkeypatch):
    from paddle_tpu.serving.gateway import protocol

    real = protocol.Gateway._chunk

    def altered(self, cmpl_id, created, token_ids, reason=None):
        return real(self, cmpl_id, created,
                    [(t + 1) % 256 for t in token_ids], reason)

    monkeypatch.setattr(protocol.Gateway, "_chunk", altered)
    result, _ = run_tiny(tiny_root, "tiny.tiny-chat", seconds=3.0)
    assert result["correct"] is False
    assert result["compared"]["logit_gap"]["value"] > 0.01


def test_serving_control_token_gap():
    """widest_logit_gap: 0 for the reference's own first tokens, the gap
    for another's."""
    from chipbench import compare

    logits = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 2.5]], np.float32)
    assert compare.widest_logit_gap(logits, [1, 0]) == (0.0, 0)
    gap, where = compare.widest_logit_gap(logits, [2, 2])
    assert gap == pytest.approx(1.0) and where == 0
