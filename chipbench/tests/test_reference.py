"""The plain reference against the program's `GPTForCausalLM` at a tiny
size on the CPU: logits, loss, gradients; and the lower-precision modes
really are lower."""

import json
import os

import numpy as np
import pytest

from conftest import DATA


@pytest.fixture(scope="module")
def tiny():
    import jax.numpy as jnp

    from chipbench import program, weights as W

    with open(os.path.join(DATA, "tiny-dense.json")) as f:
        cfg = json.load(f)
    w = W.make_all(cfg, 1234, jnp.float32)
    model = program.build_model(cfg, w)
    model.eval()
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 40),
                                            dtype=np.int32)
    return cfg, w, model, ids


def test_weights_are_seeded_and_group_by_group(tiny):
    import jax.numpy as jnp

    from chipbench import weights as W

    cfg, w, _, _ = tiny
    again = W.make_all(cfg, 1234, jnp.float32)
    other = W.make_all(cfg, 2**31 + 1234, jnp.float32)
    one = W.make_group(cfg, 1234, "layer.1", jnp.float32)
    for leaf, a in w["layer.1"].items():
        assert np.array_equal(a, again["layer.1"][leaf])
        assert np.array_equal(a, one[leaf])
        assert not np.array_equal(a, other["layer.1"][leaf])
    assert W.n_params(cfg) == sum(p.size for p in tiny[2].parameters())


def test_logits_match_the_program(tiny):
    import paddle_tpu as paddle
    from chipbench.reference import dense_gqa as ref

    cfg, w, model, ids = tiny
    with paddle.no_grad():
        want = np.asarray(model(paddle.to_tensor(ids))._data)
    rows = np.repeat(np.arange(2), 40)
    cols = np.tile(np.arange(40), 2)
    got = np.asarray(ref.logits_at(cfg, lambda g: w[g], ids, rows, cols))
    assert np.abs(got - want.reshape(-1, want.shape[-1])).max() < 2e-5


def test_loss_and_gradients_match_the_program(tiny):
    import jax

    import paddle_tpu as paddle
    from chipbench import program
    from chipbench.reference import dense_gqa as ref

    cfg, w, model, ids = tiny
    labels = np.roll(ids, -1, 1)
    loss, grads = jax.value_and_grad(ref.loss_fn)(
        jax.tree.map(lambda a: a, w), ids, labels, cfg, "f32", 40)
    model.train()
    out, _ = model(paddle.to_tensor(ids), labels=paddle.to_tensor(labels))
    out.backward()
    assert abs(float(out) - float(loss)) < 1e-5
    params = dict(model.named_parameters())
    for g, leaves in grads.items():
        for leaf, want in leaves.items():
            got = np.asarray(params[program.param_name(g, leaf)].grad._data)
            assert np.abs(got - np.asarray(want)).max() < 1e-5, (g, leaf)
    model.eval()


def test_lower_precision_modes_are_lower(tiny):
    from chipbench.reference import dense_gqa as ref

    cfg, w, _, ids = tiny
    rows, cols = np.zeros(40, int), np.arange(40)
    exact = np.asarray(ref.logits_at(cfg, lambda g: w[g], ids, rows, cols))
    err = {m: np.abs(np.asarray(ref.logits_at(
        cfg, lambda g: w[g], ids, rows, cols, m)) - exact).max()
        for m in ("bf16", "fp8")}
    assert 0 < err["bf16"] < err["fp8"]
