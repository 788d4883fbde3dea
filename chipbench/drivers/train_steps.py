"""Driver `train_steps`: `paddle.jit.TrainStep.__call__` on
`GPTForCausalLM(fused_lm_loss=True)` with `AdamW(multi_precision=True)`, fed
by a `DataLoader` with process workers.

Set-up builds ONE step object with its state, drives it through its first
`checked_steps` steps by the window's own call and feed (reading what the
check compares: each loss, the first gradient's norm a leaf from the
optimizer's first moment, the parameters' change from the master weights),
and hands the same object to the window.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np


class Rows:
    """Row i of the seeded token stream: (ids, next-token labels).  Plain
    numpy, so the DataLoader's spawned workers never touch jax."""

    def __init__(self, n, seq, vocab, seed):
        self.n, self.seq, self.vocab, self.seed = n, seq, vocab, seed

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.default_rng([int(self.seed), 7, int(i)])
        row = rng.integers(0, self.vocab, self.seq + 1, dtype=np.int32)
        return row[:-1], row[1:]


def batch_of(rows, batch, step):
    """What the loader yields as its `step`-th batch, for the reference."""
    pairs = [rows[step * batch + j] for j in range(batch)]
    return (np.stack([p[0] for p in pairs]), np.stack([p[1] for p in pairs]))


def _loss_fn(net, ids, labels):
    loss, _ = net(ids, labels=labels)
    return loss


def _norms(arrays):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])
    return [float(v) for v in fn(arrays)]


def _diff_norms(arrays, others):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda xs, ys: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(xs, ys)])
    return [float(v) for v in fn(arrays, others)]


class State:
    def __init__(self):
        self.model = self.opt = self.step = self.batches = None
        self.prog = None

    def one(self, ctx, waits=None):
        t = time.perf_counter()
        with ctx.span("trainer.input"):
            ids, labels = next(self.batches)
        if waits is not None:
            waits.append(time.perf_counter() - t)
        with ctx.span("trainer.step"):
            return self.step(ids, labels)

    def free(self):
        if self.batches is not None:
            self.batches.close()             # stops the workers
        self.model = self.opt = self.step = self.batches = None
        gc.collect()


def hyper_of(cfg):
    a = cfg["assumed"]
    return {k: a[k] for k in ("learning_rate", "beta1", "beta2", "epsilon",
                              "weight_decay")}


def build(ctx, rows_factory=Rows):
    """The step object with its state and its feed (no step taken)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader

    from chipbench import program, weights as W

    cfg, tr = ctx.cfg, ctx.traffic
    st = State()
    dtype = jnp.dtype(cfg["dtype"])
    w = W.make_all(cfg, ctx.seed, dtype)
    trainer = cfg.get("trainer", {})
    st.model = program.build_model(
        cfg, w, fused_lm_loss=trainer.get("fused_lm_loss", True),
        use_recompute=trainer.get("use_recompute", False))
    del w
    h = hyper_of(cfg)
    st.opt = paddle.optimizer.AdamW(
        learning_rate=h["learning_rate"], beta1=h["beta1"], beta2=h["beta2"],
        epsilon=h["epsilon"], weight_decay=h["weight_decay"],
        parameters=st.model.parameters(),
        multi_precision=trainer.get("multi_precision", True))
    st.step = paddle.jit.TrainStep(st.model, _loss_fn, st.opt)
    rows = rows_factory(1 << 24, tr["seq"], cfg["vocab_size"], ctx.seed)
    loader = DataLoader(rows, batch_size=tr["batch"],
                        num_workers=tr["workers"],
                        use_process_workers=tr["workers"] > 0)
    st.batches = iter(loader)
    return st


def first_steps(ctx, st):
    """The first `checked_steps` steps, with the readings of the check."""
    import jax.numpy as jnp

    from chipbench import program, weights as W

    cfg = ctx.cfg
    named = list(st.model.named_parameters())
    names = [n for n, _ in named]
    b1 = hyper_of(cfg)["beta1"]
    losses, grad_norms = [], None
    for k in range(ctx.traffic["checked_steps"]):
        losses.append(float(st.one(ctx)))
        if k == 0:
            moments = [st.opt._accumulators[id(p)]["moment1"]
                       for _, p in named]
            grad_norms = {n: v / (1.0 - b1)
                          for n, v in zip(names, _norms(moments))}
            del moments
    w0 = W.make_all(cfg, ctx.seed, jnp.dtype(cfg["dtype"]))
    start = []
    for n in names:
        g, leaf = program.leaf_of(n, cfg)
        start.append(w0[g][leaf])
    del w0
    now = [st.opt._accumulators[id(p)].get("master_weight", p._data)
           for _, p in named]
    change = dict(zip(names, _diff_norms(now, start)))
    del now, start
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def setup(ctx):
    st = build(ctx)
    t = time.perf_counter()
    st.prog = first_steps(ctx, st)
    ctx.log(f"first {len(st.prog['losses'])} steps (compile included) "
            f"{time.perf_counter() - t:.1f} s, losses {st.prog['losses']}")
    return st


def window(ctx, st, seconds):
    import jax

    in_flight = ctx.traffic["steps_in_flight"]
    losses, waits = [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        losses.append(st.one(ctx, waits))
        if len(losses) > in_flight:
            with ctx.span("trainer.wait_device"):
                jax.block_until_ready(losses[-1 - in_flight]._data)
    jax.block_until_ready(losses[-1]._data)
    elapsed = time.perf_counter() - t0
    return {"steps": len(losses), "seconds": elapsed,
            "losses": [float(x) for x in losses], "input_wait_s": waits,
            "tokens_per_step": ctx.traffic["batch"] * ctx.traffic["seq"]}


def end_to_end(ctx, st):
    r = ctx.records
    return {"train_tokens_per_s": {
        "value": r["steps"] * r["tokens_per_step"] / r["seconds"],
        "unit": "tokens/s"}}


def counts(ctx):
    r = ctx.records
    return r["steps"], sum(1 for x in r["losses"] if not math.isfinite(x))


def reference_readings(ctx, mode="f32", batch_rows=None):
    """The plain reference through the same first steps, from the seed."""
    import jax
    import jax.numpy as jnp

    from chipbench import program, weights as W
    from chipbench.reference import dense_gqa as ref

    cfg, tr = ctx.cfg, ctx.traffic
    dtype = jnp.dtype(cfg["dtype"])
    rows = Rows(1 << 24, tr["seq"], cfg["vocab_size"], ctx.seed)
    n_rows = batch_rows or tr["batch"]
    batches = []
    for k in range(tr["checked_steps"]):
        ids, labels = batch_of(rows, tr["batch"], k)
        batches.append((ids[:n_rows], labels[:n_rows]))
    up = jax.jit(lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t))
    params = up(W.make_all(cfg, ctx.seed, dtype))
    losses, first, params = ref.train_steps(cfg, hyper_of(cfg), params,
                                            batches, mode)
    w0 = W.make_all(cfg, ctx.seed, dtype)
    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y.astype(jnp.float32)))),
        a, b))(params, w0)
    del params, w0
    flat = lambda tree: {program.param_name(g, leaf): float(v)
                         for g, leaves in tree.items()
                         for leaf, v in leaves.items()}
    return {"losses": losses, "grad_norms": flat(first),
            "change_norms": flat(diff)}


def check(ctx, st):
    from chipbench import compare

    prog = st.prog
    st.free()
    t = time.perf_counter()
    ref = reference_readings(ctx)
    ctx.log(f"reference {time.perf_counter() - t:.1f} s, losses "
            f"{ref['losses']}")
    out = compare.training(prog, ref, ctx.limits)
    ctx.log("loss gap by step (read, not compared: limits file) "
            f"{[abs(a - b) for a, b in zip(prog['losses'], ref['losses'])]}")
    finite = all(math.isfinite(x) for x in ctx.records["losses"])
    out["window_losses_not_finite"] = {"value": 0.0 if finite else 1.0,
                                       "limit": 0.0}
    return out


def calibrate(make_ctx, seeds, control_seeds, emit):
    """The readings the limits are set from, in one process on the chip:
    the program against the reference on every seed of `seeds`; on the first
    `control_seeds` of them the control (the reference in fp8) and the
    planted fault (half of the batch left out, the mean over the rest)
    against the reference.  A step that returns its state unchanged reads 1
    in change_norm_gap by the measure itself and needs no run."""
    from chipbench import compare

    no_limits = {"loss_gap": None, "grad_norm_gap": None,
                 "change_norm_gap": None}

    def gaps(a, b):
        return {k: v["value"] for k, v in compare.training(
            a, b, no_limits).items()}

    for i, seed in enumerate(seeds):
        ctx = make_ctx(seed)
        st = build(ctx)
        prog = first_steps(ctx, st)
        st.free()
        ref = reference_readings(ctx)
        emit({"seed": seed, "kind": "program", **gaps(prog, ref),
              "losses": prog["losses"], "ref_losses": ref["losses"]})
        if i < control_seeds:
            emit({"seed": seed, "kind": "control_fp8",
                  **gaps(reference_readings(ctx, mode="fp8"), ref)})
            half = max(1, ctx.traffic["batch"] // 2)
            emit({"seed": seed, "kind": "fault_half_batch",
                  **gaps(reference_readings(ctx, batch_rows=half), ref)})
