#!/usr/bin/env python3
"""Compile a cell's largest programs for a DESCRIBED v5e (`v5e:2x2`, one
device) in the sandbox, and print `memory_analysis()`: a cell that does not
fit is found here and not on the chip.  Nothing runs; a compile that passes
is not a chip run (on-chip-measurement guide, section 2.3).

    JAX_PLATFORMS=cpu python3 chipbench/compile_check.py train ref-train

Only one process may hold the TPU compiler at a time: run nothing else that
loads libtpu beside it.
"""

import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _cfg(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(ROOT, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


@contextlib.contextmanager
def as_tpu():
    """Code that asks the backend still sees the CPU here; make the routers
    take their TPU branch while the program is traced."""
    import jax

    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = real


def report(name, compiled, t0):
    m = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)
    text = compiled.as_text()
    print(json.dumps({
        "program": name, "compile_s": round(time.perf_counter() - t0, 1),
        "argument_GB": gb(m.argument_size_in_bytes),
        "output_GB": gb(m.output_size_in_bytes),
        "alias_GB": gb(m.alias_size_in_bytes),
        "temp_GB": gb(m.temp_size_in_bytes),
        "peak_estimate_GB": gb(m.argument_size_in_bytes
                               + m.output_size_in_bytes
                               - m.alias_size_in_bytes
                               + m.temp_size_in_bytes),
        "mosaic_kernels": text.count("tpu_custom_call")}), flush=True)


def shapes_of(tree, sharding):
    import jax

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def check_ref_train(one_chip):
    """The plain reference's AdamW step at the training cell's size."""
    import jax
    import jax.numpy as jnp

    from chipbench import weights as W
    from chipbench.drivers.train_steps import hyper_of
    from chipbench.reference import dense_gqa as ref

    cfg, tr = _cfg("mistral-7b-v0.3-train"), _traffic("pretrain-4k")
    params = {g: {leaf: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
                  for leaf, (shape, _) in W.group_shapes(cfg, g).items()}
              for g in W.groups(cfg)}
    ids = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32,
                               sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    t0 = time.perf_counter()
    step = ref.make_train_step(cfg, hyper_of(cfg))
    report("reference AdamW step 2x4096 f32",
           step.lower(params, params, params, t, ids, ids).compile(), t0)


def check_train(one_chip):
    """`TrainStep`'s own step function at 2 x 4096, bf16 + AdamW master."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from chipbench import program, weights as W
    from chipbench.drivers import train_steps as drv

    cfg, tr = _cfg("mistral-7b-v0.3-train"), _traffic("pretrain-4k")
    w = W.make_all(cfg, 0, jnp.bfloat16)          # on the CPU: slow, once
    model = program.build_model(cfg, w, fused_lm_loss=True)
    h = drv.hyper_of(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=h["learning_rate"], beta1=h["beta1"], beta2=h["beta2"],
        epsilon=h["epsilon"], weight_decay=h["weight_decay"],
        parameters=model.parameters(), multi_precision=True)
    step = paddle.jit.TrainStep(model, drv._loss_fn, opt)
    ids = np.zeros((tr["batch"], tr["seq"]), np.int32)
    (_, pa, ba, os_, lr, key, ss, batch) = step._marshal(
        paddle.to_tensor(ids), paddle.to_tensor(ids))
    args = shapes_of((pa, ba, os_, lr, key, ss, *batch), one_chip)
    t0 = time.perf_counter()
    with as_tpu():
        compiled = jax.jit(step._make_step_fn(),
                           donate_argnums=(0, 2)).lower(*args).compile()
    report("TrainStep 2x4096 bf16 + AdamW(multi_precision)", compiled, t0)


CHECKS = {"ref-train": check_ref_train, "train": check_train}


def main(argv):
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in argv or sorted(CHECKS):
        CHECKS[name](one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
