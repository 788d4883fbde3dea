"""Companion for the launcher's watcher mode: a training script that uses
whatever device jax gives it, and says which.

    python -m paddle_tpu.distributed.launch --log_dir <dir> \
        tests/companions/launch_device_probe.py [platform]

The launcher's parent has imported paddle_tpu; that import initializes no
backend, so this child — a separate process — is free to take the chip.
With a platform argument the probe fails unless it got that platform
(`tpu` on the chip machine, where a parent that held the chip would make
this child fail or hang)."""

import sys

import jax

import paddle_tpu as paddle
from paddle_tpu.utils import compile_cache

compile_cache.enable()
want = sys.argv[1] if len(sys.argv) > 1 else None
dev = jax.devices()[0]
if want is not None and dev.platform != want:
    sys.exit(f"LAUNCH_PROBE wanted {want!r}, got {dev.platform!r}")
paddle.seed(0)
x = paddle.rand([256, 256])
y = paddle.matmul(x, x)
print(f"LAUNCH_PROBE ok platform={dev.platform} kind={dev.device_kind} "
      f"sum={float(y.sum()):.3f}")
