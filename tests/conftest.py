"""Test harness: force an 8-device virtual CPU platform (SURVEY.md §4 —
single-process SPMD tests replace the reference's multi-GPU subprocess
pattern).

The tests run on the CPU and never on a chip: the host-device-count XLA
flag has to be in the environment before the CPU backend initializes, and
jax_platforms is pinned to cpu here so that a test process started without
JAX_PLATFORMS=cpu on a machine that has an accelerator still leaves it
alone.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy end-to-end parity tests excluded from the tier-1 "
        "run (-m 'not slow'); the dedicated CI serving jobs run them "
        "without the filter",
    )


@pytest.fixture(autouse=True)
def _fresh_seed():
    import paddle_tpu as paddle

    paddle.seed(2024)
    yield
