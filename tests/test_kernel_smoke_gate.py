"""The kernel-smoke gate itself: every check passes in interpret mode,
and a seeded perturbation of ANY kernel's result trips the gate loudly
(VERDICT r2 item 3 — the gate must be proven able to fail)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import kernel_checks  # noqa: E402

_NAMES = []


def _names():
    # one full suite execution, shared by every parametrized case (each
    # yield of _kernel_checks computes real kernels — it is not free)
    if not _NAMES:
        _NAMES.extend(n for n, _, _ in kernel_checks._kernel_checks())
    return _NAMES


def test_all_checks_pass_clean():
    seen = []
    for name, err, tol in kernel_checks._kernel_checks():
        assert err < tol, f"{name}: {err} >= {tol}"
        seen.append(name)
    if not _NAMES:  # reuse this run for the parametrized cases below
        _NAMES.extend(seen)


@pytest.mark.parametrize("name", [
    "flash_fwd_causal1", "flash_bwd_dq_causal0", "flash_bwd_dkv_alias",
    "layer_norm", "rms_norm", "group_norm", "group_norm_bwd_dx",
    "ring_step_loss", "ring_bwd_dq", "fused_ce_loss", "fused_ce_dweight",
])
def test_gate_trips_on_perturbation(name):
    if name == "flash_bwd_dkv_alias":
        name = "flash_bwd_dk_causal1"
    names = _names()
    assert name in names, f"{name} not in gate: {names}"
    with pytest.raises(AssertionError, match=name):
        kernel_checks.kernel_smoke(perturb=name)


def test_gate_covers_backward_paths():
    names = _names()
    for required in ("flash_bwd_dq_causal0", "flash_bwd_dv_causal1",
                     "group_norm_bwd_dw", "ring_bwd_dk", "fused_ce_dhidden"):
        assert required in names
