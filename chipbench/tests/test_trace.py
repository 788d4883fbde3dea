"""trace.py: the reduction on a hand-made event list, and on the small
trace recorded on the chip that is kept beside this file
(data/train_1s.xplane.pb: the training cell, a one-second window, PR 23)."""

import os

import pytest

from chipbench import trace
from conftest import DATA

KERNEL = ('%jvp__.2 = (bf16[64,4096,128]{2,1,0:T(8,128)(2,1)}) custom-call('
          'bf16[64,4096,128]{2,1,0} %bitcast.391), '
          'custom_call_target="tpu_custom_call"')
FUSION = ('%fusion.12 = bf16[8192,4096]{1,0:T(8,128)(2,1)S(1)} fusion('
          'bf16[32768,4096]{1,0} %p.1), kind=kCustom, calls=%fc.1')


def hand_made():
    ops = [(FUSION, 1000.0, 400.0), (KERNEL, 1400.0, 300.0),
           (FUSION, 1600.0, 200.0),          # overlaps the kernel's tail
           (FUSION, 2500.0, 500.0),
           (KERNEL, 100.0, 50.0)]            # before the window: clipped out
    host = [("chipbench.window", 1000.0, 2500.0),
            ("chipbench.trainer.step", 1000.0, 900.0),
            ("chipbench.trainer.input", 1900.0, 500.0),
            ("other", 0.0, 9000.0)]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": [
        ("jit_step_fn(1)", 1000.0, 800.0)]}}, "host": host}


def test_reduce_hand_made():
    r = trace.reduce(hand_made())
    assert r["window_s"] == pytest.approx(2500e-9)
    # union: [1000, 1800) and [2500, 3000)
    assert r["busy_s"] == pytest.approx(1300e-9)
    assert trace.seconds_matching(r, "tpu_custom_call") == pytest.approx(
        300e-9)
    assert trace.seconds_matching(r, "no such op") is None
    top = dict(r["device_ops"])
    assert top["%fusion.12 fusion -> bf16[8192,4096]"] == pytest.approx(
        1100e-9)
    assert "%jvp__.2 custom-call:tpu_custom_call -> (bf16[64,4096,128])" in top
    gaps = dict(r["idle_gaps"])
    # [1800, 2500): its middle lies in trainer.input; [3000, 3500): no span
    assert gaps["chipbench.trainer.input"] == pytest.approx(700e-9)
    assert gaps["host: no span"] == pytest.approx(500e-9)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []})


def test_recorded_trace():
    path = os.path.join(DATA, "train_1s.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("the recorded trace is not there")
    r = trace.reduce(trace.load(path))
    assert 0.5 < r["window_s"] < 3.0
    assert 0 < r["busy_s"] <= r["window_s"]
    flash = trace.seconds_matching(r, 'custom_call_target="tpu_custom_call"')
    assert flash and flash < r["busy_s"]
    assert len(r["device_ops"]) == 10
    assert any(n.startswith("chipbench.") for n, _ in r["idle_gaps"])
