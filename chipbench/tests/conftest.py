"""CPU rehearsals of the benchmark: `python -m pytest chipbench/tests -q`.
Tiny sizes, Pallas never compiled; no time, rate or share read here is a
device number."""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

TINY_TRAIN = {"driver": "train_steps", "batch": 2, "seq": 64, "workers": 0,
              "checked_steps": 3, "steps_in_flight": 2}
TINY_CHAT = {"driver": "open_loop_http",
             "arrivals": {"rate_rps": 4.0, "burst_factor": 1.0},
             "prompts": {"median": 16, "sigma": 0.5, "min": 9, "max": 32},
             "outputs": {"kind": "uniform", "min": 4, "max": 8},
             "sampling": {"temperature": 0.7, "top_p": 0.95, "top_k": 0},
             "greedy_fraction": 0.3, "shape_seed": 1, "drain_s": 30.0,
             "check_requests": 3, "check_pad": 16}
TINY_BATCH = dict(TINY_CHAT, driver="closed_loop_http",
                  arrivals={"clients": 3}, greedy_fraction=1.0)


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout in miniature: the benchmark's data files copied, and a
    configuration, three traffic mixes, their limits and a per-layer metric
    ADDED as files only — nothing that is there is edited."""
    root = tmp_path / "checkout"
    here = root / "chipbench"
    src = os.path.join(ROOT, "chipbench")
    for d in ("configs", "traffic", "limits", "layer_metrics"):
        shutil.copytree(os.path.join(src, d), here / d)
    shutil.copy(os.path.join(src, "peaks.json"), here / "peaks.json")
    shutil.copy(os.path.join(DATA, "tiny-dense.json"),
                here / "configs" / "tiny-dense.json")
    for name, mix in (("tiny-train", TINY_TRAIN), ("tiny-chat", TINY_CHAT),
                      ("tiny-batch", TINY_BATCH)):
        (here / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    (here / "limits" / "tiny.tiny-train.json").write_text(json.dumps(
        {"grad_norm_gap": 0.01, "change_norm_gap": 0.01}))
    for cell in ("tiny.tiny-chat", "tiny.tiny-batch"):
        (here / "limits" / f"{cell}.json").write_text(
            json.dumps({"logit_gap": 0.01}))
    (here / "layer_metrics" / "tiny.input_wait_p50_ms.json").write_text(
        json.dumps({"reader": "mean_ms",
                    "args": {"field": "input_wait_s", "stat": "median"}}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "none",
                             "file": "chipbench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    for mix in ("tiny-train", "tiny-chat", "tiny-batch"):
        bench["workloads"].append({"name": f"tiny.{mix}", "config": "tiny",
                                   "traffic": mix, "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny.tiny-train")
        if m["name"] == "gen_tokens_per_s":
            m["workloads"] += ["tiny.tiny-chat", "tiny.tiny-batch"]
    bench["per_layer"].append({
        "name": "tiny.input_wait_p50_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "trainer",
        "moves": "train_tokens_per_s", "workloads": ["tiny.tiny-train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), bench


def run_tiny(tiny_root, cell_name, seed=2_200_000_123, seconds=2.0):
    """Everything of a run but the harness's look for a chip."""
    import importlib
    import time

    import jax

    from chipbench import harness

    root, bench = tiny_root
    cell = harness.Cell(root, bench, cell_name)
    ctx = harness.Context(cell, seed, seconds, False,
                          {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                          os.path.join(root, "chipbench", ".work"),
                          lambda msg: None)
    driver = importlib.import_module(
        "chipbench.drivers." + cell.traffic["driver"])
    result = harness.run_cell(ctx, driver, jax.devices()[:1],
                              time.perf_counter())
    return result, ctx
