"""The harness end to end at a tiny configuration that no cell names: the
three drivers, files found by name, the refusal to run off the chip."""

import json
import os
import subprocess
import sys
import types

import pytest

from conftest import ROOT, run_tiny


def test_refuses_to_run_off_the_chip():
    """No TPU here: the command says why, exits non-zero, prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "mistral7b-train.pretrain-4k", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_files_are_found_by_name(tiny_root):
    """A configuration, a traffic mix, limits and a per-layer metric that
    exist only as added files and added entries are found."""
    from chipbench import harness

    root, bench = tiny_root
    cell = harness.Cell(root, bench, "tiny.tiny-train")
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["seq"] == 64
    assert [m["name"] for m in cell.end_to_end()] == ["train_tokens_per_s",
                                                      "setup_s"]
    names = [m["name"] for m in cell.per_layer()]
    assert names == ["tiny.input_wait_p50_ms"]
    real = harness.Cell(root, bench, "mistral7b-train.pretrain-4k")
    assert "tiny.input_wait_p50_ms" not in [m["name"]
                                            for m in real.per_layer()]
    with pytest.raises(SystemExit):
        cell.peaks("TPU v9")


def test_every_metric_of_the_benchmark_has_its_files():
    from chipbench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    here = os.path.join(ROOT, "chipbench")
    for m in bench["per_layer"]:
        spec = harness.load_json(os.path.join(here, "layer_metrics",
                                              m["name"] + ".json"))
        assert os.path.exists(os.path.join(here, "readers",
                                           spec["reader"] + ".py"))
        assert m["moves"] in [e["name"] for e in bench["end_to_end"]]
    for w in bench["workloads"]:
        cell = harness.Cell(ROOT, bench, w["name"])
        assert os.path.exists(os.path.join(
            here, "drivers", cell.traffic["driver"] + ".py"))
        assert any(e["name"] == "setup_s" for e in cell.end_to_end())
        assert len(cell.end_to_end()) >= 2 and cell.per_layer()


def test_train_steps_driver(tiny_root):
    from chipbench import harness

    result, ctx = run_tiny(tiny_root, "tiny.tiny-train")
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 3 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(result)[-1] == "compared"
    # the metric that exists only as an added file reads the run's records
    assert harness.read_layer_metric(ctx, "tiny.input_wait_p50_ms") >= 0


def r_stats(ctx):
    return set(ctx.records["stats"])


@pytest.mark.parametrize("cell", ["tiny.tiny-chat", "tiny.tiny-batch"])
def test_http_drivers(tiny_root, cell):
    from chipbench import harness

    result, ctx = run_tiny(tiny_root, cell, seconds=3.0)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert set(result["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    assert r_stats(ctx) >= {"ttft_p95_ms", "ttft_p50_ms", "gap_mean_ms",
                            "gap_p99_ms", "completed_tokens_per_s"}
    assert result["compared"]["logit_gap"]["tokens"] > 0
    r = ctx.records
    assert r["programs_compiled_in_window"] == 0      # warm-up found all
    late = r["late_s"]
    assert len(late) == result["attempted"]
    # the queue and the live lanes at the window's two ends, as
    # `Engine.stats()` gave them; the metric files read the close's queue
    assert set(r["queue"]) == {"at_go", "at_close"}
    for end in r["queue"].values():
        assert set(end) == {"queue_depth", "active_slots"}
    assert r["queue"]["at_go"] == {"queue_depth": 0, "active_slots": 0}
    assert harness.read_layer_metric(ctx, "engine.queue_at_close.overload") \
        == r["queue"]["at_close"]["queue_depth"]
    # every serving driver keeps the whole span log of a 51 s window and
    # its answers for the span readers (the default ring holds 65,536)
    from paddle_tpu.observability import events

    assert events.default_log().capacity >= 1 << 20
    if cell == "tiny.tiny-chat":
        # an open loop sends exactly rate x seconds requests, all due inside
        assert result["attempted"] == 12
        assert all(0 <= q["due_s"] < 3.0 for q in r["requests"])


def _queue_ctx(records):
    return types.SimpleNamespace(records=records, cell=types.SimpleNamespace(
        root=ROOT))


@pytest.mark.parametrize("metric", ["engine.queue_at_close.overload",
                                    "engine.queue_at_close.dsv2"])
@pytest.mark.parametrize("records, reads", [
    # above the knee: the requests still waiting for a lane at the close
    ({"queue": {"at_go": {"queue_depth": 140, "active_slots": 128},
                "at_close": {"queue_depth": 352, "active_slots": 128}}}, 352),
    # under the knee: a queue of 0 is a reading, and the one that says so
    ({"queue": {"at_go": {"queue_depth": 0, "active_slots": 48},
                "at_close": {"queue_depth": 0, "active_slots": 54}}}, 0),
    # a parent whose driver kept no such field, or no records at all
    ({"engine": {"decode_steps": 10}, "requests": []}, None),
    ({"queue": {"at_go": {"queue_depth": 3}}}, None),
    (None, None),
])
def test_queue_at_close_on_made_up_records(metric, records, reads):
    from chipbench import harness

    got = harness.read_layer_metric(_queue_ctx(records), metric)
    assert got == reads
    assert type(got) is type(reads)            # 0 is 0, never None or False


def test_record_reader_returns_numbers_only():
    from chipbench.readers import record

    ctx = _queue_ctx({"a": {"b": 2.5, "flag": True, "rows": [1, 2]}, "n": 0})
    assert record.read(ctx, ["a", "b"]) == 2.5
    assert record.read(ctx, ["n"]) == 0
    for path in (["a"], ["a", "flag"], ["a", "rows"], ["a", "b", "c"],
                 ["missing"]):
        assert record.read(ctx, path) is None


def test_overload_cells_keep_to_the_rate_rule():
    """chipbench/README.md's rule on the committed files: an overload mix
    offers 1.3 to 1.5 times the requests/s its `rate_from` records as
    completed with the lanes full (tokens/s over the mean generated tokens a
    request of the mix's fixed trace), and the answers due after the close
    fit its `drain_s`."""
    from chipbench import harness, loadgen

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = bench["run_seconds"]
    for w in bench["workloads"]:
        cell = harness.Cell(ROOT, bench, w["name"])
        mix = cell.traffic
        rule = mix.get("rate_rule")
        if rule is None:
            continue
        assert w["why"].endswith("re-fix the rate")
        rate = mix["arrivals"]["rate_rps"]
        span = mix.get("lead_in_s", 0.0) + seconds
        rows, _, _ = loadgen._sizes(mix, int(round(rate * span)))
        mean = sum(r[1] for r in rows) / len(rows)
        assert mean == pytest.approx(rule["mean_generated_tokens"], rel=1e-3)
        completes = rule["capacity_tokens_per_s"] / mean
        assert 1.3 <= rate / completes <= 1.5, (w["name"], rate / completes)
        assert rule["factor"] == 1.4
        assert f"{rate:g}" in w["why"]
        lanes = cell.config["engine"]["num_slots"]
        due = ((rate - completes) * span + lanes) * mean \
            / rule["capacity_tokens_per_s"]
        assert due < mix["drain_s"]


def test_schedule_keeps_its_sizes_across_seeds():
    from chipbench import loadgen
    from conftest import TINY_CHAT

    a = loadgen.schedule(TINY_CHAT, 5, 10.0, 256)
    b = loadgen.schedule(TINY_CHAT, 2**31 + 9, 10.0, 256)
    again = loadgen.schedule(TINY_CHAT, 5, 10.0, 256)
    assert a == again
    size = lambda s: sorted((q["prompt_len"], q["max_tokens"], q["greedy"])
                            for q in s["requests"])
    assert size(a) == size(b) and a != b
    assert len(a["requests"]) == 40
    gaps = lambda s: sorted(round(y["due_s"] - x["due_s"], 9) for x, y in
                            zip(s["requests"], s["requests"][1:]))
    assert max(q["due_s"] for q in a["requests"]) < 10.0
    assert sum(q["greedy"] for q in a["requests"]) == 12


def test_order_block_gives_every_seed_the_same_prefix_of_work():
    """A mix with `order_block` rotates nothing: every seed sends at the
    trace's own times, and any block of consecutive arrivals holds the same
    sizes whatever the seed, in another order."""
    from chipbench import loadgen
    from conftest import TINY_CHAT

    mix = dict(TINY_CHAT, order_block=8)
    a = loadgen.schedule(mix, 5, 10.0, 256)
    b = loadgen.schedule(mix, 2**31 + 9, 10.0, 256)
    assert a == loadgen.schedule(mix, 5, 10.0, 256)
    assert [q["due_s"] for q in a["requests"]] == \
        [q["due_s"] for q in b["requests"]]
    size = lambda q: (q["prompt_len"], q["max_tokens"], q["greedy"])
    for lo in range(0, 40, 8):
        assert sorted(map(size, a["requests"][lo:lo + 8])) == \
            sorted(map(size, b["requests"][lo:lo + 8]))
    assert list(map(size, a["requests"])) != list(map(size, b["requests"]))
    assert [q["body"]["prompt"] for q in a["requests"]] != \
        [q["body"]["prompt"] for q in b["requests"]]
    assert sum(q["greedy"] for q in a["requests"]) == 12
    # a mix without the field keeps the rotation it had
    r = loadgen.schedule(TINY_CHAT, 5, 10.0, 256)
    assert [q["due_s"] for q in r["requests"]] != \
        [q["due_s"] for q in a["requests"]]


def test_benchmark_json_keeps_to_the_contract():
    """The limits that are refused before a single run."""
    import re

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    bench = json.loads(text)
    assert len(text) <= 64 * 1024
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["why"]) and line(c["source"])
        assert c["file"].startswith("chipbench/")
        assert all(name.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) or "size" in k
                       for k in c["reduced"])
    cells = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert line(w["why"]) and w["chips"] in (1, 4)
        cells.add(w["name"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # a metric's cells all report the end-to-end metric it moves
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get(
            "workloads", cells))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m.get("workloads", [])) <= cells
