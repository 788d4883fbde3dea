#!/usr/bin/env python
"""BERT b32xs128 shape-physics A/B (VERDICT r4 item 3): test the claimed
"small-GEMM shape physics at h=768" BEFORE believing it.

Variants (each in a fresh process so PADDLE_TPU_FUSE_QKV binds at model
build):
  base      — b32xs128, three separate [768,768] QKV GEMMs (family row)
  fuseqkv   — b32xs128, QKV as ONE [768,2304] GEMM (in-trace weight
              concat; checkpoint layout unchanged)
  pack      — b16xs256, same tokens/step as b32xs128 (the sequence-
              packing SHAPE experiment: GEMM M stays 4096, attention
              runs at s256 — measures geometry, not packing semantics)
  fuse+pack — both

All variants run scan8 (one dispatch per 8 steps, so host dispatch
latency stays out of the comparison) and the ABBA order decorrelates
slow drift. Prints one JSON line per run + a summary; writes
AB_BERT.json.  This parent imports nothing that touches jax: each child
needs the chip, and a chip belongs to one process at a time.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VARIANTS = {
    "base": ({}, dict(B=32, scan_k=8, S=128)),
    "fuseqkv": ({"PADDLE_TPU_FUSE_QKV": "1"}, dict(B=32, scan_k=8, S=128)),
    "pack": ({}, dict(B=16, scan_k=8, S=256)),
    "fuse+pack": ({"PADDLE_TPU_FUSE_QKV": "1"},
                  dict(B=16, scan_k=8, S=256)),
}

CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {repo!r} + "/benchmarks")
from bench_models import bench_bert
r = bench_bert(**{kwargs})
print("ABRESULT " + json.dumps(r))
"""


def run_one(name):
    env_extra, kwargs = VARIANTS[name]
    env = dict(os.environ, **env_extra)
    code = CHILD.format(repo=REPO, kwargs=repr(kwargs))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=1200, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"{name}: {r.stdout[-800:]} {r.stderr[-800:]}")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("ABRESULT ")][-1]
    res = json.loads(line[len("ABRESULT "):])
    toks = res["value"] * (256 if "pack" in name else 128)
    out = {"variant": name, "seqs_per_s": res["value"],
           "tokens_per_s": round(toks, 0),
           "metric": res["metric"],
           "device_pct_ceiling": res.get("pct_of_ceiling")}
    print(json.dumps(out), flush=True)
    return out


def main():
    order = ["base", "fuseqkv", "pack", "fuse+pack",
             "fuse+pack", "pack", "fuseqkv", "base"]   # ABBA-style
    runs = [run_one(n) for n in order]
    by = {}
    for r in runs:
        by.setdefault(r["variant"], []).append(r["tokens_per_s"])
    summary = {v: {"tokens_per_s_best": max(ts),
                   "tokens_per_s_all": ts} for v, ts in by.items()}
    base = summary["base"]["tokens_per_s_best"]
    for v, s in summary.items():
        s["vs_base"] = round(s["tokens_per_s_best"] / base, 4)
    print(json.dumps(summary, indent=1))
    with open(os.path.join(REPO, "AB_BERT.json"), "w") as f:
        json.dump({"runs": runs, "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main()
