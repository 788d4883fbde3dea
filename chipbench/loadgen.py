"""The one general traffic generator: a mix is a data file of parameters
(chipbench/traffic/<mix>.json) and this module expands it into a schedule.

Copied from `paddle_tpu/observability/loadgen.py::generate` (lognormal prompt
lengths, Pareto output budgets, 2-state Markov-modulated Poisson arrivals,
Zipf-chosen shared prefixes, every variate from PCG64 generators, virtual
time only) with these changes:

* the SIZES (prompt lengths, output budgets, inter-arrival gaps, prefix
  choices, which requests are greedy) are drawn from the mix's own
  `shape_seed`, so every `--seed` offers the same set of sizes and gaps;
  together they are one fixed trace.  `--seed` decides where in that trace
  the window STARTS (a rotation) and the token ids.  Which long prompt
  meets which short gap then does not depend on the seed; a tail read on
  two seeds differs by timing, not by luck of the draw.  (Tried first,
  PR 23: a fresh permutation a seed spread ttft_p95_ms by about 7 % over
  three seeds.)  An open loop sends exactly round(rate x seconds)
  requests, the gaps scaled so that all are due inside the window;
* a rotation still changes the WORK of a window that closes on a queue (an
  overload cell finishes only the first three quarters of what it is sent,
  and where the trace starts decides which three quarters: their prompts
  are prefill time, their budgets how often a lane turns over).  A mix
  that states `order_block` gets no rotation: every seed sends the trace's
  arrivals at the trace's own times, and the seed only shuffles the sizes
  among each `order_block` consecutive arrivals (and draws the token ids).
  Whatever prefix of the trace a window gets through is then the same set
  of requests for every seed, to within one block;
* lengths are clipped to the mix's closed range, so the set of compiled
  programs is bounded;
* sampling parameters are part of the request (the original's replay always
  sent temperature 0);
* a closed loop (`clients` > 0) gives each client its own request sequence.

Mix parameters (all under the traffic file's top level):
  arrivals  {"rate_rps", "burst_factor", "calm_dwell_s", "burst_dwell_s"}
            for an open loop, or {"clients": n} for a closed loop
  prompts   {"median", "sigma", "min", "max"}             lognormal, clipped
  outputs   {"kind": "pareto", "xm", "alpha", "cap"} or
            {"kind": "uniform", "min", "max"}
  prefixes  {"populations", "len", "zipf_a"}     shared system prompts (opt.)
  sampling  {"temperature", "top_p", "top_k"}    of the sampled requests
  greedy_fraction   share of requests sent at temperature 0
  shape_seed        seed of the sizes
  order_block       the seed shuffles sizes inside blocks of this many
                    consecutive arrivals and rotates nothing (optional)
"""

from __future__ import annotations

import numpy as np


def _zipf_weights(n, a):
    w = 1.0 / np.arange(1, n + 1, dtype=float) ** float(a)
    return w / w.sum()


def _arrival_gaps(rng, arr, n):
    """n inter-arrival gaps of the MMPP (burst_factor 1 is plain Poisson)."""
    rate, factor = float(arr["rate_rps"]), float(arr.get("burst_factor", 1.0))
    calm = float(arr.get("calm_dwell_s", 10.0))
    burst = float(arr.get("burst_dwell_s", 2.0))
    gaps, in_burst = [], False
    left = float(rng.exponential(calm))
    for _ in range(n):
        t = 0.0
        while True:
            gap = float(rng.exponential(
                1.0 / (rate * (factor if in_burst else 1.0))))
            if gap <= left:
                left -= gap
                t += gap
                break
            t += left
            in_burst = not in_burst
            left = float(rng.exponential(burst if in_burst else calm))
        gaps.append(t)
    return gaps


def _sizes(mix, n):
    """n (prompt_len, max_tokens, prefix population or -1, greedy) tuples
    and n gaps, from the mix's shape seed alone."""
    rng = np.random.default_rng(int(mix.get("shape_seed", 0)))
    p, o = mix["prompts"], mix["outputs"]
    pre = mix.get("prefixes") or {}
    plen = pre.get("len", 0)
    pops = pre.get("populations", 0)
    pop_p = _zipf_weights(pops, pre.get("zipf_a", 1.0)) if pops else None
    rows = []
    for _ in range(n):
        length = int(np.clip(round(p["median"] * float(np.exp(
            rng.normal(0.0, p["sigma"])))), max(p["min"], plen + 1),
            p["max"]))
        if o["kind"] == "pareto":
            budget = int(np.clip(round(o["xm"] * (1.0 + float(
                rng.pareto(o["alpha"])))), 1, o["cap"]))
        elif o["kind"] == "uniform":
            budget = int(rng.integers(o["min"], o["max"] + 1))
        else:
            raise ValueError(f"unknown outputs kind {o['kind']!r}")
        pop = int(rng.choice(pops, p=pop_p)) if pops else -1
        rows.append((length, budget, pop))
    # exactly the stated share is greedy, spread evenly
    n_greedy = int(round(float(mix.get("greedy_fraction", 0.0)) * n))
    greedy = np.zeros(n, bool)
    if n_greedy:
        greedy[np.linspace(0, n - 1, n_greedy).round().astype(int)] = True
    arr = mix["arrivals"]
    gaps = _arrival_gaps(rng, arr, n) if "rate_rps" in arr else [0.0] * n
    return rows, greedy, gaps


def schedule(mix, seed, seconds, vocab, model_id="paddle-tpu"):
    """The requests of one window: {"mode", "seconds", "requests": [...]}.
    Open loop: every request with its due time, those due inside the window.
    Closed loop: `clients` sequences, long enough to outlast the window."""
    arr = mix["arrivals"]
    closed = "clients" in arr
    if closed:
        per_client = int(mix.get("requests_per_client",
                                 max(4, int(seconds * 2))))
        n = int(arr["clients"]) * per_client
    else:
        # exactly rate x seconds requests, every one of them due inside
        # the window: the gaps keep their (seed-independent) shape and
        # are scaled so that the last arrival falls before the close
        n = max(1, int(round(float(arr["rate_rps"]) * seconds)))
    rows, greedy, gaps = _sizes(mix, n)
    if not closed:
        scale = seconds / (sum(gaps) + sum(gaps) / n)
        gaps = [g * scale for g in gaps]
    rng = np.random.default_rng([int(seed), 11])
    # the mix's sequence of (size, gap) is one fixed trace; the seed picks
    # where in it the window starts (and the token ids), so every seed
    # meets the same coincidences of long prompts and short gaps
    order = (np.arange(n) + int(rng.integers(0, n))) % n
    when = order
    block = int(mix.get("order_block", 0))
    if block:
        # no rotation: the trace's arrivals at their own times, the sizes
        # shuffled inside each block of consecutive arrivals, so that any
        # prefix of the trace is the same work whatever the seed
        when = np.arange(n)
        block_rng = np.random.default_rng([int(seed), 23])
        order = np.concatenate([lo + block_rng.permutation(min(block, n - lo))
                                for lo in range(0, n, block)])
    pre = mix.get("prefixes") or {}
    prefix_rng = np.random.default_rng([int(seed), 13])
    prefixes = [[int(t) for t in prefix_rng.integers(0, vocab, pre["len"])]
                for _ in range(pre.get("populations", 0))]
    samp = mix.get("sampling", {})
    requests, t = [], 0.0
    for i in range(n):
        length, budget, pop = rows[order[i]]
        t += gaps[when[i]]
        head = prefixes[pop] if pop >= 0 else []
        ids = head + [int(x) for x in rng.integers(
            0, vocab, length - len(head))]
        body = {"model": model_id, "prompt": ids, "stream": True,
                "max_tokens": budget}
        is_greedy = bool(greedy[order[i]]) or samp.get("temperature", 0) <= 0
        if not is_greedy:
            body.update(temperature=samp["temperature"],
                        top_p=samp.get("top_p", 1.0),
                        top_k=samp.get("top_k", 0),
                        seed=int(rng.integers(0, 2**31 - 1)))
        else:
            body.update(temperature=0.0)
        requests.append({"index": i, "due_s": t if not closed else None,
                         "client": (i % int(arr["clients"])) if closed
                         else None,
                         "greedy": is_greedy, "prompt_len": len(ids),
                         "max_tokens": budget, "body": body})
    return {"mode": "closed" if closed else "open", "seconds": seconds,
            "drain_s": float(mix.get("drain_s", 60.0)),
            "requests": requests}
