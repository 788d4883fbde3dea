"""The readings a serving cell's limit is set from (chipbench/control.py):
on every seed a short window at the cell's own load, the program's widest
logit gap over the sample; on the first seeds the control at the same
prompts and served tokens: the reference in fp8, the gap of the token IT
puts first; and the planted fault: one served token altered.

Set-up is minutes, so one engine serves every seed: each seed's weights are
handed to it through `Engine._state_arrays`, the argument list its compiled
programs take (the benchmark's own runs never do this: there every run
builds its engine from its seed)."""

from __future__ import annotations

import gc

import numpy as np


def swap_weights(ctx, st):
    """This seed's weights into the model and the engine, the old ones
    freed first (two copies do not fit beside the pool)."""
    import jax.numpy as jnp

    from chipbench import program, weights as W

    cfg = ctx.cfg
    params = dict(st.model.named_parameters())
    names = list(st.engine._state_names)
    st.engine._state_arrays = None
    for p in params.values():
        p._data = jnp.zeros((), p._data.dtype)
    gc.collect()
    w = W.make_all(cfg, ctx.seed, jnp.dtype(cfg["dtype"]))
    for g, leaves in w.items():
        for leaf, a in leaves.items():
            params[program.param_name(g, leaf)]._data = a
    sd = st.model.state_dict()
    st.engine._state_arrays = [sd[n]._data for n in names]


def calibrate(make_ctx, seeds, control_seeds, emit):
    from chipbench import compare, serving

    st = None
    for i, seed in enumerate(seeds):
        ctx = make_ctx(seed)
        if st is None:
            st = serving.build_engine(ctx, warm=True)
        else:
            swap_weights(ctx, st)
        serving.start_client(ctx, st, ctx.traffic, ctx.seconds)
        ctx.records = serving.window(ctx, st, ctx.seconds)
        serving.drain(ctx, st)
        recs = ctx.records["requests"]
        by_index = {q["index"]: q["body"]["prompt"]
                    for q in st.sched["requests"]}
        sample = serving.check_sample(ctx, recs)
        prompts = [by_index[r["index"]] for r in sample]
        attempted, failed = serving.counts(ctx)
        logits, tokens, _ = serving.reference_gaps(ctx, sample, prompts)
        gap, where = compare.widest_logit_gap(logits, tokens)
        emit({"seed": seed, "kind": "program", "logit_gap": gap,
              "tokens": len(tokens), "requests": len(sample),
              "attempted": attempted, "failed": failed,
              "compiled_in_window":
                  ctx.records["programs_compiled_in_window"],
              "ref_best_abs_mean": float(np.abs(logits.max(-1)).mean())})
        if i < control_seeds:
            low, _, _ = serving.reference_gaps(ctx, sample, prompts, "fp8")
            first = low.argmax(-1)
            gap8, _ = compare.widest_logit_gap(logits, first)
            emit({"seed": seed, "kind": "control_fp8", "logit_gap": gap8,
                  "tokens": len(tokens),
                  "tokens_changed": int((first != logits.argmax(-1)).sum())})
            # fault: a served token altered where it is produced (the
            # next id), at the position the reference is surest of and at
            # the one it is least sure of
            margin = logits.max(-1) - np.partition(logits, -2, -1)[:, -2]
            for name, pos in (("surest", int(margin.argmax())),
                              ("least_sure", int(margin.argmin()))):
                altered = list(tokens)
                altered[pos] = (altered[pos] + 1) % logits.shape[-1]
                gapf, _ = compare.widest_logit_gap(logits, altered)
                emit({"seed": seed, "kind": f"fault_token_altered_{name}",
                      "logit_gap": gapf})
    if st is not None:
        st.free()
