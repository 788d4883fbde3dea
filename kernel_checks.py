"""Numerics checks of every Pallas kernel path, forward and backward,
against its XLA twin, on the current backend.

``chip_smoke.py`` runs them on the chip (real Mosaic) before anything else
is trusted there; ``tests/test_kernel_smoke_gate.py`` runs them interpreted
on the CPU and proves that the gate can fail.  No timing is taken here.
"""


def _kernel_checks(perturb=None):
    """Yield (name, max_abs_err, tol) for every Pallas kernel path, fwd AND
    bwd, computed on the CURRENT backend (real Mosaic on TPU, interpret on
    CPU — the same code is exercised by tests/test_kernel_smoke_gate.py).
    `perturb=name` injects a seeded offset into that check's kernel result
    so the gate's ability to fail loudly is itself testable."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    # single source of truth for interpret-vs-Mosaic: the kernels' own
    # backend predicate (the gate must test the mode the models use)
    from paddle_tpu.ops.pallas.norms import _interpret_default
    interp = _interpret_default()

    def bump(name, arr):
        # perturbation emulates a silent kernel regression; multiplicative
        # + additive so it exceeds both absolute and relative tolerances
        return arr * 1.5 + 2.0 if perturb == name else arr

    rng = np.random.RandomState(0)
    b, s, h, kv, d = 1, 256, 4, 2, 128
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, kv, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, kv, d), jnp.bfloat16)

    from paddle_tpu.ops.pallas.flash import flash_attention as pallas_flash
    from paddle_tpu.ops.flash_attention import _xla_flash
    for causal in (False, True):
        nm = f"flash_fwd_causal{int(causal)}"
        out = np.asarray(bump(nm, pallas_flash(q, k, v, causal=causal,
                                               interpret=interp)), np.float32)
        ref = np.asarray(_xla_flash(q, k, v, causal, None), np.float32)
        yield nm, np.abs(out - ref).max(), 0.1

    # flash BACKWARD (dq/dk/dv, GQA): the bwd kernels only ran inside full
    # benches before — a Mosaic regression there showed up as a silently
    # wrong loss (VERDICT r2 item 3)
    for causal in (False, True):
        def loss_pl(q, k, v):
            o = pallas_flash(q, k, v, causal=causal, interpret=interp)
            return (o.astype(jnp.float32) ** 2).sum()

        def loss_ref(q, k, v):
            return (_xla_flash(q, k, v, causal, None)
                    .astype(jnp.float32) ** 2).sum()

        gp = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name_c, a, r in zip(("dq", "dk", "dv"), gp, gr):
            nm = f"flash_bwd_{name_c}_causal{int(causal)}"
            a = np.asarray(bump(nm, a.astype(jnp.float32)))
            r = np.asarray(r.astype(jnp.float32))
            scale = max(1.0, np.abs(r).max())
            yield nm, np.abs(a - r).max() / scale, 0.05

    from paddle_tpu.ops.pallas.norms import layer_norm, rms_norm
    x = jnp.asarray(rng.randn(8, 512), jnp.float32)
    w = jnp.asarray(rng.randn(512), jnp.float32)
    bias = jnp.asarray(rng.randn(512), jnp.float32)
    ln = np.asarray(bump("layer_norm", layer_norm(x, w, bias,
                                                  interpret=interp)))
    mu = np.asarray(x, np.float64).mean(-1, keepdims=True)
    var = np.asarray(x, np.float64).var(-1, keepdims=True)
    ln_ref = (np.asarray(x) - mu) / np.sqrt(var + 1e-5) * np.asarray(w) + np.asarray(bias)
    yield "layer_norm", np.abs(ln - ln_ref).max(), 1e-3
    rn = np.asarray(bump("rms_norm", rms_norm(x, w, interpret=interp)))
    rn_ref = np.asarray(x) / np.sqrt((np.asarray(x, np.float64) ** 2).mean(-1, keepdims=True) + 1e-6) * np.asarray(w)
    yield "rms_norm", np.abs(rn - rn_ref).max(), 1e-3

    from paddle_tpu.ops.pallas.norms import group_norm
    xg = jnp.asarray(rng.randn(2, 32, 16, 16), jnp.float32)
    wg = jnp.asarray(rng.randn(32), jnp.float32)
    bg = jnp.asarray(rng.randn(32), jnp.float32)

    def gn_ref_fn(xv, wv, bv):
        g4 = xv.reshape(2, 8, 4, 16, 16).astype(jnp.float32)
        mu = g4.mean(axis=(2, 3, 4), keepdims=True)
        var = ((g4 - mu) ** 2).mean(axis=(2, 3, 4), keepdims=True)
        out = ((g4 - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(xv.shape)
        return out * wv.reshape(1, 32, 1, 1) + bv.reshape(1, 32, 1, 1)

    gn = np.asarray(bump("group_norm", group_norm(xg, wg, bg, 8, 1e-5,
                                                  interpret=interp)))
    yield "group_norm", np.abs(gn - np.asarray(gn_ref_fn(xg, wg, bg))).max(), 1e-3

    gp = jax.grad(lambda *a: (group_norm(*a, 8, 1e-5, interp) ** 2).sum(),
                  argnums=(0, 1, 2))(xg, wg, bg)
    gr = jax.grad(lambda *a: (gn_ref_fn(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(xg, wg, bg)
    for name_c, a, r in zip(("dx", "dw", "db"), gp, gr):
        nm = f"group_norm_bwd_{name_c}"
        a = np.asarray(bump(nm, a))
        r = np.asarray(r)
        scale = max(1.0, np.abs(r).max())
        yield nm, np.abs(a - r).max() / scale, 1e-3

    # one ring-attention step (sep axis of 1 on this chip: the ring bwd
    # kernel path — global-lse flash bwd with rotating accumulators — runs
    # on real silicon; multi-device parity is covered on the CPU mesh)
    from jax.sharding import Mesh
    from paddle_tpu.distributed.shard_map_compat import (
        NO_CHECK as sm_kw, shard_map)
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.ring_attention import ring_flash_attention_arrays
    mesh = Mesh(np.array(jax.devices()[:1]), ("sep",))
    spec = P(None, "sep", None, None)

    def ring_loss(q, k, v):
        f = shard_map(
            lambda a, b, c: ring_flash_attention_arrays(
                a, b, c, causal=True, axis_name="sep", interpret=interp),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            **sm_kw)
        return (f(q, k, v).astype(jnp.float32) ** 2).sum()

    ring_val_and_grads = jax.value_and_grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    ref_val_and_grads = jax.value_and_grad(
        lambda a, b, c: (_xla_flash(a, b, c, True, None)
                         .astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    rv, rg = ring_val_and_grads
    fv, fg = ref_val_and_grads
    yield ("ring_step_loss",
           abs(float(bump("ring_step_loss", rv)) - float(fv)) / max(1.0, abs(float(fv))),
           0.02)
    for name_c, a, r in zip(("dq", "dk", "dv"), rg, fg):
        nm = f"ring_bwd_{name_c}"
        a = np.asarray(bump(nm, a.astype(jnp.float32)))
        r = np.asarray(r.astype(jnp.float32))
        scale = max(1.0, np.abs(r).max())
        yield nm, np.abs(a - r).max() / scale, 0.05

    # fused chunked LM-head CE, fwd + grads, vs the unfused XLA logits path
    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy
    nrow, hdim, vocab = 96, 64, 512
    hid = jnp.asarray(rng.randn(nrow, hdim) * 0.3, jnp.float32)
    wce = jnp.asarray(rng.randn(hdim, vocab) * 0.1, jnp.float32)
    lab = jnp.asarray(rng.randint(0, vocab, (nrow,)), jnp.int32)

    def ce_ref(hv, wv):
        logits = (hv @ wv).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lab[:, None], -1)[:, 0]
        return (lse - picked).mean()

    fv, fg = jax.value_and_grad(
        lambda hv, wv: fused_linear_cross_entropy(hv, wv, lab, chunk_rows=32),
        argnums=(0, 1))(hid, wce)
    rv, rg = jax.value_and_grad(ce_ref, argnums=(0, 1))(hid, wce)
    yield ("fused_ce_loss",
           abs(float(bump("fused_ce_loss", fv)) - float(rv)) / max(1.0, abs(float(rv))),
           1e-4)
    for name_c, a, r in zip(("dhidden", "dweight"), fg, rg):
        nm = f"fused_ce_{name_c}"
        a = np.asarray(bump(nm, a))
        r = np.asarray(r)
        scale = max(1e-3, np.abs(r).max())
        yield nm, np.abs(a - r).max() / scale, 1e-3


def kernel_smoke(perturb=None):
    """Numerics check of every Pallas kernel path — forward AND backward —
    ON THE REAL CHIP before any timing: a Mosaic-lowering regression must
    fail loudly here rather than silently corrupt the perf numbers
    (SURVEY.md §4 tolerance discipline; VERDICT r2 item 3)."""
    for name, err, tol in _kernel_checks(perturb):
        assert err < tol, f"{name} kernel mismatch: {err} >= {tol}"
