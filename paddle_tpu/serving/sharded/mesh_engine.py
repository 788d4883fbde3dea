"""MeshEngine: the tensor-parallel serving engine — one engine per mesh.

The entire single-chip ``Engine`` rides along unmodified: scheduler,
prefix radix store, preemption, horizon scan, speculative decoding,
sampling, host-authoritative mirrors, observability.  The ONLY override
is ``_run_model`` — the functionalized forward every compiled program
(prefill, horizon-scan body, verify window) calls — which here runs a
``shard_map`` over a ``("dp","tp")`` mesh with the
:class:`~.layout.ServingSpecLayout` placements.

Bitwise-parity doctrine (validated against the single-chip jitted
forward for MHA and GQA, prefill and decode shapes):

* every Linear is **column-parallel** (output dimension sharded over
  tp) — each output element is a full-length contraction identical to
  the single-chip one.  Row-parallel partial-sum matmuls are banned:
  psum over partial products re-associates float adds and parity dies;
* each shard runs rope + ``paged_write`` + the ragged paged-attention
  XLA fallback on its LOCAL head slice (all three are per-head/per-
  element exact, so a head slice computes bitwise what the full-head
  program computes for those heads);
* head outputs combine through **ONE psum per layer** over zero-padded
  disjoint supports: each shard ``dynamic_update_slice``s its local
  heads into zeros[b,s,heads,head_dim] at its head offset; psum of
  disjoint supports is exact because ``x + 0.0 == x`` bitwise;
* every other combine is ``lax.all_gather(tiled=True)`` — a pure
  concatenation in shard order, which moves bytes, never re-rounds.

Decode-program collective census (hand-derived, held EXACT by
tests/test_comms_observability.py): per layer per scanned step,
1 psum (head combine) + 3 all_gathers (o_proj out, SwiGLU intermediate,
down_proj out), plus 1 all_gather per step for the lm_head logits — so
a horizon-``h`` dispatch over ``L`` layers counts ``psum@tp = L*h`` and
``all_gather@tp = (3L+1)*h`` (int8 KV adds ``pmax@tp = 2L*h`` for the
cross-shard absmax in ``paged_write_quant``).

Parity must be compared jit-vs-jit: eager and jitted XLA execution
round differently (fusion), and the engine's CompiledFn jits every
program — which is the production path.

Deliberately NOT built here (see ROADMAP): dp > 1 (reserved for
disaggregated prefill/decode), multi-host meshes, and the Pallas decode
kernel under shard_map (the per-shard path uses the XLA fallback; on
TPU the kernel would slot in per-shard the same way).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ...core import tape as _tape
from ...core.tensor import Tensor
from ...distributed.shard_map_compat import NO_CHECK, shard_map
from ...nn import functional as F
from ...ops.rope import apply_rotary_emb
from ...tensor import manipulation as M
from ..engine import Engine, _lane_rows
from ..kv_cache import (PagedKV, model_cache_layout, paged_write,
                        paged_write_quant)
from ..paged_attention import paged_attention
from .layout import ServingSpecLayout


class MeshEngine(Engine):
    """Tensor-parallel :class:`~..engine.Engine` over a ``(dp, tp)``
    device mesh.  Construct with ``tp=N`` (or ``mesh_shape=(1, N)``);
    tp must divide the model's kv_heads/heads/hidden/intermediate/vocab
    (validated eagerly by :class:`ServingSpecLayout`).  ``tp=1`` is the
    degenerate single-shard mesh — useful as the parity control.

    Give each CONCURRENTLY-driven engine its own model instance: every
    engine traces through ``model.use_state()``, and a mesh engine
    swaps in locally-SLICED weights — sharing one module object with
    another engine stepping on a different thread (e.g. gateway
    replicas) races the swap.  Between same-shape single-chip engines
    the race is value-benign; against a mesh engine it is a shape
    error mid-trace."""

    def __init__(self, model, config=None, mesh_shape=None, tp=None,
                 register_profiler=True, layout=None):
        self.mesh_shape = self._norm_mesh_knob(mesh_shape, tp)
        if model_cache_layout(model).buffers != 2:
            raise ValueError(
                f"MeshEngine cannot serve {type(model).__name__}: it splits "
                "the cache's k/v pair by kv head, and this model states one "
                "buffer a layer (a latent, MLA, cache: one head that every "
                "query head reads); its shard forward is a hand copy of the "
                "dense GQA layer and knows no expert layers either. Serve "
                "this family on one chip through Engine (create_llm_engine "
                "without tp/mesh_shape)")
        dp, tp_size = self.mesh_shape
        self.tp = tp_size
        self.layout = layout or ServingSpecLayout()
        self.layout.validate(model.config, tp_size)
        devices = jax.devices()
        need = dp * tp_size
        if need > len(devices):
            raise ValueError(
                f"mesh_shape {self.mesh_shape} needs {need} devices, "
                f"only {len(devices)} visible (CPU runs need "
                f"--xla_force_host_platform_device_count)")
        self.mesh = Mesh(np.array(devices[:need]).reshape(dp, tp_size),
                         self.layout.mesh_axes)
        super().__init__(model, config, register_profiler=register_profiler)
        self._shard_placement()
        self._build_forward()

    # ------------------------------------------------------------- knobs
    @staticmethod
    def _norm_mesh_knob(mesh_shape, tp):
        """Normalize the (mesh_shape, tp) knob pair to a ``(dp, tp)``
        tuple, mirroring ``Engine._norm_quant_knob``'s loud-on-nonsense
        discipline."""
        if mesh_shape is None and tp is None:
            raise ValueError(
                "MeshEngine needs mesh_shape=(dp, tp) or tp=<int>")
        if mesh_shape is None:
            mesh_shape = (1, tp)
        try:
            shape = tuple(int(v) for v in mesh_shape)
        except (TypeError, ValueError):
            raise ValueError(
                f"unsupported mesh_shape {mesh_shape!r} "
                "(expected a (dp, tp) pair of ints)")
        if len(shape) != 2:
            raise ValueError(
                f"unsupported mesh_shape {mesh_shape!r} "
                "(expected exactly (dp, tp))")
        dp, tp_size = shape
        if tp is not None and int(tp) != tp_size:
            raise ValueError(
                f"tp={tp} contradicts mesh_shape {mesh_shape!r}")
        if tp_size < 1:
            raise ValueError(f"tp must be >= 1, got {tp_size}")
        if dp != 1:
            raise ValueError(
                f"dp={dp} is not supported yet: the dp axis is reserved "
                "for disaggregated prefill/decode (ROADMAP); use "
                "mesh_shape=(1, tp)")
        return shape

    # --------------------------------------------------------- placement
    def _put(self, arr, spec):
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _shard_placement(self):
        """Device_put weights and the paged pool under the layout's
        NamedShardings.  Weight-quant pairs shard BOTH leaves along the
        output axis — ``channelwise_scales`` are per OUTPUT channel
        ([1, out]), so slicing q and scale together commutes bitwise
        with dequantization.  Replicated inputs (ids/tables/scan state)
        need no placement: uncommitted host uploads replicate onto the
        mesh under jit."""
        specs = self.layout.state_specs(self._state_names)
        arrays = []
        for a, sp in zip(self._state_arrays, specs):
            if type(a) is tuple:
                arrays.append(tuple(self._put(x, sp) for x in a))
            else:
                arrays.append(self._put(a, sp))
        self._state_arrays = arrays
        self._place_pool()

    def _place_pool(self):
        """(Re-)place the paged pool arrays under the layout's
        shardings.  Beyond construction this is the tiered-KV swap-in
        hook: a host-arena upload rebinds pool buffers whose sharding
        XLA inferred, and re-putting them restores the head-sharded
        placement before the next dispatch.  The swap itself is pure
        byte movement — the host arena holds GATHERED full blocks
        (device_get assembles shards on the way out), so placement is
        the only sharded-serving concern; per-shard local-slice arenas
        are deliberately NOT built (see ARCHITECTURE \"Tiered KV\")."""
        pool_spec = self.layout.kv_pool()
        self.pool.k = [self._put(a, pool_spec) for a in self.pool.k]
        self.pool.v = [self._put(a, pool_spec) for a in self.pool.v]
        if self._kv_quant:
            sc = self.layout.kv_scales()
            self.pool.k_scale = [self._put(a, sc)
                                 for a in self.pool.k_scale]
            self.pool.v_scale = [self._put(a, sc)
                                 for a in self.pool.v_scale]

    # ----------------------------------------------------- mesh forward
    def _build_forward(self):
        """Build the shard_map-wrapped per-shard forward once — it is
        shape-polymorphic (prefill buckets, decode windows, and nb
        re-buckets all trace through the same callable; jit caching
        stays at the CompiledFn layer)."""
        num_layers = len(self.model.model.layers)
        pool_spec = self.layout.kv_pool()
        state_specs = tuple(
            (sp, sp) if type(a) is tuple else sp
            for a, sp in zip(self._state_arrays,
                             self.layout.state_specs(self._state_names)))
        in_specs = [state_specs, P(), P(), P(),
                    (pool_spec,) * num_layers, (pool_spec,) * num_layers]
        out_specs = [P(), (pool_spec,) * num_layers,
                     (pool_spec,) * num_layers]
        if self._kv_quant:
            sc = self.layout.kv_scales()
            in_specs += [(sc,) * num_layers, (sc,) * num_layers]
            out_specs += [(sc,) * num_layers, (sc,) * num_layers]
        self._mesh_fwd = shard_map(
            self._shard_forward, mesh=self.mesh,
            in_specs=tuple(in_specs), out_specs=tuple(out_specs),
            **NO_CHECK)

    def _shard_forward(self, state, ids, tables, pos, pool_k, pool_v,
                       pool_ks=None, pool_vs=None):
        """The per-shard decode-model forward (runs inside shard_map,
        once per tp rank).  Mirrors ``GPTModel`` + ``_forward_paged``
        with the layout's tp combines spliced in; sublayers are bound to
        their LOCAL weight slices through ``use_state`` (which swaps raw
        arrays without shape checks)."""
        axis = self.layout.tp_axis
        ti = lax.axis_index(axis)
        arrays = {}
        for name, a in zip(self._state_names, state):
            if type(a) is tuple:
                q, scale = a
                a = (q.astype(jnp.float32)
                     * scale).astype(self._wq_dtypes[name])
            arrays[name] = a
        mdl = self.model.model
        cfg = self.model.config
        heads, kvh, hd = (cfg.num_attention_heads, cfg.kv_heads,
                          cfg.head_dim)
        heads_l, kvh_l = heads // self.tp, kvh // self.tp
        b, s = ids.shape
        quant = pool_ks is not None

        def gather(t):
            # tiled all_gather on the last axis: exact concatenation in
            # shard order — the column-parallel combine
            return Tensor(lax.all_gather(t._data, axis,
                                         axis=t._data.ndim - 1,
                                         tiled=True))

        new_k, new_v, new_ks, new_vs = [], [], [], []
        with _tape.no_grad(), self.model.use_state(arrays):
            x = mdl.embed_tokens(Tensor(ids))
            pos_ids = Tensor(pos[:, None]
                             + jnp.arange(s, dtype=pos.dtype)[None, :])
            for i, layer in enumerate(mdl.layers):
                attn = layer.self_attn
                residual = x
                h = layer.input_layernorm(x)
                q = M.reshape(attn.q_proj(h), [b, s, heads_l, hd])
                k = M.reshape(attn.k_proj(h), [b, s, kvh_l, hd])
                v = M.reshape(attn.v_proj(h), [b, s, kvh_l, hd])
                q = apply_rotary_emb(q, position_ids=pos_ids,
                                     base=attn.rope_theta)
                k = apply_rotary_emb(k, position_ids=pos_ids,
                                     base=attn.rope_theta)
                if quant:
                    kp, ks = paged_write_quant(pool_k[i], pool_ks[i],
                                               k._data, tables, pos,
                                               axis_name=axis)
                    vp, vs = paged_write_quant(pool_v[i], pool_vs[i],
                                               v._data, tables, pos,
                                               axis_name=axis)
                    new_ks.append(ks)
                    new_vs.append(vs)
                else:
                    kp = paged_write(pool_k[i], k._data, tables, pos)
                    vp = paged_write(pool_v[i], v._data, tables, pos)
                    ks = vs = None
                new_k.append(kp)
                new_v.append(vp)
                out = paged_attention(q._data, kp, vp, tables, pos,
                                      ks, vs)
                # ONE psum per layer: each shard owns a disjoint head
                # range, so summing zero-padded buffers is exact
                full = jnp.zeros((b, s, heads, hd), out.dtype)
                full = lax.dynamic_update_slice(
                    full, out, (0, 0, ti * heads_l, 0))
                full = lax.psum(full, axis)
                o = attn.o_proj(M.reshape(Tensor(full),
                                          [b, s, heads * hd]))
                x = residual + layer.dropout(gather(o))
                residual = x
                h2 = layer.post_attention_layernorm(x)
                g = gather(F.silu(layer.mlp.gate_proj(h2))
                           * layer.mlp.up_proj(h2))
                d = gather(layer.mlp.down_proj(g))
                x = residual + layer.dropout(d)
            x = mdl.norm(x)
            logits = gather(self.model.lm_head(x))
        if quant:
            return (logits._data, tuple(new_k), tuple(new_v),
                    tuple(new_ks), tuple(new_vs))
        return logits._data, tuple(new_k), tuple(new_v)

    def _run_model(self, state_arrays, ids, views, rows=None):
        """The single override point: same contract as the base
        ``_run_model`` (raw param arrays + ids + PagedKV views ->
        (logits, new views)), routed through the mesh forward.  Every
        caller — prefill, the horizon-scan body, spec-decode verify
        windows — inherits sharding with no code of its own.  The mesh
        forward's sharded head still projects every position; ``rows``
        (prefill's one position a lane) indexes its logits."""
        num_layers = len(views)
        tables, pos = views[0].tables, views[0].pos
        pool_k = tuple(v.k for v in views)
        pool_v = tuple(v.v for v in views)
        if self._kv_quant:
            pool_ks = tuple(v.k_scale for v in views)
            pool_vs = tuple(v.v_scale for v in views)
            logits, nk, nv, nks, nvs = self._mesh_fwd(
                tuple(state_arrays), ids, tables, pos, pool_k, pool_v,
                pool_ks, pool_vs)
        else:
            logits, nk, nv = self._mesh_fwd(
                tuple(state_arrays), ids, tables, pos, pool_k, pool_v)
            nks = nvs = (None,) * num_layers
        s = ids.shape[1]
        new_views = [PagedKV(k, v, tables, pos + s, ks, vs)
                     for k, v, ks, vs in zip(nk, nv, nks, nvs)]
        if rows is not None:
            logits = _lane_rows(logits, rows)
        return logits, new_views

    # ------------------------------------------------------------ census
    def expected_decode_census(self, horizon=None, k_draft=0):
        """The hand-derived collective census of one compiled decode
        dispatch, which the census tests hold the walker to.  Per
        scanned step: L psums (head combines) + 3L+1 all_gathers
        (o_proj, SwiGLU intermediate, down_proj per layer; lm_head
        once); int8 KV adds 2L pmaxes (k and v absmax per layer)."""
        h = int(horizon or self.config.max_horizon)
        num_layers = len(self.model.model.layers)
        axis = self.layout.tp_axis
        census = {("psum", axis): num_layers * h,
                  ("all_gather", axis): (3 * num_layers + 1) * h}
        if self._kv_quant:
            census[("pmax", axis)] = 2 * num_layers * h
        return census

    def decode_census_program(self, horizon=None, k_draft=0, nb=2):
        """(fn, args) for the comms walker / bench: the REAL compiled
        decode program (``_decode_fn`` with static horizon/k baked)
        over representative zero-state arguments at table width
        ``nb``."""
        h = int(horizon or self.config.max_horizon)
        n = self.config.num_slots
        nb = int(min(nb, self.cache.max_blocks_per_slot))
        i32, f32 = jnp.int32, jnp.float32
        pool_ks = list(self.pool.k_scale) if self._kv_quant else None
        pool_vs = list(self.pool.v_scale) if self._kv_quant else None
        args = (self._state_arrays,
                jnp.zeros(n, i32), jnp.zeros(n, i32), jnp.zeros(n, i32),
                jnp.ones(n, bool),
                jnp.zeros((n, self.config.max_seq_len), i32),
                jnp.ones(n, bool), jnp.zeros(n, jnp.uint32),
                jnp.zeros(n, f32), jnp.zeros(n, i32), jnp.ones(n, f32),
                jnp.full(n, -1, i32),
                jnp.full(n, self.config.max_seq_len, i32),
                jnp.zeros((n, nb), i32),
                list(self.pool.k), list(self.pool.v), pool_ks, pool_vs)
        # grammar args ride as keywords (positional would land on the
        # horizon/k_draft slots already bound above); Nones with
        # structured generation off, slab tables + sentinel states on
        dfa_state, dfa_next, dfa_mask, dfa_forced = \
            self._grammar_program_args()
        fn = functools.partial(self._decode_fn, horizon=h,
                               k_draft=int(k_draft),
                               dfa_state=dfa_state, dfa_next=dfa_next,
                               dfa_mask=dfa_mask, dfa_forced=dfa_forced)
        return fn, args

    def decode_comms_report(self, horizon=None, k_draft=0, publish=False):
        """Walk the decode program's jaxpr with the PR 11 comms walker,
        assert it matches the hand census, and return the CommsReport
        (per-op counts + analytic wire bytes).  ``publish=True`` also
        lands the counts on the typed metrics registry — the serving
        programs' comms card."""
        from ...observability import comms

        fn, args = self.decode_census_program(horizon, k_draft)
        report = comms.analyze_fn(fn, *args)
        expected = self.expected_decode_census(horizon, k_draft)
        got = report.counts()
        if got != expected:
            raise AssertionError(
                f"decode census {got} != hand-derived {expected}")
        if publish:
            report.publish()
        return report

    # ------------------------------------------------------------- stats
    def stats(self):
        """Base engine stats plus the mesh stamp: shape, devices, and
        the per-shard slice of the KV pool (each chip holds only
        kv_heads/tp of every block)."""
        s = super().stats()
        s["mesh"] = {
            "mesh_shape": {"dp": self.mesh_shape[0],
                           "tp": self.mesh_shape[1]},
            "axes": list(self.layout.mesh_axes),
            "devices": [str(d) for d in self.mesh.devices.flat],
            "kv_pool_bytes_per_shard":
                self._kv_pool_bytes() // self.tp,
            "kv_heads_per_shard":
                self.model.config.kv_heads // self.tp,
        }
        return s
