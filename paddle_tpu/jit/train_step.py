"""TrainStep: the whole training step (forward + backward + optimizer) as ONE
compiled XLA program.

This is the TPU performance path that replaces the reference's
to_static-training + CINN pipeline (SURVEY.md §3.4): parameters and optimizer
state are functionalized into explicit pytree arguments (donated, so updates
are in-place in HBM), the tape runs at trace time, and XLA fuses fwd+bwd+adam
across the step. The same object also powers fleet.distributed_model's jitted
path, where `shardings` place params/batch on a mesh.
"""

from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from ..core.tensor import Tensor
from ..core import tape as _tape
from ..core import random_state
from ..observability import metrics as _obs_metrics
from ..observability.span import build as _obs_build, span as _obs_span

# NOTE: jax dispatch is async — step_seconds is host wall time per
# dispatched step, which converges to true step time whenever the caller
# consumes the loss (float()) each step, as Model.fit and every trainer
# in this repo do
_STEP_SECONDS = _obs_metrics.histogram(
    "train.step_seconds", "TrainStep wall seconds per compiled step")
_STEP_IPS = _obs_metrics.histogram(
    "train.ips", "TrainStep items (batch rows) per second")
_STEP_COUNT = _obs_metrics.counter(
    "train.steps", "compiled optimizer steps taken")


class TrainStep:
    def __init__(self, model, loss_fn, optimizer, scaler=None, donate=True,
                 mesh=None, in_shardings=None, has_aux=False,
                 auto_layout=None):
        """loss_fn(model, *batch_tensors) -> loss Tensor (scalar), or with
        has_aux=True -> (loss, aux) where aux is a Tensor/tuple of Tensors
        returned alongside the loss (e.g. network outputs for metric
        updates — ref Model.fit reports metrics every train batch).

        auto_layout (default: on for single-device steps): compile with
        compiler-CHOSEN input layouts (jax.experimental.layout AUTO) and
        re-lay the params/optimizer states out once to match. Without it,
        XLA must layout-copy big weights between the conv-preferred and
        the default parameter layout EVERY step (donated aliasing pins
        entry layout == exit layout): an earlier round's SD-UNet trace
        showed 40% of device time in f32 master-weight layout flips
        (that trace is not in the tree; not measured since)."""
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.scaler = scaler if (scaler is not None and scaler.is_enable()) else None
        self.donate = donate
        self.mesh = mesh
        self.has_aux = has_aux
        import os as _os

        env = _os.environ.get("PADDLE_TPU_AUTO_LAYOUT")
        if auto_layout is None and env is not None:
            auto_layout = env not in ("0", "false", "off")
        self.auto_layout = (auto_layout if auto_layout is not None
                            else mesh is None and in_shardings is None)
        benv = _os.environ.get("PADDLE_TPU_UPDATE_BARRIER")
        # None = decide at build time from model size (see _build): the
        # barrier un-fuses dW matmuls from the optimizer update — a big
        # win for compute-dense models (BERT +17% on-chip) but a loss for
        # huge-parameter models whose grads then materialize to HBM
        # (860M-param SD-UNet −9%)
        self.update_barrier = (benv not in ("0", "false", "off")
                               if benv is not None else None)
        self._jitted = None
        self._compiled_cache = {}
        self._layout_owner = None   # cache entry whose AUTO layouts the
        # state arrays currently hold (see _run_auto)
        self._param_names = None
        self._buffer_names = None

    def _ensure_states(self):
        # materialize optimizer accumulators before tracing
        for p in self.optimizer._parameter_list:
            self.optimizer._state_for(p)

    def _build(self):
        if self.update_barrier is None:
            param_bytes = sum(
                p._data.size * p._data.dtype.itemsize
                for p in self.optimizer._parameter_list
                if hasattr(p, "_data"))
            self.update_barrier = param_bytes <= 512 * 1024 * 1024
        for p in self.optimizer._parameter_list:
            sh = getattr(getattr(p, "_data", None), "sharding", None)
            if sh is not None and len(sh.device_set) > 1:
                # AUTO layouts lower from bare avals (no shardings): only
                # safe when every param lives on ONE device — a
                # DistModel/pipeline step whose params carry multi-device
                # NamedShardings would be silently gathered onto one chip
                self.auto_layout = False
                # the step is traced and run under the mesh its state is
                # sharded over, so code inside can see it (the attention
                # router runs its Mosaic kernel per shard: see
                # ops.flash_attention._per_shard)
                if self.mesh is None:
                    self.mesh = getattr(sh, "mesh", None)
                break
        step_fn = self._make_step_fn()
        # donated state buffers must exit with their ENTRY shardings or XLA
        # silently copies instead of aliasing ("Some donated buffers were
        # not usable" in the r4 dryrun tail — wasted HBM at scale): pin the
        # state outputs to the current state shardings when multi-device
        step_fn = self._constrain_state_outputs(step_fn)
        self._jitted = jax.jit(step_fn,
                               donate_argnums=(0, 2) if self.donate else ())

    def _mesh_scope(self):
        """``jax.set_mesh`` over the mesh of a sharded step; nothing for a
        single-device one."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    _NOSH = object()          # "leave this leaf unconstrained" sentinel

    def _constrain_state_outputs(self, step_fn):
        from jax.sharding import NamedSharding

        sd = self.model.state_dict()
        opt = self.optimizer
        nosh = TrainStep._NOSH

        def sh_of(a):
            s = getattr(a, "sharding", None)
            return (s if isinstance(s, NamedSharding)
                    and len(s.device_set) > 1 else nosh)

        p_sh = [sh_of(sd[n]._data) for n in self._param_names]
        b_sh = [sh_of(sd[n]._data) for n in self._buffer_names]
        # _state_for is get-or-create: params outside the optimizer's
        # parameter list materialize their accumulator here
        o_sh = [jax.tree.map(sh_of, opt._state_for(sd[n]))
                for n in self._param_names]
        if all(s is nosh for s in p_sh + b_sh) and all(
                s is nosh for st in o_sh for s in jax.tree.leaves(st)):
            return step_fn          # single-device state: nothing to pin

        def cst(a, s):
            return a if s is nosh else jax.lax.with_sharding_constraint(a, s)

        def constrained(pa, ba, os_, lr, key, ss, *batch):
            np_, nb, nos, loss, nss, aux = step_fn(pa, ba, os_, lr, key,
                                                   ss, *batch)
            np_ = [cst(a, s) for a, s in zip(np_, p_sh)]
            nb = [cst(a, s) for a, s in zip(nb, b_sh)]
            nos = [jax.tree.map(cst, st, s) for st, s in zip(nos, o_sh)]
            return np_, nb, nos, loss, nss, aux

        return constrained

    def _run_auto(self, *args, _fn_factory=None, _key_tag=()):
        """AUTO-layout execution: jit with compiler-CHOSEN layouts for the
        params/buffers/opt-state args only (batch/lr/rng keep the default
        layout — relaying a fresh host batch out every step cost ResNet
        ~5%), compile per arg signature, query the chosen input formats,
        and device_put any mismatched state leaf ONCE — donated aliasing
        keeps every later step zero-copy. `_fn_factory`/`_key_tag` let
        many() run its scanned K-step program through the same treatment
        (args keep the (params, buffers, opt_states, ...) leading trio)."""
        auto_spec = Format(Layout.AUTO)

        flat, treedef = jax.tree.flatten(args)
        # only the batch part of the signature can vary between calls
        # (state shapes are fixed per TrainStep); keying on it alone keeps
        # the per-step key O(batch) instead of O(params)
        bflat, btree = jax.tree.flatten(args[6:])
        key = (_key_tag, len(flat), btree,
               tuple((a.shape, a.dtype) for a in bflat))
        ent = self._compiled_cache.get(key)
        if ent is None:
            specs = (auto_spec,) * 3 + (None,) * (len(args) - 3)
            # buffers (arg 1) are donated here too: their exit layouts
            # must alias their AUTO entry layouts for the trusted-skip
            # below to hold for >=2-D buffers
            jitted = jax.jit((_fn_factory or self._make_step_fn)(),
                             donate_argnums=(0, 1, 2) if self.donate else (),
                             in_shardings=specs,
                             out_shardings=auto_spec)
            # AUTO-layout lowering requires abstract avals (concrete
            # arrays carry layouts that would contradict AUTO)
            sds = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                               jnp.asarray(a).dtype), args)
            compiled = jitted.lower(*sds).compile()
            fmt_flat, fmt_tree = jax.tree.flatten(compiled.input_formats[0])
            if fmt_tree != treedef:  # defensive: structures must agree
                raise RuntimeError("input_formats structure mismatch")
            # leaves of args 0/1/2 (params, buffers, opt states) are
            # rebound from the step's outputs, so their relayout may
            # DONATE the source buffer (no transient double copy of the
            # model+optimizer); lr/rng/batch buffers are caller-owned
            own = set()
            off = 0
            for i, a in enumerate(args):
                n = len(jax.tree.flatten(a)[0])
                if i in (0, 1, 2):
                    own.update(range(off, off + n))
                off += n
            ent = self._compiled_cache[key] = (compiled, fmt_flat, own)
        compiled, fmt_flat, own = ent
        # after the first successful call under THIS entry the own (state)
        # leaves come back from the step's outputs already in the chosen
        # layouts (donated aliasing) — checking ~2k Formats per step cost
        # ~15 ms of Python on the 860M-param UNet, so trust the aliasing
        # and only verify the few caller-owned leaves (batch/lr/rng). The
        # trust is keyed to ONE entry at a time: switching batch shapes
        # relayouts the state into the new entry's formats, so any other
        # entry must re-verify from scratch.
        trusted = self._layout_owner == key
        moved = [a if (trusted and i in own)
                 or getattr(a, "format", None) == f
                 else jax.device_put(a, f, donate=(i in own))
                 for i, (a, f) in enumerate(zip(flat, fmt_flat))]
        try:
            out = compiled(*jax.tree.unflatten(treedef, moved))
        except ValueError as e:
            # ONLY argument-layout mismatches are retryable (raised at
            # arg-processing time, BEFORE execution/donation — a state
            # leaf was rebound externally, e.g. load_state_dict
            # mid-training). Genuine runtime failures (OOM, asserts) may
            # have consumed donated buffers; retrying would bury the real
            # error under "Array has been deleted".
            if trusted and "layout" in str(e).lower():
                self._layout_owner = None
                return self._run_auto(*args, _fn_factory=_fn_factory,
                                      _key_tag=_key_tag)
            raise
        self._layout_owner = key
        return out

    def _make_step_fn(self):
        """Construct the pure step function (params/buffers/opt-state pytrees
        in, updated pytrees out) — subclasses jit it with their own shardings."""
        model = self.model
        opt = self.optimizer
        sd = model.state_dict()
        params = {n: t for n, t in sd.items() if isinstance(t, Tensor) and not t.stop_gradient}
        buffers = {n: t for n, t in sd.items() if n not in params}
        self._param_names = list(params.keys())
        self._buffer_names = list(buffers.keys())
        name_by_id = {id(p): n for n, p in params.items()}
        loss_fn = self.loss_fn
        has_aux = self.has_aux

        scaler = self.scaler

        def step_fn(param_arrays, buffer_arrays, opt_states, lr, rng_key,
                    scaler_state, *batch):
            arrays = dict(zip(self._param_names, param_arrays))
            arrays.update(zip(self._buffer_names, buffer_arrays))
            with random_state.fork_rng(rng_key):
                with model.use_state(arrays):
                    sd_live = model.state_dict()
                    live_params = [sd_live[n] for n in self._param_names]
                    for p in live_params:
                        p.grad = None
                    res = loss_fn(model, *[Tensor(b) for b in batch])
                    if has_aux:
                        loss, aux = res
                        aux_arrays = jax.tree.map(
                            lambda t: t._data if isinstance(t, Tensor) else t,
                            aux)
                    else:
                        loss, aux_arrays = res, ()
                    found_inf = jnp.zeros((), jnp.bool_)
                    if scaler is None:
                        loss.backward()
                    else:
                        # dynamic loss scaling, fully in-program (the
                        # reference's GradScaler.scale/unscale_/update,
                        # grad_scaler.py (U), staged into one XLA step)
                        scale, good, bad = scaler_state
                        (loss * Tensor(scale)).backward()
                        inv = 1.0 / scale
                        with _tape.no_grad():
                            for p in live_params:
                                if p.grad is None:
                                    continue
                                g32 = p.grad._data.astype(jnp.float32) * inv
                                found_inf = found_inf | ~jnp.all(jnp.isfinite(g32))
                                p.grad._data = g32.astype(p.grad._data.dtype)
                    params_grads = [(p, p.grad) for p in live_params if p.grad is not None]
                    if opt._grad_clip is not None:
                        params_grads = opt._grad_clip(params_grads)
                    if self.update_barrier and params_grads:
                        # keep the dW matmuls OUT of the optimizer-update
                        # fusions: fused (dW + AdamW) ops ran at ~18
                        # TFLOP/s on the r4 BERT trace vs ~60+ for the
                        # bare matmul — the epilogue's 4 full-size f32
                        # outputs wreck the MXU pipeline
                        barr = jax.lax.optimization_barrier(
                            [g._data for _, g in params_grads])
                        for (_, g), na in zip(params_grads, barr):
                            g._data = na
                    grad_by_id = {id(p): g for p, g in params_grads}
                    new_params = []
                    new_opt_states = []
                    with _tape.no_grad():
                        for n, st in zip(self._param_names, opt_states):
                            p = sd_live[n]
                            g = grad_by_id.get(id(p))
                            if g is None:
                                new_params.append(p._data)
                                new_opt_states.append(st)
                                continue
                            plr = lr * p.optimize_attr.get("learning_rate", 1.0)
                            g_arr = opt._regularized_grad(p, g._data)
                            np_, nst = opt._update_for(p, p._data, g_arr, st,
                                                       plr)
                            if scaler is not None:
                                # skip the step on inf/nan grads
                                np_ = jnp.where(found_inf, p._data, np_)
                                nst = jax.tree.map(
                                    lambda new, old: jnp.where(found_inf, old, new),
                                    nst, st)
                            new_params.append(np_)
                            new_opt_states.append(nst)
                    new_buffers = [model.state_dict()[n]._data for n in self._buffer_names]
                    # clear tracer grads so they don't leak out of the trace
                    for p in live_params:
                        p.grad = None
            if scaler is None:
                new_scaler_state = scaler_state
            else:
                # GradScaler.update() semantics, traced
                bad1 = jnp.where(found_inf, bad + 1, jnp.zeros_like(bad))
                good1 = jnp.where(found_inf, jnp.zeros_like(good), good + 1)
                dec = found_inf & (bad1 >= scaler._decr_every)
                inc = (~found_inf) & (good1 >= scaler._incr_every)
                if not scaler._dynamic:
                    dec = inc = jnp.zeros((), jnp.bool_)
                new_scale = jnp.where(
                    dec, jnp.maximum(scale * scaler._decr_ratio, 1.0),
                    jnp.where(inc, scale * scaler._incr_ratio, scale))
                new_scaler_state = (new_scale,
                                    jnp.where(inc, jnp.zeros_like(good1), good1),
                                    jnp.where(dec, jnp.zeros_like(bad1), bad1))
            return (new_params, new_buffers, new_opt_states, loss._data,
                    new_scaler_state, aux_arrays)

        return step_fn

    def _marshal(self, *batch, draw_key=True):
        """Build the exact positional argument tuple __call__ feeds the
        jitted step (also used by cost_analysis, which must NOT advance the
        global RNG stream — pass draw_key=False there)."""
        if self._jitted is None:
            self._ensure_states()
            self._build()
        # the state Tensor OBJECTS are stable across steps (__call__
        # rebinds their ._data in place) — walking the module tree per
        # step cost ~10 ms of Python on an 860M-param model
        sd = getattr(self, "_sd_cache", None)
        if sd is None:
            sd = self._sd_cache = self.model.state_dict()
        param_arrays = [sd[n]._data for n in self._param_names]
        buffer_arrays = [sd[n]._data for n in self._buffer_names]
        opt = self.optimizer
        opt_states = [opt._state_for(sd[n]) for n in self._param_names]
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        rng_key = (random_state.next_key() if draw_key
                   else jax.random.PRNGKey(0))
        batch_arrays = [b._data if isinstance(b, Tensor) else jnp.asarray(b) for b in batch]
        if self.scaler is not None:
            scaler_state = (jnp.asarray(self.scaler._scale, jnp.float32),
                            jnp.asarray(self.scaler._good_steps, jnp.int32),
                            jnp.asarray(self.scaler._bad_steps, jnp.int32))
        else:
            scaler_state = ()
        return (sd, param_arrays, buffer_arrays, opt_states, lr, rng_key,
                scaler_state, batch_arrays)

    def cost_analysis(self, *batch):
        """XLA cost analysis of the COMPILED step executable (flops, bytes
        accessed, ...) — post-optimization counts, so CSE'd/DCE'd work is
        not credited to utilization numbers. Compiling here re-runs XLA
        (the executable cache may or may not absorb it) — acceptable for
        benchmarking, not for hot paths; the pre-optimization
        lowering-level analysis is only the fallback."""
        (_, param_arrays, buffer_arrays, opt_states, lr, rng_key,
         scaler_state, batch_arrays) = self._marshal(*batch, draw_key=False)
        lowered = self._jitted.lower(param_arrays, buffer_arrays, opt_states,
                                     lr, rng_key, scaler_state, *batch_arrays)
        try:
            cost = lowered.compile().cost_analysis()
        except Exception:
            cost = None
        if not cost:
            cost = lowered.cost_analysis()
        # jax returns either a dict or a per-device list of dicts
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return cost

    def many(self, batches):
        """Run K optimizer steps as ONE compiled program (`lax.scan` over
        the single-step fn): the same UPDATE math as K sequential
        __call__s (bitwise for RNG-free steps; see the RNG caveat below) —
        K parameter/optimizer updates, each with its own RNG key — but one
        host dispatch, which matters when dispatch latency (not compute)
        bounds wall-clock. `batches` is a list of K
        equal-shape batch tuples. LR is read ONCE for the whole pack (an
        LRScheduler stepped between many() calls behaves like a
        per-K-steps schedule), and the K keys come from ONE split of the
        global stream — statistically equivalent to, but not bitwise the
        same as, the K successive draws sequential __call__s make
        (dropout masks differ; RNG-free steps match exactly).
        Returns the K per-step losses as one Tensor [K]."""
        if not batches:
            raise ValueError("many() expects at least one batch")
        t0 = time.perf_counter()
        if self.has_aux:
            raise ValueError("many() does not support has_aux steps (the "
                             "per-step aux would be K-stacked; run "
                             "__call__ per step instead)")
        cls = type(self)
        if (cls._build is not TrainStep._build
                or cls._make_step_fn is not TrainStep._make_step_fn
                or cls._run_auto is not TrainStep._run_auto):
            # a subclass that overrides dispatch (GroupShardedTrainStep's
            # sharded _build/_place_states) would be silently bypassed by
            # this scan — params would compile UNSHARDED; benign
            # subclasses that keep the dispatch methods inherit many()
            raise NotImplementedError(
                f"many() supports TrainStep's own dispatch; "
                f"{cls.__name__} overrides it and must run one step per "
                "call")
        k = len(batches)
        # marshal STATE only (no batch: its arrays would be converted
        # here and discarded, a wasted H2D copy on the latency path)
        (sd, param_arrays, buffer_arrays, opt_states, lr, _, scaler_state,
         _) = self._marshal(draw_key=False)
        tuples = [b if isinstance(b, (tuple, list)) else (b,)
                  for b in batches]
        stacked = [
            jnp.stack([(b[i]._data if isinstance(b[i], Tensor)
                        else jnp.asarray(b[i])) for b in tuples])
            for i in range(len(tuples[0]))
        ]
        rng_keys = jax.random.split(random_state.next_key(), k)

        def make_many_fn():
            step_fn = self._constrain_state_outputs(self._make_step_fn())

            def many_fn(pa, ba, os_, lr_, keys, ss, *stk):
                def body(carry, xs):
                    pa_, ba_, os2, ss2 = carry
                    key = xs[0]
                    batch = xs[1:]
                    np_, nb, nos, loss, nss, _aux = step_fn(
                        list(pa_), list(ba_), list(os2), lr_, key, ss2,
                        *batch)
                    return (tuple(np_), tuple(nb), tuple(nos), nss), loss

                (pa2, ba2, os2, ss2), losses = jax.lax.scan(
                    body, (tuple(pa), tuple(ba), tuple(os_), ss),
                    (keys,) + stk)
                return list(pa2), list(ba2), list(os2), losses, ss2

            return many_fn

        run_args = (param_arrays, buffer_arrays, opt_states, lr, rng_keys,
                    scaler_state) + tuple(stacked)
        if self.auto_layout:
            # big-parameter models (SD-UNet) NEED the AUTO-layout
            # treatment inside the scan too — plain jit re-pins the
            # donated entry layouts and re-introduces the per-step
            # master-weight layout flips the r4 trace diagnosed
            (new_params, new_buffers, new_opt_states, losses,
             new_scaler_state) = self._run_auto(
                *run_args, _fn_factory=make_many_fn, _key_tag=("many", k))
        else:
            ckey = ("many", k,
                    tuple((a.shape, str(a.dtype)) for a in stacked))
            jitted = self._compiled_cache.get(ckey)
            if jitted is None:
                jitted = jax.jit(
                    make_many_fn(),
                    donate_argnums=(0, 1, 2) if self.donate else ())
                self._compiled_cache[ckey] = jitted
            with self._mesh_scope():
                (new_params, new_buffers, new_opt_states, losses,
                 new_scaler_state) = jitted(*run_args)
        if self.scaler is not None:
            (self.scaler._scale, self.scaler._good_steps,
             self.scaler._bad_steps) = new_scaler_state
        opt = self.optimizer
        for n, arr in zip(self._param_names, new_params):
            sd[n]._data = arr
        for n, arr in zip(self._buffer_names, new_buffers):
            sd[n]._data = arr
        for n, st in zip(self._param_names, new_opt_states):
            opt._accumulators[id(sd[n])] = st
        opt._step_count += k
        dt = time.perf_counter() - t0
        _STEP_COUNT.inc(k)
        # one observation per pack: the per-step average of the scanned
        # K-step program (individual in-scan steps are not host-visible)
        _STEP_SECONDS.observe(dt / k)
        return Tensor(losses)

    def __call__(self, *batch):
        """One step, enqueued: returns without a block on the device (the
        loss is a device array until the caller reads it).  The span log
        gets ``train.step.enqueue`` a call, and the first call, which
        builds the program, one record in the build table."""
        building = contextlib.nullcontext() if self._jitted is not None \
            else _obs_build("train_step", {
                "batch": [list(getattr(b, "shape", ())) for b in batch]})
        with _obs_span("train.step.enqueue",
                       step=self.optimizer._step_count), building:
            return self._step(*batch)

    def _step(self, *batch):
        t0 = time.perf_counter()
        (sd, param_arrays, buffer_arrays, opt_states, lr, rng_key,
         scaler_state, batch_arrays) = self._marshal(*batch)
        opt = self.optimizer
        run = self._run_auto if self.auto_layout else self._jitted
        with self._mesh_scope():
            (new_params, new_buffers, new_opt_states, loss, new_scaler_state,
             aux_arrays) = run(
                param_arrays, buffer_arrays, opt_states, lr, rng_key,
                scaler_state, *batch_arrays
            )
        if self.scaler is not None:
            self.scaler._scale, self.scaler._good_steps, self.scaler._bad_steps = (
                new_scaler_state)
        for n, arr in zip(self._param_names, new_params):
            sd[n]._data = arr
        for n, arr in zip(self._buffer_names, new_buffers):
            sd[n]._data = arr
        for n, st in zip(self._param_names, new_opt_states):
            opt._accumulators[id(sd[n])] = st
        opt._step_count += 1
        dt = time.perf_counter() - t0
        _STEP_COUNT.inc()
        _STEP_SECONDS.observe(dt)
        if batch_arrays and hasattr(batch_arrays[0], "shape") \
                and batch_arrays[0].shape and dt > 0:
            _STEP_IPS.observe(batch_arrays[0].shape[0] / dt)
        if self.has_aux:
            return Tensor(loss), jax.tree.map(Tensor, aux_arrays)
        return Tensor(loss)
