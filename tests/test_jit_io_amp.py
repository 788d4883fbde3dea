"""to_static / TrainStep / io / amp tests (SURVEY.md §4 dy2static pattern:
eager vs compiled parity)."""

import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


class TestToStatic:
    def test_eager_static_parity(self):
        def fn(x, y):
            return paddle.tanh(x) @ y + x.sum()

        static_fn = paddle.jit.to_static(fn)
        a, b = paddle.randn([4, 4]), paddle.randn([4, 4])
        np.testing.assert_allclose(static_fn(a, b).numpy(), fn(a, b).numpy(), rtol=1e-5, atol=1e-6)

    def test_cache_by_shape(self):
        calls = []

        @paddle.jit.to_static
        def fn(x):
            calls.append(1)
            return x * 2

        fn(paddle.ones([2, 3]))
        fn(paddle.ones([2, 3]))
        assert len(calls) == 1  # traced once
        fn(paddle.ones([4, 3]))
        assert len(calls) == 2  # retraced on new shape

    def test_layer_to_static_updates_buffers(self):
        bn = nn.BatchNorm1D(4)
        bn = paddle.jit.to_static(bn)
        x = paddle.randn([8, 4]) * 3 + 1
        bn(x)
        assert abs(float(bn._mean.numpy().mean())) > 1e-4  # running stats moved

    def test_randomness_varies_across_calls(self):
        drop = nn.Dropout(0.5)
        drop = paddle.jit.to_static(drop)
        x = paddle.ones([100])
        a = drop(x).numpy()
        b = drop(x).numpy()
        assert not np.array_equal(a, b)  # rng key threaded per call

    def test_alternating_state_signatures_keep_own_captures(self):
        """ADVICE r5: one StaticFunction cache entry holds several jax.jit
        traces when the STATE changes aval (inputs identical, so _spec_key
        matches) — e.g. amp rebinding a param's dtype. The out-tree /
        mutation capture must be keyed per trace signature: with the old
        single last-trace box, alternating calls applied the most recent
        trace's output structure to the other signature's results."""
        import jax.numpy as jnp

        class DtypeDependent(nn.Layer):
            def __init__(self):
                super().__init__()
                self.lin = nn.Linear(4, 4)

            def forward(self, x):
                y = self.lin(x)
                # trace-time static on the PARAM dtype, not the input:
                # both traces live under one _spec_key cache entry
                if str(self.lin.weight.dtype) == "float32":
                    return y
                return {"out": y, "casted": True}

        layer = paddle.jit.to_static(DtypeDependent())
        x = paddle.ones([2, 4])
        out_f32 = layer(x)
        assert isinstance(out_f32, paddle.Tensor)
        w = layer.lin.weight
        w32 = w._data
        w._data = w32.astype(jnp.bfloat16)
        out_bf16 = layer(x)
        assert isinstance(out_bf16, dict) and out_bf16["casted"] is True
        # flip back: the f32 trace's capture must be found again
        w._data = w32
        again = layer(x)
        assert isinstance(again, paddle.Tensor)
        np.testing.assert_array_equal(again.numpy(), out_f32.numpy())
        # and forward once more on the bf16 signature
        w._data = w32.astype(jnp.bfloat16)
        assert isinstance(layer(x), dict)

    def test_jit_save_load_roundtrip(self, tmp_path):
        layer = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        layer.eval()
        path = str(tmp_path / "model")
        paddle.jit.save(layer, path, input_spec=[paddle.jit.InputSpec([1, 4])])
        loaded = paddle.jit.load(path)
        x = paddle.randn([1, 4])
        np.testing.assert_allclose(loaded(x).numpy(), layer(x).numpy(), rtol=1e-5, atol=1e-6)


class TestTrainStep:
    def test_matches_eager_training(self):
        paddle.seed(3)
        X = np.random.RandomState(0).rand(32, 4).astype(np.float32)
        Y = X.sum(-1, keepdims=True)

        def build():
            paddle.seed(7)
            m = nn.Linear(4, 1)
            o = paddle.optimizer.Adam(learning_rate=0.1, parameters=m.parameters())
            return m, o

        # eager
        m1, o1 = build()
        for _ in range(5):
            loss = F.mse_loss(m1(paddle.to_tensor(X)), paddle.to_tensor(Y))
            loss.backward()
            o1.step()
            o1.clear_grad()
        # jitted
        m2, o2 = build()
        step = paddle.jit.TrainStep(m2, lambda net, x, y: F.mse_loss(net(x), y), o2)
        for _ in range(5):
            step(paddle.to_tensor(X), paddle.to_tensor(Y))
        np.testing.assert_allclose(m1.weight.numpy(), m2.weight.numpy(), rtol=1e-4, atol=1e-5)

    def test_to_static_model_trains_with_eager_backward(self):
        """Paddle parity: `model = to_static(model); loss.backward();
        opt.step()` — the jitted forward records as ONE tape node whose
        vjp flows grads to the parameters."""
        X = np.random.RandomState(0).rand(32, 4).astype(np.float32)
        Y = X.sum(-1, keepdims=True)

        def build():
            paddle.seed(7)
            m = nn.Linear(4, 1)
            o = paddle.optimizer.SGD(learning_rate=0.1,
                                     parameters=m.parameters())
            return m, o

        m1, o1 = build()                       # eager reference
        for _ in range(5):
            loss = F.mse_loss(m1(paddle.to_tensor(X)), paddle.to_tensor(Y))
            loss.backward()
            o1.step()
            o1.clear_grad()
        m2, o2 = build()
        paddle.jit.to_static(m2)               # jitted forward, eager loop
        for _ in range(5):
            loss = F.mse_loss(m2(paddle.to_tensor(X)), paddle.to_tensor(Y))
            loss.backward()
            o2.step()
            o2.clear_grad()
        np.testing.assert_allclose(m1.weight.numpy(), m2.weight.numpy(),
                                   rtol=1e-5, atol=1e-6)
        # grads also flow to differentiable INPUTS through the jit node
        x = paddle.to_tensor(X)
        x.stop_gradient = False
        m2(x).sum().backward()
        assert x.grad is not None and x.grad.shape == [32, 4]

    def test_many_matches_sequential_steps(self):
        """many(K): one scanned program == K sequential __call__s (same
        updates, K× fewer dispatches — the dispatch-latency amortizer)."""
        rng = np.random.RandomState(1)
        batches = [(paddle.to_tensor(rng.rand(16, 4).astype(np.float32)),
                    paddle.to_tensor(rng.rand(16, 1).astype(np.float32)))
                   for _ in range(4)]

        def build():
            paddle.seed(11)
            m = nn.Linear(4, 1)
            o = paddle.optimizer.Adam(learning_rate=0.05,
                                      parameters=m.parameters())
            return m, o

        m1, o1 = build()
        step1 = paddle.jit.TrainStep(m1, lambda net, x, y: F.mse_loss(net(x), y), o1)
        seq_losses = [float(step1(*b)) for b in batches]
        m2, o2 = build()
        step2 = paddle.jit.TrainStep(m2, lambda net, x, y: F.mse_loss(net(x), y), o2)
        many_losses = step2.many(batches).numpy()
        np.testing.assert_allclose(many_losses, seq_losses, rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(m1.weight.numpy(), m2.weight.numpy(),
                                   rtol=1e-5, atol=1e-6)
        assert o2._step_count == 4

    def test_grad_clip_inside_step(self):
        m = nn.Linear(4, 1)
        o = paddle.optimizer.SGD(learning_rate=1.0, parameters=m.parameters(),
                                 grad_clip=nn.ClipGradByGlobalNorm(0.01))
        step = paddle.jit.TrainStep(m, lambda net, x, y: F.mse_loss(net(x), y) * 1000, o)
        w0 = m.weight.numpy().copy()
        step(paddle.randn([8, 4]), paddle.randn([8, 1]))
        delta = np.linalg.norm(
            np.concatenate([(m.weight.numpy() - w0).ravel(),
                            (m.bias.numpy() - 0 * m.bias.numpy()).ravel() * 0])
        )
        assert delta < 0.02  # bounded by clip * lr plus bias


class TestIO:
    def test_dataloader_shapes_order(self):
        from paddle_tpu.io import DataLoader, Dataset

        class DS(Dataset):
            def __len__(self):
                return 10

            def __getitem__(self, i):
                return np.full((2,), i, np.float32), i

        dl = DataLoader(DS(), batch_size=3, drop_last=False)
        batches = list(dl)
        assert len(batches) == 4
        assert batches[0][0].shape == [3, 2]
        assert batches[-1][0].shape == [1, 2]
        np.testing.assert_array_equal(batches[0][1].numpy(), [0, 1, 2])

    def test_threaded_loader_preserves_order(self):
        from paddle_tpu.io import DataLoader, Dataset

        class DS(Dataset):
            def __len__(self):
                return 50

            def __getitem__(self, i):
                import time

                time.sleep(0.001 * (i % 3))
                return np.asarray([i], np.float32)

        dl = DataLoader(DS(), batch_size=5, num_workers=3)
        got = np.concatenate([b.numpy().ravel() for b in dl])
        np.testing.assert_array_equal(got, np.arange(50, dtype=np.float32))

    def test_distributed_batch_sampler_partitions(self):
        from paddle_tpu.io import DistributedBatchSampler, Dataset

        class DS(Dataset):
            def __len__(self):
                return 12

            def __getitem__(self, i):
                return i

        seen = []
        for rank in range(3):
            s = DistributedBatchSampler(DS(), batch_size=2, num_replicas=3, rank=rank)
            for batch in s:
                seen.extend(batch)
        assert sorted(seen) == list(range(12))

    def test_random_split_and_concat(self):
        from paddle_tpu.io import random_split, ConcatDataset, TensorDataset

        ds = TensorDataset([paddle.arange(10).reshape([10, 1])])
        a, b = random_split(ds, [7, 3])
        assert len(a) == 7 and len(b) == 3
        cat = ConcatDataset([a, b])
        assert len(cat) == 10


class TestAmp:
    def test_autocast_matmul_bf16(self):
        a = paddle.randn([4, 4])
        with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
            out = paddle.matmul(a, a)
        assert out.dtype == paddle.bfloat16

    def test_blacklist_stays_fp32(self):
        a = paddle.randn([4, 4])
        with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
            out = F.softmax(a)
        assert out.dtype == paddle.float32

    def test_o2_decorate_casts_params(self):
        m = nn.Linear(4, 4)
        opt = paddle.optimizer.Adam(parameters=m.parameters())
        m, opt = paddle.amp.decorate(m, opt, level="O2", dtype="bfloat16")
        assert m.weight.dtype == paddle.bfloat16
        assert opt._multi_precision

    def test_grad_flows_through_autocast(self):
        m = nn.Linear(4, 4)
        x = paddle.randn([2, 4])
        with paddle.amp.auto_cast(dtype="bfloat16"):
            out = m(x).sum()
        out.backward()
        assert m.weight.grad is not None
        assert m.weight.grad.dtype == paddle.float32  # grads back in param dtype


class TestPyLayer:
    def test_custom_vjp(self):
        from paddle_tpu.autograd import PyLayer

        class Exp2(PyLayer):
            @staticmethod
            def forward(ctx, x):
                ctx.save_for_backward(x)
                return paddle.exp(x * 2)

            @staticmethod
            def backward(ctx, dy):
                (x,) = ctx.saved_tensor()
                return dy * 2 * paddle.exp(x * 2)

        x = paddle.to_tensor(0.5, stop_gradient=False)
        y = Exp2.apply(x)
        y.backward()
        np.testing.assert_allclose(float(x.grad), 2 * np.exp(1.0), rtol=1e-5)


class TestCheckpointing:
    def test_model_save_load(self, tmp_path):
        net = nn.Linear(3, 3)
        m = paddle.Model(net)
        m.prepare(paddle.optimizer.Adam(parameters=net.parameters()), nn.MSELoss())
        p = str(tmp_path / "ck")
        m.save(p)
        assert os.path.exists(p + ".pdparams")
        net2 = nn.Linear(3, 3)
        m2 = paddle.Model(net2)
        m2.prepare(paddle.optimizer.Adam(parameters=net2.parameters()), nn.MSELoss())
        m2.load(p)
        np.testing.assert_array_equal(net.weight.numpy(), net2.weight.numpy())


class TestTrainStepScaler:
    def test_dynamic_loss_scaling_in_train_step(self):
        """Scaler staged into the jitted step: scale grows on good steps,
        halves on inf, and an inf step leaves params untouched."""
        paddle.seed(0)
        m = nn.Linear(4, 4)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2, parameters=m.parameters())
        scaler = paddle.amp.GradScaler(
            init_loss_scaling=2.0**10, incr_every_n_steps=2,
            decr_every_n_nan_or_inf=1)
        step = paddle.jit.TrainStep(
            m, lambda net, x, y: nn.functional.mse_loss(net(x), y), opt,
            scaler=scaler)
        x = paddle.randn([8, 4])
        y = paddle.randn([8, 4])
        l0 = float(step(x, y))
        for _ in range(3):
            l1 = float(step(x, y))
        assert l1 < l0
        assert float(scaler._scale) == 2.0**12  # two incr_every_n_steps=2 bumps
        w_before = m.weight.numpy().copy()
        xinf = paddle.to_tensor(np.full((8, 4), 1e30, np.float32))
        step(xinf, y)
        np.testing.assert_array_equal(m.weight.numpy(), w_before)
        assert float(scaler._scale) == 2.0**11  # halved on inf


class _PicklableDS:
    """Module-level (spawn-picklable) dataset for the process-worker test."""

    def __len__(self):
        return 24

    def __getitem__(self, i):
        import os

        return np.asarray([i, os.getpid()], np.int64)


class TestProcessWorkers:
    def test_process_loader_matches_sync_and_uses_other_pids(self):
        import os

        from paddle_tpu.io import DataLoader

        dl = DataLoader(_PicklableDS(), batch_size=4, num_workers=2,
                        use_process_workers=True, timeout=120)
        batches = [b.numpy() for b in dl]
        assert len(batches) == 6
        ids = np.concatenate([b[:, 0] for b in batches])
        np.testing.assert_array_equal(ids, np.arange(24))  # order preserved
        pids = set(np.concatenate([b[:, 1] for b in batches]).tolist())
        assert os.getpid() not in pids  # fetched in child processes
        assert len(pids) >= 1

    def test_process_worker_error_propagates(self):
        from paddle_tpu.io import DataLoader

        dl = DataLoader(_FailingDS(), batch_size=2, num_workers=2,
                        use_process_workers=True, timeout=120)
        import pytest as _pytest

        with _pytest.raises(RuntimeError, match="worker .* failed"):
            list(dl)


class _FailingDS:
    def __len__(self):
        return 8

    def __getitem__(self, i):
        if i == 5:
            raise ValueError("boom")
        return np.asarray([i], np.float32)


class TestInferencePredictor:
    """paddle.inference over jit-saved StableHLO: the reference's
    handle-based workflow end to end."""

    def test_handle_workflow_roundtrip(self, tmp_path):
        from paddle_tpu import inference
        from paddle_tpu.static import InputSpec

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 3))
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(4, 8).astype(np.float32))
        expect = model(x).numpy()
        prefix = str(tmp_path / "m")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([4, 8], "float32", "feats")])

        cfg = inference.Config(prefix + ".pdmodel", prefix + ".pdiparams")
        pred = inference.create_predictor(cfg)
        assert pred.get_input_names() == ["feats"]
        h = pred.get_input_handle("feats")
        h.copy_from_cpu(x.numpy())
        # output handles are wireable BEFORE the first run, and persist
        out_h = pred.get_output_handle(pred.get_output_names()[0])
        pred.run()
        np.testing.assert_allclose(out_h.copy_to_cpu(), expect,
                                   rtol=1e-5, atol=1e-6)
        # the SAME handle observes the next run's results (serving loop)
        h.copy_from_cpu(x.numpy() * 2.0)
        pred.run()
        expect2 = model(paddle.to_tensor(x.numpy() * 2.0)).numpy()
        np.testing.assert_allclose(out_h.copy_to_cpu(), expect2,
                                   rtol=1e-5, atol=1e-6)
        # legacy list mode still works
        legacy = pred.run([x.numpy()])
        np.testing.assert_allclose(legacy[0], expect, rtol=1e-5, atol=1e-6)

    def test_missing_input_raises(self, tmp_path):
        from paddle_tpu import inference
        from paddle_tpu.static import InputSpec

        model = nn.Linear(4, 2)
        prefix = str(tmp_path / "m2")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([2, 4], "float32")])
        pred = inference.create_predictor(inference.Config(prefix))
        with pytest.raises(RuntimeError, match="inputs not set"):
            pred.run()
        with pytest.raises(KeyError):
            pred.get_input_handle("nope")

    def test_params_path_honored_and_dup_names_rejected(self, tmp_path):
        import shutil

        from paddle_tpu import inference
        from paddle_tpu.static import InputSpec

        model = nn.Linear(4, 2)
        prefix = str(tmp_path / "m3")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([2, 4], "float32")])
        # params living elsewhere (real paddle layout)
        alt = str(tmp_path / "weights" / "final.pdiparams")
        (tmp_path / "weights").mkdir()
        shutil.move(prefix + ".pdiparams", alt)
        pred = inference.create_predictor(
            inference.Config(prefix + ".pdmodel", alt))
        out = pred.run([np.zeros((2, 4), np.float32)])
        assert out[0].shape == (2, 2)
        with pytest.raises(ValueError, match="unique"):
            paddle.jit.save(model, str(tmp_path / "m4"),
                            input_spec=[InputSpec([2, 4], "float32", "x"),
                                        InputSpec([2, 4], "float32", "x")])


class TestOnnxExportAdapter:
    """r4: paddle.onnx.export is a functional adapter — it writes the
    StableHLO serving artifact (with a loud format warning) instead of
    raising; jit.save now exports None dims batch-polymorphically."""

    def test_export_serves_any_batch(self, tmp_path):
        import warnings

        import paddle_tpu.onnx as ponnx
        import paddle_tpu.inference as inference
        from paddle_tpu.static import InputSpec

        paddle.seed(0)
        m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            p = ponnx.export(m, str(tmp_path / "model.onnx"),
                             input_spec=[InputSpec([None, 4], "float32")])
            assert any("StableHLO" in str(x.message) for x in w)
        pred = inference.create_predictor(inference.Config(p))
        for bs in (1, 3, 7):
            out = pred.run([np.ones((bs, 4), np.float32)])[0]
            assert out.shape == (bs, 2)
        ref = m(paddle.to_tensor(np.ones((3, 4), np.float32))).numpy()
        np.testing.assert_allclose(pred.run([np.ones((3, 4), np.float32)])[0],
                                   ref, rtol=1e-5)

    def test_export_requires_input_spec(self, tmp_path):
        import paddle_tpu.onnx as ponnx

        with pytest.raises(ValueError, match="input_spec"):
            ponnx.export(nn.Linear(2, 2), str(tmp_path / "m"))

    def test_jit_save_polymorphic_roundtrip(self, tmp_path):
        from paddle_tpu.static import InputSpec

        paddle.seed(1)
        m = nn.Linear(6, 3)
        paddle.jit.save(m, str(tmp_path / "poly"),
                        input_spec=[InputSpec([None, 6], "float32")])
        layer = paddle.jit.load(str(tmp_path / "poly"))
        for bs in (2, 5):
            x = np.random.RandomState(bs).randn(bs, 6).astype(np.float32)
            np.testing.assert_allclose(
                layer(paddle.to_tensor(x)).numpy(),
                m(paddle.to_tensor(x)).numpy(), rtol=1e-5)

    def test_jit_save_polymorphic_shared_batch_two_inputs(self, tmp_path):
        # two inputs whose batch dims must be EQUAL (a + b): independent
        # symbols can't be related, so export retries with per-axis
        # shared symbols
        from paddle_tpu.static import InputSpec

        class TwoIn(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 2)

            def forward(self, a, b):
                return self.fc(a + b)

        paddle.seed(2)
        m = TwoIn()
        paddle.jit.save(m, str(tmp_path / "two"),
                        input_spec=[InputSpec([None, 4], "float32"),
                                    InputSpec([None, 4], "float32")])
        layer = paddle.jit.load(str(tmp_path / "two"))
        for bs in (2, 6):
            a = np.random.RandomState(bs).randn(bs, 4).astype(np.float32)
            np.testing.assert_allclose(
                layer(paddle.to_tensor(a), paddle.to_tensor(a)).numpy(),
                m(paddle.to_tensor(a), paddle.to_tensor(a)).numpy(),
                rtol=1e-5)


class TestToStaticParamMutation:
    def test_param_mutation_survives_grad_path(self):
        """A traced forward that rewrites a parameter must have the update
        applied on BOTH call paths — the no-grad one and the tape-enabled
        one used during training (advisor r4: the grad path silently
        dropped it)."""

        class EmaLayer(nn.Layer):
            def __init__(self):
                super().__init__()
                self.lin = nn.Linear(4, 4)
                self.ema = self.create_parameter(
                    [4, 4], default_initializer=nn.initializer.Constant(0.0))

            def forward(self, x):
                # parameter rewritten inside the forward (EMA-style)
                self.ema.set_value(self.ema * 0.5 + self.lin.weight * 0.5)
                return self.lin(x).sum()

        paddle.seed(0)
        m = EmaLayer()
        sm = paddle.jit.to_static(m)
        x = paddle.randn([2, 4])

        # tape enabled + a differentiable input → the grad-aware path
        loss = sm(x)
        after_one = m.ema.numpy().copy()
        assert np.abs(after_one).max() > 1e-6, \
            "param mutation dropped on the grad-aware to_static path"
        expect = after_one * 0.5 + m.lin.weight.numpy() * 0.5
        loss2 = sm(x)
        np.testing.assert_allclose(m.ema.numpy(), expect, rtol=1e-5)
        # grads still flow to the ordinary parameters
        loss2.backward()
        assert m.lin.weight.grad is not None

    def test_untouched_params_not_churned(self):
        """States the forward does not touch keep their exact arrays on
        the grad path (the writeback is trace-time mutation-gated)."""
        lin = nn.Linear(4, 2)
        sm = paddle.jit.to_static(lin)
        w_arr = lin.weight._data
        sm(paddle.randn([3, 4]))
        assert lin.weight._data is w_arr

    def test_optimizer_over_param_subset(self):
        """TrainStep with an optimizer managing only SOME trainable params
        must still build (review r5: the sharding-constraint pass did an
        unguarded accumulator lookup)."""
        class TwoPart(nn.Layer):
            def __init__(self):
                super().__init__()
                self.a = nn.Linear(4, 4)
                self.b = nn.Linear(4, 1)

            def forward(self, x):
                return self.b(self.a(x))

        paddle.seed(0)
        m = TwoPart()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=m.b.parameters())
        step = paddle.jit.TrainStep(
            m, lambda net, x, y: ((net(x) - y) ** 2).mean(), opt)
        x = paddle.randn([8, 4]); y = paddle.randn([8, 1])
        l1 = float(step(x, y)); l2 = float(step(x, y))
        assert np.isfinite(l1) and np.isfinite(l2)


class TestManyRngDelta:
    def test_rng_free_steps_bitwise_and_dropout_statistical(self):
        """Quantify many()'s documented RNG contract (VERDICT r4 item 8):
        RNG-free steps match sequential BITWISE; with dropout, the K keys
        come from ONE split of the stream, so masks differ from the K
        sequential draws — but the realized drop RATE and the resulting
        training trajectory stay statistically equivalent."""
        rng = np.random.RandomState(3)
        batches = [(paddle.to_tensor(rng.rand(64, 8).astype(np.float32)),
                    paddle.to_tensor(rng.rand(64, 1).astype(np.float32)))
                   for _ in range(4)]

        def build(with_dropout):
            paddle.seed(123)
            layers = [nn.Linear(8, 32)]
            if with_dropout:
                layers.append(nn.Dropout(0.5))
            layers += [nn.ReLU(), nn.Linear(32, 1)]
            m = nn.Sequential(*layers)
            m.train()
            o = paddle.optimizer.SGD(learning_rate=0.05,
                                     parameters=m.parameters())
            return m, o

        # RNG-free: bitwise identical params after K steps
        m1, o1 = build(False)
        s1 = paddle.jit.TrainStep(m1, lambda n, x, y: F.mse_loss(n(x), y),
                                  o1)
        for b in batches:
            s1(*b)
        m2, o2 = build(False)
        s2 = paddle.jit.TrainStep(m2, lambda n, x, y: F.mse_loss(n(x), y),
                                  o2)
        s2.many(batches)
        np.testing.assert_array_equal(m1[0].weight.numpy(),
                                      m2[0].weight.numpy())

        # dropout: per-step losses DIFFER (different masks)...
        m3, o3 = build(True)
        s3 = paddle.jit.TrainStep(m3, lambda n, x, y: F.mse_loss(n(x), y),
                                  o3)
        seq_losses = np.array([float(s3(*b)) for b in batches])
        m4, o4 = build(True)
        s4 = paddle.jit.TrainStep(m4, lambda n, x, y: F.mse_loss(n(x), y),
                                  o4)
        many_losses = s4.many(batches).numpy()
        assert not np.allclose(seq_losses, many_losses, rtol=1e-6), \
            "masks should differ (documented: statistical, not bitwise)"
        # ...but the trajectories stay in the same band (same loss scale,
        # same descent) and the final params are close in distribution
        assert abs(seq_losses.mean() - many_losses.mean()) \
            < 0.5 * seq_losses.mean() + 0.05
        w1, w2 = m3[0].weight.numpy(), m4[0].weight.numpy()
        assert abs(w1.std() - w2.std()) < 0.1 * max(w1.std(), w2.std())


class TestSaveEarlyExit:
    def test_jit_save_load_early_exit_decode(self, tmp_path):
        """r5: jit.save must export the dy2static-CONVERTED forward —
        an early-exit decode serializes to StableHLO and round-trips."""
        class Dec(nn.Layer):
            def __init__(self):
                super().__init__()
                self.lin = nn.Linear(4, 4)

            def forward(self, h):
                n = 0
                while n < 8:
                    h = self.lin(h)
                    if paddle.max(paddle.abs(h)) < 0.05:
                        return h * 0.0
                    n = n + 1
                return h

        paddle.seed(0)
        m = Dec()
        m.eval()
        # ref from the EAGER forward (concrete control flow is exact);
        # m stays unwrapped so jit.save itself must do the conversion
        x = paddle.to_tensor(np.ones((1, 4), np.float32))
        ref = m(x).numpy()
        path = str(tmp_path / "dec")
        paddle.jit.save(m, path, input_spec=[paddle.jit.InputSpec([1, 4])])
        # the export shadow is fully removed afterwards
        assert "forward" not in m.__dict__
        loaded = paddle.jit.load(path)
        np.testing.assert_allclose(loaded(x).numpy(), ref, rtol=1e-5)

    def test_save_restores_instance_forward(self, tmp_path):
        """A pre-existing instance-level forward survives jit.save
        (review r5: the shadow cleanup used to delete it)."""
        import types

        lin = nn.Linear(4, 2)

        def custom_fwd(self, x):
            return lin.__class__.forward(self, x) + 1.0

        lin.eval()
        inst = types.MethodType(custom_fwd, lin)
        object.__setattr__(lin, "forward", inst)
        x = paddle.randn([3, 4])
        before = lin(x).numpy()
        paddle.jit.save(lin, str(tmp_path / "m"),
                        input_spec=[paddle.jit.InputSpec([3, 4])])
        assert lin.__dict__.get("forward") is inst
        np.testing.assert_allclose(lin(x).numpy(), before, rtol=1e-6)
