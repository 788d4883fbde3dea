"""chip_smoke.py, rehearsed without the chip.

The script itself refuses to run on a CPU (the no-fallback rule, tested
here); its phase functions are imported and run at tiny widths with the
Pallas kernels in interpret mode, so that wrong paths, arguments and
control flow are found before any chip time is spent.  Also here: the
import-touches-no-backend rule that lets one process own the chip, and
the compile-cache helper's placement rules.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.models import GPTConfig  # noqa: E402

TINY = GPTConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, max_position_embeddings=128)


def _run(args, env_extra, cwd=_ROOT):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(env_extra)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


class TestNoFallback:
    @pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
    def test_cpu_run_fails_and_says_so(self, argv):
        r = _run([os.path.join(_ROOT, "chip_smoke.py")] + argv,
                 {"JAX_PLATFORMS": "cpu"})
        assert r.returncode != 0
        last = json.loads(r.stdout.strip().splitlines()[-1])
        assert last["ok"] is False
        assert last["device"]["platform"] == "cpu"
        # no phase ran: the device check comes first
        assert '"phase"' not in r.stdout

    def test_set_device_never_hands_back_another_platform(self):
        import paddle_tpu as paddle
        from paddle_tpu.core import device

        with pytest.raises(ValueError, match="0 'tpu' device"):
            device.set_device("tpu")
        with pytest.raises(ValueError, match="'cpu' device"):
            device.set_device("cpu:99")
        # the reference's accelerator names map to the default platform,
        # loudly
        with pytest.warns(UserWarning, match="default platform 'cpu'"):
            assert device.set_device("gpu:0").platform == "cpu"
        assert paddle.device.get_device().startswith("cpu")


class TestOneProcessPerChip:
    def test_imports_initialize_no_backend(self):
        """A parent that only imports the package (the launcher, a
        DataLoader worker, a benchmark driver) must leave the chip to its
        child."""
        code = (
            "import paddle_tpu, paddle_tpu.serving, paddle_tpu.jit\n"
            "import paddle_tpu.distributed.launch, paddle_tpu.io\n"
            "import paddle_tpu.inference, paddle_tpu.serving.gateway\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "import paddle_tpu as paddle\n"
            "paddle.seed(7)\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "paddle.rand([2])\n"
            "assert xla_bridge.backends_are_initialized()\n")
        r = _run(["-c", code], {"JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-2000:]

    def test_launcher_watcher_runs_a_device_script(self, tmp_path):
        """`launch --log_dir` starts the script as a child that uses the
        device (the chip, on the chip machine: chip run of PR 21)."""
        probe = os.path.join(_ROOT, "tests", "companions",
                             "launch_device_probe.py")
        r = _run(["-m", "paddle_tpu.distributed.launch", "--log_dir",
                  str(tmp_path / "logs"), probe, "cpu"],
                 {"JAX_PLATFORMS": "cpu",
                  "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
        assert r.returncode == 0, r.stderr[-2000:]
        log = (tmp_path / "logs" / "workerlog.0.0").read_text()
        assert "LAUNCH_PROBE ok platform=cpu" in log

    def test_lazy_key_keeps_the_seeded_stream(self):
        """The default stream and a seeded one are bitwise what an eager
        PRNGKey(seed) + fold_in(counter) gives."""
        from paddle_tpu.core import random as rs

        stream = rs._KeyStream(5)
        assert stream._base is None               # nothing built yet
        want = [jax.random.fold_in(jax.random.PRNGKey(5), i)
                for i in range(3)]
        got = [stream.next_key() for _ in range(3)]
        for a, b in zip(got, want):
            np.testing.assert_array_equal(jax.random.key_data(a),
                                          jax.random.key_data(b))
        # first touched inside a trace: the base stays concrete
        fresh = rs._KeyStream(9)
        jax.jit(lambda: jax.random.key_data(fresh.next_key()))()
        assert not isinstance(fresh.base, jax.core.Tracer)

    def test_dataloader_workers_never_initialize_a_backend(self,
                                                           monkeypatch):
        """Spawned workers re-import the package.  With a platform name
        that does not exist in their environment, any backend
        initialization in a worker would raise and fail the batch."""
        from paddle_tpu.io import DataLoader

        jnp.zeros(()).block_until_ready()         # this process: cpu, up
        monkeypatch.setenv("JAX_PLATFORMS", "no_such_platform")
        loader = DataLoader(chip_smoke._Tokens(4, 8, 50, 0), batch_size=2,
                            num_workers=2, use_process_workers=True)
        batches = list(loader)
        assert len(batches) == 2
        ids, labels = batches[0]
        assert tuple(ids.shape) == (2, 8) and tuple(labels.shape) == (2, 8)


class TestCompileCache:
    def test_env_dir_wins_and_nothing_is_set_in_code(self, monkeypatch,
                                                     tmp_path):
        from paddle_tpu.utils import compile_cache

        before = jax.config.jax_compilation_cache_dir
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        try:
            assert compile_cache.enable() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        finally:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", floor)

    def test_fixed_path_from_any_working_directory(self, monkeypatch,
                                                   tmp_path):
        from paddle_tpu.utils import compile_cache

        before = jax.config.jax_compilation_cache_dir
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            monkeypatch.chdir(tmp_path)
            first = compile_cache.enable()
            monkeypatch.chdir(_ROOT)
            second = compile_cache.enable()
            assert first == second == os.path.join(_ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
            assert compile_cache.entry_count(str(tmp_path / "none")) == 0
        finally:
            # tier-1 stays cache-free
            jax.config.update("jax_compilation_cache_dir", before)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", floor)


class TestPhasesOnCpu:
    """Rehearsal 1 of the on-chip-measurement guide, kept as tests."""

    def test_trainer_phase(self):
        cfg = dataclasses.replace(TINY, fused_lm_loss=True)
        row = chip_smoke.trainer_phase(cfg, batch=2, seq=32, steps=3,
                                       lr=1e-2, tol=1e-3, dtype="float32")
        assert row["ok"], row
        assert row["tokens_per_step"] == 64 and len(row["losses"]) == 3
        # single-device TrainStep: the AUTO-layout path engages
        assert row["auto_layout_engaged"] is True

    def test_server_phase(self):
        row = chip_smoke.server_phase(
            TINY, num_slots=4, max_seq_len=128, pool_blocks=64,
            prompt_lens=(5, 20, 23, 70), new_tokens=6, logit_tol=1e-3,
            dtype="float32", timeout=240.0)
        assert row["ok"], row
        # on one compiler and in f32 nothing forks: every stream of the
        # concurrent pass and of the three lone repeats is generate()'s
        assert row["streams"] == 7 and not any(row["forks"].values())
        assert row["streams_bitwise_equal_generate"] == 7
        assert row["finish_reasons"] == ["length"]
        assert row["kv_blocks_in_use_after_drain"] == 0

    def test_a_fork_passes_only_on_a_near_tie(self):
        """`_judge_stream`: a stream may leave generate()'s where the
        reference logits cannot tell the two tokens apart, and only
        there."""
        from paddle_tpu.serving import SamplingParams

        ref = np.zeros(16, np.float32)
        ref[[3, 5, 7]] = 4.0, 3.9, 1.0
        greedy = SamplingParams(max_new_tokens=4)
        top2 = SamplingParams(max_new_tokens=4, temperature=1.0, top_k=2)

        def judge(got, sp, tol=0.25):
            return chip_smoke._judge_stream(got, [1, 2, 3, 9], [0], sp,
                                            lambda seq: ref, tol)

        assert judge([1, 2, 3, 9], greedy) is None
        near = judge([1, 2, 5, 0], greedy)            # 3.9 against 4.0
        assert near["first_diff"] == 2 and near["benign"]
        assert not judge([1, 2, 5, 0], greedy, tol=0.05)["benign"]
        assert not judge([1, 2, 7, 0], greedy)["benign"]
        assert judge([1, 2, 5, 0], top2)["benign"]    # both in the top 2
        assert not judge([1, 2, 7, 0], top2)["benign"]
        assert not judge([1, 2, 3], greedy)["benign"]  # a short stream

    def test_kernel_phase_interpreted(self):
        row = chip_smoke.kernel_phase(
            (4, 2, 8, 4), (1, 3, 16), (40, 64), (2, 32, 4, 4), tol=2e-2,
            lanes=2, nb=8, interpret=True)
        assert row["ok"], row
        assert row["compiled"] is False
        assert {"paged_attention_s16", "paged_attention_int8_s1",
                "rms_norm_dx", "layer_norm_db", "group_norm_dw",
                "kernel_checks.flash_fwd_causal1"} <= set(row["errors"])

    def test_tp_phase_on_virtual_devices(self):
        """Rehearsal 2: the tensor-parallel path on virtual CPU devices."""
        row = chip_smoke.tp_phase(
            TINY, 2, num_slots=4, max_seq_len=128, pool_blocks=64,
            prompt_lens=(5, 20, 70), new_tokens=6, logit_tol=1e-3,
            dtype="float32")
        assert row["streams_bitwise_equal_single_chip"] == 3, row
        assert row["checks"]["no_blocks_in_use"], row
        assert row["mesh"] == {"dp": 1, "tp": 2}

    def test_mesh_train_phase_on_virtual_devices(self):
        cfg = dataclasses.replace(TINY, fused_lm_loss=True)
        row = chip_smoke.mesh_train_phase(cfg, batch=4, seq=32, steps=3,
                                          lr=1e-2, tol=1e-3,
                                          dtype="float32")
        assert row["checks"]["losses_vs_single_chip"], row
        assert row["checks"]["loss_falls"], row
        assert row["checks"]["auto_layout_off_for_sharded_state"], row


def test_per_shard_flash_under_a_mesh():
    """The Mosaic kernel cannot be partitioned by GSPMD; under a mesh the
    attention router runs it per shard.  Interpreted here, on 2 x 2
    virtual devices, against the XLA attention on one device."""
    import functools

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.flash_attention import _per_shard, _xla_flash
    from paddle_tpu.ops.pallas.flash import flash_attention

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(4, 128, 8, 32), jnp.float32)
    k = jnp.asarray(rng.randn(4, 128, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(4, 128, 2, 32), jnp.float32)
    kernel = functools.partial(flash_attention, causal=True, interpret=True)

    def loss(fn, *a):
        return (fn(*a) ** 2).sum()

    want = jax.value_and_grad(
        lambda *a: loss(lambda q, k, v: _xla_flash(q, k, v, True, None),
                        *a), argnums=(0, 1, 2))(q, k, v)
    # outside a mesh the kernel is called as it is
    np.testing.assert_allclose(_per_shard(kernel, q, k, v),
                               _xla_flash(q, k, v, True, None), atol=2e-5)
    sharding = NamedSharding(mesh, P("dp", None, "mp", None))
    with jax.set_mesh(mesh):
        got = jax.jit(jax.value_and_grad(
            lambda *a: loss(functools.partial(_per_shard, kernel), *a),
            argnums=(0, 1, 2)))(*(jax.device_put(x, sharding)
                                  for x in (q, k, v)))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert g.sharding.spec == P("dp", None, "mp", None)
        np.testing.assert_allclose(g, w, atol=2e-4)
