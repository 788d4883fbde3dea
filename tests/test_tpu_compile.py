"""The main path's kernels, compiled at real widths for a described v5e.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not attached (on-chip-measurement guide, section 2).
A compile that passes is not a chip run — nothing executes — but it is
where interpret-mode tests are blind: block shapes the chip's tiling
refuses, and more scoped VMEM than a kernel may use.  Each case below was
refused, or compiled only by luck, before PR 21.

All of these live in this ONE file and describe the topology inside a
module-scoped fixture: only one process may load the TPU's library, so it
must never be loaded while a module is imported, and never by two xdist
workers.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

QH, KH, D, BLOCK = 32, 8, 128, 16          # LLaMA-3-8B attention widths
LANES, TABLE_BLOCKS, POOL_BLOCKS = 8, 128, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("heads,lanes,table,s,pool_dtype,geometry", [
    ((QH, KH), LANES, TABLE_BLOCKS, 1, jnp.bfloat16, ("stream", 8)),
    ((QH, KH), LANES, TABLE_BLOCKS, 5, jnp.bfloat16, ("stream", 8)),
    ((QH, KH), LANES, TABLE_BLOCKS, 256, jnp.bfloat16, ("tile", 1)),
    ((QH, KH), LANES, TABLE_BLOCKS, 1024, jnp.bfloat16, ("tile", 1)),
    ((QH, KH), LANES, TABLE_BLOCKS, 1, jnp.int8, ("tile", 1)),
    # the serving cell's decode programs
    ((QH, KH), 32, 64, 1, jnp.bfloat16, ("stream", 8)),
    ((QH, KH), 32, 128, 1, jnp.bfloat16, ("stream", 8)),
    # the widest window that streams at these widths, and the next
    ((QH, KH), LANES, TABLE_BLOCKS, 9, jnp.bfloat16, ("stream", 8)),
    ((QH, KH), LANES, TABLE_BLOCKS, 16, jnp.bfloat16, ("tile", 1)),
    # an MHA pool (the 7B presets: as many kv heads as query heads), where
    # a chunk's columns are four times as many: decode streams half the
    # blocks a chunk, the short prefill buckets tile
    ((32, 32), LANES, TABLE_BLOCKS, 1, jnp.bfloat16, ("stream", 4)),
    ((32, 32), LANES, TABLE_BLOCKS, 64, jnp.bfloat16, ("tile", 1)),
    ((32, 32), LANES, TABLE_BLOCKS, 128, jnp.bfloat16, ("tile", 1)),
    # a tp=4 shard's heads: few columns a chunk, so long windows stream
    ((8, 2), LANES, TABLE_BLOCKS, 128, jnp.bfloat16, ("stream", 8))])
def test_paged_attention(one_chip, heads, lanes, table, s, pool_dtype,
                         geometry):
    """Decode, verify windows, prefill buckets — and the int8 pool,
    whose (1, 16) scale blocks the chip's tiling refused.  A window
    whose working set fits streams a lane's live blocks (manual copies
    out of HBM) on a bf16 pool; the others and the int8 pool take the
    tile geometry.  Each case names the geometry it must get."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving.paged_attention import _pallas_paged_attention

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def traces():
        path, blocks = geometry
        return metrics.value("paged_attn.trace", path=path,
                             blocks_per_cell=blocks)

    qh, kh = heads
    pool = sds((POOL_BLOCKS, BLOCK, kh, D), pool_dtype)
    args = [sds((lanes, s, qh, D), jnp.bfloat16), pool, pool,
            sds((lanes, table), jnp.int32), sds((lanes,), jnp.int32)]
    if pool_dtype == jnp.int8:
        scales = sds((POOL_BLOCKS, BLOCK), jnp.float32)
        args += [scales, scales]
    before = traces()
    compiled = _compile(
        functools.partial(_pallas_paged_attention, interpret=False), *args)
    assert _has_kernel(compiled)
    assert traces() == before + 1


def test_flash_attention_fwd_bwd(one_chip):
    from paddle_tpu.ops.pallas.flash import flash_attention

    q = jax.ShapeDtypeStruct((1, 4096, QH, D), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, KH, D), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return (out.astype(jnp.float32) ** 2).sum()

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        q, kv, kv)
    assert compiled.as_text().count("tpu_custom_call") >= 3  # fwd, dq, dkv


@pytest.mark.parametrize("norm", ["rms_norm", "layer_norm"])
def test_norm_fwd_bwd_at_hidden_4096(one_chip, norm):
    """A fixed 256-row block needed 18 MiB of scoped VMEM in the backward
    at hidden 4096; the block is sized from the hidden width now."""
    from paddle_tpu.ops.pallas import norms

    x = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=one_chip)
    if norm == "rms_norm":
        fn, args = (lambda x, w: norms.rms_norm(x, w, 1e-6, False)), (x, w)
    else:
        fn = lambda x, w, b: norms.layer_norm(x, w, b, 1e-5, False)
        args = (x, w, w)

    def loss(*a):
        return (fn(*a).astype(jnp.float32) ** 2).sum()

    compiled = _compile(
        jax.value_and_grad(loss, argnums=tuple(range(len(args)))), *args)
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_group_norm_fwd_bwd_unet_shape(one_chip):
    """[2, 320, 64, 64] with 32 groups chose a (4, 40960) block: 4 rows
    are neither a multiple of 8 nor the whole array."""
    from paddle_tpu.ops.pallas import norms

    shape = (2, 320, 64, 64)
    assert norms.group_norm_supported(shape, 32)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((320,), jnp.float32, sharding=one_chip)

    def loss(x, w, b):
        out = norms.group_norm(x, w, b, 32, 1e-5, False)
        return (out.astype(jnp.float32) ** 2).sum()

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        x, w, w)
    assert _has_kernel(compiled)


def test_flash_attention_in_a_sharded_step(topo):
    """GSPMD refuses a Mosaic kernel ("cannot be automatically
    partitioned"): under a mesh the attention router runs it per shard.
    Compiled for all four described chips, dp2 x mp2, forward and
    backward, with no collective around the kernel."""
    from paddle_tpu.ops.flash_attention import _per_shard
    from paddle_tpu.ops.pallas.flash import flash_attention

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    sharding = NamedSharding(mesh, P("dp", None, "mp", None))
    q = jax.ShapeDtypeStruct((4, 1024, QH, D), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((4, 1024, KH, D), jnp.bfloat16,
                              sharding=sharding)
    kernel = functools.partial(flash_attention, causal=True,
                               interpret=False)

    def loss(q, k, v):
        return (_per_shard(kernel, q, k, v).astype(jnp.float32) ** 2).sum()

    with jax.set_mesh(mesh):
        compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "all-gather" not in text and "all-to-all" not in text
    assert all(s.spec == P("dp", None, "mp", None)
               for s in compiled.output_shardings)


@pytest.mark.parametrize("lanes,vocab", [(128, 102400), (32, 32768)])
def test_sampler_at_the_serve_cells_shapes(one_chip, lanes, vocab):
    """The two serve cells' sampler, compiled whole: the chip's compiler
    finds no sort in it (the full-vocabulary sort was 7-10 s of every
    serving program's compile) and both threshold searches as loops."""
    from paddle_tpu.serving.sampling import sample_batch

    row = lambda dtype: jax.ShapeDtypeStruct((lanes,), dtype,
                                             sharding=one_chip)
    logits = jax.ShapeDtypeStruct((lanes, vocab), jnp.bfloat16,
                                  sharding=one_chip)
    text = _compile(sample_batch, logits, row(jnp.uint32), row(jnp.int32),
                    row(jnp.float32), row(jnp.int32),
                    row(jnp.float32)).as_text()
    assert " sort(" not in text
    assert text.count(" while(") >= 2
