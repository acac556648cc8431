"""The serving path's Pallas kernels compile for a TPU v5e at qwen3-1.7b widths.

Nothing runs here: each test lowers a kernel for a *described* v5e chip
(no accelerator attached) and asserts the compiled program holds a Mosaic
``tpu_custom_call``, i.e. the kernel was not refused and did not fall back
to the interpreter. Widths are the published qwen3-1.7b ones (28 layers,
8 KV heads, head_dim 128, 32-token blocks, bf16) with a 256-block pool.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and a test run spreads
files over several workers that all import this module.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.transfer import ShardSpec, head_plane_view
from repro.kernels.kv_gather import kv_append_tokens, kv_transfer
from repro.kernels.paged_attention import paged_decode_attention

CFG = get_config("qwen3-1.7b")
L, KV, HD, BS = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim, CFG.block_size
NB = 256                 # pool blocks (8,192 tokens)
POOL = (NB, L, 2, KV, BS, HD)   # FLOWKV pool, pages stored kv-head-major
B, W = 4, 128            # decode batch and block-table width


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_kv_transfer_flowkv_pool_compiles(one_chip):
    pool = _spec(POOL, jnp.bfloat16, one_chip)
    pages = _spec((3 * L * 2,), jnp.int32, one_chip)
    _assert_mosaic(
        lambda s, d, sp, dp: kv_transfer(s, d, sp, dp, interpret=False),
        pool, pool, pages, pages)


def test_kv_append_tokens_compiles(one_chip):
    _assert_mosaic(
        lambda pool, bt, pos, k, v: kv_append_tokens(
            pool, bt, pos, k, v, interpret=False),
        _spec(POOL, jnp.bfloat16, one_chip),
        _spec((B, W), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        _spec((L, B, KV, HD), jnp.bfloat16, one_chip),
        _spec((L, B, KV, HD), jnp.bfloat16, one_chip))


def test_paged_decode_attention_stats_compiles(one_chip):
    _assert_mosaic(
        lambda q, pool, bt, ln: paged_decode_attention(
            q, pool, L - 1, bt, ln, interpret=False, return_stats=True),
        _spec((B, CFG.num_heads, HD), jnp.bfloat16, one_chip),
        _spec(POOL, jnp.bfloat16, one_chip),
        _spec((B, W), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip))


def test_sharded_fine_row_transfer_compiles(one_chip):
    # tp=2 shard pools moved through the head-plane view, as
    # ShardedTransferEngine.execute lowers a cross-degree plan
    shard = ShardSpec(2, KV)
    pool = _spec((NB, L, 2, shard.heads_per_shard, BS, HD), jnp.bfloat16,
                 one_chip)
    planes = _spec((3 * L * 2 * shard.heads_per_shard,), jnp.int32, one_chip)
    _assert_mosaic(
        lambda s, d, sr, dr: kv_transfer(
            head_plane_view(s), head_plane_view(d), sr, dr,
            interpret=False).reshape(d.shape),
        pool, pool, planes, planes)
