"""KV layout transform (paper Eq. 5) and page read/write."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import layout as L


def _spec(layout):
    return L.KVCacheSpec(num_layers=4, num_blocks=10, block_size=4,
                         num_kv_heads=2, head_dim=8, dtype=jnp.float32,
                         layout=layout)


def test_layout_shapes_and_counts():
    fk = _spec(L.KVLayout.FLOWKV)
    vl = _spec(L.KVLayout.VLLM)
    # pages stored kv-head-major (KV, bs, hd); payload is still one page
    assert fk.shape == (10, 4, 2, 2, 4, 8)
    assert vl.shape == (4, 2, 10, 2, 4, 8)
    assert fk.page_shape == vl.page_shape == (2, 4, 8)
    assert fk.payload == vl.payload == 64
    assert fk.transfer_calls_per_block() == 1
    assert vl.transfer_calls_per_block() == 8        # L*2, the paper's factor
    assert fk.bytes_per_block == vl.bytes_per_block


def test_transform_roundtrip():
    vl = _spec(L.KVLayout.VLLM)
    x = jnp.arange(np.prod(vl.shape), dtype=jnp.float32).reshape(vl.shape)
    y = L.vllm_to_flowkv(x)
    assert y.shape == _spec(L.KVLayout.FLOWKV).shape
    np.testing.assert_array_equal(np.asarray(L.flowkv_to_vllm(y)), np.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(L.convert(x, L.KVLayout.VLLM, L.KVLayout.VLLM)), np.asarray(x))


@pytest.mark.parametrize("layout", [L.KVLayout.FLOWKV, L.KVLayout.VLLM])
def test_write_read_block(layout):
    spec = _spec(layout)
    cache = L.alloc_cache(spec)
    rng = np.random.RandomState(0)
    k = jnp.asarray(rng.randn(4, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(4, 2, 8), jnp.float32)
    cache = L.write_block(cache, spec, 3, 2, k, v)
    k2, v2 = L.read_block(cache, spec, 3, 2)
    np.testing.assert_allclose(np.asarray(k2), np.asarray(k))
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v))
    # stored kv-head-major: head h's plane holds that head's slots in order
    page = cache[3, 2, 0] if layout is L.KVLayout.FLOWKV else cache[2, 0, 3]
    np.testing.assert_array_equal(np.asarray(page[1]), np.asarray(k[:, 1]))


@pytest.mark.parametrize("layout", [L.KVLayout.FLOWKV, L.KVLayout.VLLM])
def test_gather_scatter_blocks(layout):
    spec = _spec(layout)
    rng = np.random.RandomState(1)
    cache = jnp.asarray(rng.randn(*spec.shape), jnp.float32)
    ids = [7, 2, 5]
    payload = L.gather_blocks(cache, spec, ids)
    assert payload.shape == (3, 4, 2, 2, 4, 8)
    dst = L.alloc_cache(spec)
    dst = L.scatter_blocks(dst, spec, [1, 3, 9], payload)
    p2 = L.gather_blocks(dst, spec, [1, 3, 9])
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(payload))
