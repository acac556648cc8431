"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill import flash_prefill, flash_prefill_op, flash_prefill_ref
from repro.kernels.kv_gather import kv_gather, kv_gather_op, kv_gather_ref, kv_scatter_op
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_attention_op,
                                           paged_decode_attention_ref)

SWEEP_PAGED = [
    # (B, H, KV, HD, BS, MAXB, dtype)
    (1, 4, 4, 16, 4, 3, jnp.float32),
    (3, 8, 4, 32, 8, 5, jnp.float32),
    (2, 8, 2, 64, 16, 4, jnp.float32),
    (2, 4, 1, 32, 8, 6, jnp.float32),        # MQA
    (2, 8, 4, 32, 8, 4, jnp.bfloat16),
]


@pytest.mark.parametrize("b,h,kv,hd,bs,maxb,dtype", SWEEP_PAGED)
def test_paged_attention_sweep(b, h, kv, hd, bs, maxb, dtype):
    key = jax.random.PRNGKey(0)
    nb, L, layer = b * maxb + 4, 3, 1
    q = jax.random.normal(key, (b, h, hd), dtype)
    pool = jax.random.normal(jax.random.PRNGKey(1), (nb, L, 2, kv, bs, hd), dtype)
    bt = jax.random.permutation(jax.random.PRNGKey(2), nb)[:b * maxb]
    bt = bt.reshape(b, maxb).astype(jnp.int32)
    lengths = jax.random.randint(jax.random.PRNGKey(3), (b,), 1, maxb * bs + 1)
    out = paged_decode_attention(q, pool, layer, bt, lengths)
    ref = paged_decode_attention_ref(q, pool, layer, bt, lengths)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_paged_attention_op_layer_slice():
    """The kernel reads each layer's pages of the full FlowKV pool in place."""
    b, h, kv, hd, bs, maxb, L = 2, 4, 2, 16, 4, 3, 3
    nb = 16
    pool = jax.random.normal(jax.random.PRNGKey(0), (nb, L, 2, kv, bs, hd))
    q = jax.random.normal(jax.random.PRNGKey(1), (b, h, hd))
    bt = jnp.arange(b * maxb, dtype=jnp.int32).reshape(b, maxb)
    lengths = jnp.asarray([7, 12], jnp.int32)
    outs = []
    for layer in range(L):
        out = paged_decode_attention_op(q, pool, layer, bt, lengths)
        ref = paged_decode_attention_ref(q, pool, layer, bt, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        outs.append(np.asarray(out))
    assert not np.allclose(outs[0], outs[1])     # layers are told apart


# One tile-native shape: head_dim 128 and 16-token blocks, so a page's
# minor dims are whole (16, 128) bf16 tiles, as at the served widths.
NATIVE = dict(nb=8, L=2, kv=2, bs=16, hd=128)


@pytest.mark.parametrize("return_stats", [False, True])
def test_paged_attention_tile_native_shape(return_stats):
    from repro.kernels.paged_attention import paged_decode_attention_stats_ref
    nb, L, kv, bs, hd = (NATIVE[k] for k in ("nb", "L", "kv", "bs", "hd"))
    b, h, maxb = 2, 4, 3
    pool = jax.random.normal(jax.random.PRNGKey(0), (nb, L, 2, kv, bs, hd),
                             jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(1), (b, h, hd), jnp.bfloat16)
    bt = jnp.asarray([[5, 0, 3], [7, 2, 6]], jnp.int32)
    lengths = jnp.asarray([40, 17], jnp.int32)
    for layer in range(L):
        got = paged_decode_attention(q, pool, layer, bt, lengths,
                                     return_stats=return_stats)
        if return_stats:
            ref = paged_decode_attention_stats_ref(q, pool, layer, bt, lengths)
        else:
            got, ref = (got,), (paged_decode_attention_ref(
                q, pool, layer, bt, lengths),)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(r, np.float32),
                                       rtol=2e-2, atol=2e-2)


def test_kv_append_tile_native_shape():
    from repro.kernels.kv_gather import kv_append_ref, kv_append_tokens
    nb, L, kv, bs, hd = (NATIVE[k] for k in ("nb", "L", "kv", "bs", "hd"))
    pool = jax.random.normal(jax.random.PRNGKey(0), (nb, L, 2, kv, bs, hd),
                             jnp.bfloat16)
    # lane 2 pads the batch by replicating lane 0, as the engine does
    bt = jnp.asarray([[4, 1], [6, 3], [4, 1]], jnp.int32)
    pos = jnp.asarray([21, 15, 21], jnp.int32)
    k_new = jax.random.normal(jax.random.PRNGKey(1), (L, 3, kv, hd), jnp.bfloat16)
    v_new = jax.random.normal(jax.random.PRNGKey(2), (L, 3, kv, hd), jnp.bfloat16)
    k_new, v_new = k_new.at[:, 2].set(k_new[:, 0]), v_new.at[:, 2].set(v_new[:, 0])
    out = kv_append_tokens(pool, bt, pos, k_new, v_new)
    ref = kv_append_ref(pool, bt, pos, k_new, v_new)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))
    changed = np.argwhere(np.any(np.asarray(out != pool), axis=(3, 5)))
    assert {tuple(c) for c in changed} == {
        (blk, l, k, slot) for blk, slot in ((1, 5), (6, 15))
        for l in range(L) for k in range(2)}


def test_kv_transfer_tile_native_shape():
    from repro.kernels.kv_gather import kv_transfer, kv_transfer_ref
    nb, L, kv, bs, hd = (NATIVE[k] for k in ("nb", "L", "kv", "bs", "hd"))
    shape = (nb, L, 2, kv, bs, hd)
    src = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
    dst = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)
    sp = jnp.asarray([0, 13, 7, 31, 22], jnp.int32)
    dp = jnp.asarray([30, 2, 17, 9, 0], jnp.int32)
    out = kv_transfer(src, dst, sp, dp)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(kv_transfer_ref(src, dst, sp, dp), np.float32))
    pages = np.asarray(out, np.float32).reshape(-1, kv, bs, hd)
    np.testing.assert_array_equal(
        pages[[30, 2, 17, 9, 0]],
        np.asarray(src, np.float32).reshape(-1, kv, bs, hd)[[0, 13, 7, 31, 22]])


SWEEP_FLASH = [
    # (B, S, H, KV, HD, q_blk, k_blk, causal, dtype)
    (2, 64, 4, 2, 16, 16, 16, True, jnp.float32),
    (1, 128, 8, 8, 32, 32, 64, True, jnp.float32),     # MHA
    (2, 96, 4, 1, 16, 32, 32, True, jnp.float32),      # MQA, uneven blocks
    (2, 64, 4, 2, 16, 16, 16, False, jnp.float32),
    (2, 64, 4, 2, 32, 32, 32, True, jnp.bfloat16),
]


@pytest.mark.parametrize("b,s,h,kv,hd,qb,kb,causal,dtype", SWEEP_FLASH)
def test_flash_prefill_sweep(b, s, h, kv, hd, qb, kb, causal, dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, hd), dtype)
    out = flash_prefill(q, k, v, causal=causal, q_blk=qb, k_blk=kb)
    ref = flash_prefill_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_prefill_op_pads():
    b, s, h, kv, hd = 1, 50, 2, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, hd))
    out = flash_prefill_op(q, k, v, q_blk=16, k_blk=16)
    ref = flash_prefill_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [1, 5, 16])
def test_kv_gather_sweep(dtype, n):
    pool = jax.random.normal(jax.random.PRNGKey(0), (32, 3, 2, 2, 4, 8), dtype)
    ids = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, 32)
    out = kv_gather(pool, ids.astype(jnp.int32))
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(kv_gather_ref(pool, ids), np.float32))


def test_kv_gather_scatter_roundtrip():
    pool = jax.random.normal(jax.random.PRNGKey(0), (32, 2, 2, 1, 4, 4))
    ids = jnp.asarray([4, 9, 30], jnp.int32)
    staged = kv_gather_op(pool, ids)
    dst = jnp.zeros_like(pool)
    dst = kv_scatter_op(dst, jnp.asarray([0, 1, 2], jnp.int32), staged)
    np.testing.assert_array_equal(np.asarray(dst[:3]), np.asarray(staged))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [1, 5, 16])
def test_kv_scatter_sweep(dtype, n):
    from repro.kernels.kv_gather import kv_scatter, kv_scatter_ref
    pool = jax.random.normal(jax.random.PRNGKey(0), (32, 3, 2, 2, 4, 8), dtype)
    staging = jax.random.normal(jax.random.PRNGKey(1), (n, 3, 2, 2, 4, 8), dtype)
    ids = jax.random.permutation(jax.random.PRNGKey(2), 32)[:n].astype(jnp.int32)
    out = kv_scatter(pool, ids, staging)
    ref = kv_scatter_ref(pool, ids, staging)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


@pytest.mark.parametrize("layout", ["flowkv", "vllm"])
def test_kv_gather_scatter_roundtrip_layouts(layout):
    """gather∘scatter round-trips a request's blocks on both pool layouts."""
    from repro.core import layout as L
    from repro.kernels.kv_gather import kv_scatter
    spec = L.KVCacheSpec(num_layers=3, num_blocks=16, block_size=4,
                         num_kv_heads=2, head_dim=8, dtype=jnp.float32,
                         layout=L.KVLayout.FLOWKV if layout == "flowkv"
                         else L.KVLayout.VLLM)
    pool = jax.random.normal(jax.random.PRNGKey(0), spec.shape)
    # the staging kernels are block-major: view VLLM pools through the
    # layout transform on the way in and out
    bm = pool if layout == "flowkv" else L.vllm_to_flowkv(pool)
    src_ids = jnp.asarray([2, 7, 11], jnp.int32)
    dst_ids = jnp.asarray([0, 5, 9], jnp.int32)
    staged = kv_gather(bm, src_ids)
    landed = kv_scatter(jnp.zeros_like(bm), dst_ids, staged)
    if layout == "vllm":
        landed = L.flowkv_to_vllm(landed)
        for s, d in zip([2, 7, 11], [0, 5, 9]):
            np.testing.assert_array_equal(np.asarray(landed)[:, :, d],
                                          np.asarray(pool)[:, :, s])
    else:
        for s, d in zip([2, 7, 11], [0, 5, 9]):
            np.testing.assert_array_equal(np.asarray(landed)[d],
                                          np.asarray(pool)[s])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_transfer_matches_ref(dtype):
    from repro.kernels.kv_gather import kv_transfer, kv_transfer_ref
    src = jax.random.normal(jax.random.PRNGKey(0), (10, 2, 2, 2, 4, 4), dtype)
    dst = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 2, 2, 4, 4), dtype)
    sp = jnp.asarray([0, 13, 7, 39, 22], jnp.int32)   # flat page ids
    dp = jnp.asarray([31, 2, 17, 9, 0], jnp.int32)
    out = kv_transfer(src, dst, sp, dp)
    ref = kv_transfer_ref(src, dst, sp, dp)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))
    # untouched pages preserved
    flat = np.asarray(out, np.float32).reshape(-1, 32)
    dflat = np.asarray(dst, np.float32).reshape(-1, 32)
    untouched = [i for i in range(dflat.shape[0]) if i not in [31, 2, 17, 9, 0]]
    np.testing.assert_array_equal(flat[untouched], dflat[untouched])
