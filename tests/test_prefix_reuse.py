"""Prefix-cache reuse: a hit must SKIP work, not just change accounting.

Covers the shared-block data plane end to end:

* stable hashing — index digests are identical across interpreter hash
  seeds (checkpoint/restore and cross-process state stay meaningful);
* residency honesty — entries die with their backing blocks (every free
  path) and re-home to the decode node after the P->D transfer;
* refcounted sharing — donor/sharer free in either order without leaks,
  audited by ``BlockManager.check_invariants``;
* accounting — with a warm prefix of length L, prefill executes exactly
  ``prompt_len - L`` tokens (counter-verified) and the scheduler's progress
  never double-counts the cached prefix;
* token identity — outputs with reuse on (local hit AND remote fetch) are
  bit-identical to cold prefill, and a remote fetch is ONE fused
  descriptor-table dispatch.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.block_manager import BlockManager
from repro.core.scheduler.hybrid_scheduler import HybridScheduler
from repro.models import transformer as T
from repro.models.api import get_model
from repro.serving.cluster import PDCluster
from repro.serving.prefix_cache import PrefixCacheIndex, _block_hashes
from repro.serving.request import Request, SamplingParams
from repro.sim.hardware import TPU_V5E

# recompute must look expensive for the router to pick fetch-over-recompute
# on the smoke-scale model (the cost model is honest: at 2 layers the real
# break-even favors recompute, which is exactly what we DON'T want to test)
WEAK = dataclasses.replace(TPU_V5E, peak_flops=1e6)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("qwen3-1.7b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _shared_prompts(cfg, n_followers=2, prefix_len=64, seed=7):
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, cfg.vocab_size, size=prefix_len).tolist()
    donor = prefix + rng.randint(0, cfg.vocab_size, size=10).tolist()
    followers = [prefix + rng.randint(0, cfg.vocab_size, size=5 + 3 * i).tolist()
                 for i in range(n_followers)]
    return donor, followers


def _reference(cfg, params, prompts, steps):
    return {tuple(p): [int(x) for x in
                       T.greedy_generate(params, cfg, jnp.asarray([p], jnp.int32), steps)[0]]
            for p in prompts}


def _run_staggered(cfg, params, donor, followers, steps=5, **kw):
    """Donor first; followers submitted once the donor's KV is resident."""
    cluster = PDCluster(cfg, params, num_blocks=128, max_batch_tokens=4096, **kw)
    reqs = [Request(prompt_tokens=list(p), sampling=SamplingParams(max_new_tokens=steps))
            for p in [donor] + followers]
    cluster.submit(reqs[0])
    for _ in range(3):
        cluster.step()
    for r in reqs[1:]:
        cluster.submit(r)
    for _ in range(120):
        cluster.step()
        if len(cluster.finished) == len(reqs):
            break
    assert len(cluster.finished) == len(reqs)
    for e in cluster.engines.values():
        e.scheduler.bm.check_invariants()
    outs = {tuple(r.prompt_tokens): list(r.output_tokens) for r in cluster.finished}
    return cluster, reqs, outs


# ---------------------------------------------------------------------------
# stable hashing
# ---------------------------------------------------------------------------
def test_block_hashes_stable_across_hash_seeds():
    """Digests must not depend on the interpreter's hash salt — run the
    chain under two different PYTHONHASHSEEDs and compare."""
    snippet = (
        "from repro.serving.prefix_cache import _block_hashes;"
        "print([h.hex() for h in _block_hashes(list(range(40)), 8)])"
    )
    outs = []
    for seed in ("0", "424242"):
        env = dict(os.environ, PYTHONHASHSEED=seed,
                   PYTHONPATH=os.pathsep.join(
                       [os.path.join(os.path.dirname(__file__), "..", "src"),
                        os.environ.get("PYTHONPATH", "")]))
        outs.append(subprocess.run(
            [sys.executable, "-c", snippet], env=env, capture_output=True,
            text=True, check=True).stdout.strip())
    assert outs[0] == outs[1]
    assert outs[0] == repr([h.hex() for h in _block_hashes(list(range(40)), 8)])


def test_block_hashes_are_a_chain():
    """hash(i) covers the whole prefix, not just block i: the same block
    content at a different chain position must hash differently."""
    a = _block_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    b = _block_hashes([9, 9, 9, 9, 5, 6, 7, 8], 4)
    assert len(a) == len(b) == 2
    assert a[1] != b[1]          # same 2nd block, different prefix


# ---------------------------------------------------------------------------
# index residency
# ---------------------------------------------------------------------------
def test_index_lookup_blocks_and_invalidation():
    idx = PrefixCacheIndex(block_size=4)
    idx.insert(0, list(range(12)), block_ids=[7, 8, 9])
    m = idx.lookup(0, list(range(12)))
    assert (m.num_tokens, m.block_ids) == (12, [7, 8, 9])
    # freeing the middle block truncates the shareable chain at the break
    idx.invalidate_blocks(0, [8])
    m = idx.lookup(0, list(range(12)))
    assert (m.num_tokens, m.block_ids) == (4, [7])
    idx.evict_node(0)
    assert idx.lookup(0, list(range(12))).num_tokens == 0


def test_index_unbacked_entries_match_but_never_share():
    idx = PrefixCacheIndex(block_size=4)
    idx.insert(1, list(range(8)))                 # routing-signal only
    m = idx.lookup(1, list(range(8)))
    assert m.num_tokens == 8 and m.block_ids == []


def test_index_unbacked_insert_keeps_backed_invalidation():
    """A later unbacked insert of the same chain must not orphan the backed
    entry's block mapping — its invalidation path has to stay live."""
    idx = PrefixCacheIndex(block_size=4)
    idx.insert(0, list(range(8)), block_ids=[1, 2])
    idx.insert(0, list(range(8)))                 # routing-signal re-insert
    assert idx.lookup(0, list(range(8))).block_ids == [1, 2]
    idx.invalidate_blocks(0, [1, 2])
    assert idx.lookup(0, list(range(8))).num_tokens == 0


def test_index_reinsert_repoints_to_newest_copy():
    idx = PrefixCacheIndex(block_size=4)
    idx.insert(0, list(range(8)), block_ids=[1, 2])
    idx.insert(0, list(range(8)), block_ids=[5, 6])     # newer copy
    assert idx.lookup(0, list(range(8))).block_ids == [5, 6]
    idx.invalidate_blocks(0, [1, 2])                    # old copy dying is a no-op
    assert idx.lookup(0, list(range(8))).block_ids == [5, 6]


# ---------------------------------------------------------------------------
# refcounted sharing (BlockManager)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("allocator", ["flowkv", "freelist"])
@pytest.mark.parametrize("donor_first", [True, False])
def test_refcount_free_ordering(allocator, donor_first):
    freed = []
    bm = BlockManager(16, 4, allocator)
    bm.on_free = freed.extend
    donor = bm.allocate(1, 9)                             # 3 blocks
    bm.allocate(2, 13, prefix_blocks=donor[:2])           # share 2, +2 fresh
    bm.check_invariants()
    assert bm.refcount(donor[0]) == 2
    order = [1, 2] if donor_first else [2, 1]
    bm.free(order[0])
    bm.check_invariants()
    # shared blocks survive the first free regardless of order
    assert bm.block_alive(donor[0]) and bm.block_alive(donor[1])
    bm.free(order[1])
    bm.check_invariants()
    # refcount zero no longer frees: the blocks PARK in the LRU cache with
    # pages intact (revivable prefix hits) until capacity pressure
    assert freed == [] and bm.free_capacity == 16
    assert bm.block_alive(donor[0]) and bm.is_cached(donor[0])
    bm.reclaim_cache()
    bm.check_invariants()
    assert bm.num_free == 16 and sorted(freed) == sorted(set(freed))
    assert not bm.block_alive(donor[0])


def test_on_free_fires_only_at_physical_reclaim():
    freed = []
    bm = BlockManager(8, 4, "flowkv")
    bm.on_free = freed.extend
    a = bm.allocate(1, 8)                 # 2 blocks
    bm.allocate(2, 12, prefix_blocks=a)   # shares both, +1 fresh
    bm.free(1)
    assert freed == []                    # still held by request 2
    bm.free(2)
    assert freed == []                    # refcount zero -> parked, not freed
    assert all(bm.is_cached(b) for b in a)
    bm.reclaim_cache()                    # pressure: pages actually recycle
    assert sorted(freed) == sorted(set(freed)) and len(freed) == 3


# ---------------------------------------------------------------------------
# scheduler accounting (the double-count satellite)
# ---------------------------------------------------------------------------
def test_prefill_progress_no_double_count():
    """progress seeds at the cached length, so the engine must report only
    EXECUTED tokens; a full-prompt report overshoots the prompt."""
    bm = BlockManager(64, 32, "flowkv")
    sched = HybridScheduler(0, bm, max_batch_tokens=4096)
    donor_blocks = bm.allocate(99, 64)

    def resolve(req):
        req.num_cached_prefix_tokens = 64
        req.prefix_src_node = 0
        req.prefix_block_ids = donor_blocks
        return donor_blocks

    sched.resolve_prefix = resolve
    req = Request(prompt_tokens=list(range(80)), sampling=SamplingParams())
    sched.enqueue_prefill(req)
    decision = sched.schedule()
    # admission billed only the suffix against the token budget
    assert decision.prefill_chunks[req.request_id] == 80 - 64
    assert bm.refcount(donor_blocks[0]) == 2        # shared, not copied
    # suffix-only completion report finishes the request EXACTLY
    assert sched.prefill_progressed(req, 80 - 64)
    assert req.request_id not in sched._progress


def test_pending_remote_fetch_not_clobbered_at_admission():
    """A request whose remote-fetch plan hasn't executed yet (e.g. the
    destination pool was momentarily full) must WAIT — re-stamping it local
    at admission would silently abandon the priced plan."""
    bm = BlockManager(64, 32, "flowkv")
    sched = HybridScheduler(0, bm, max_batch_tokens=4096)
    sched.resolve_prefix = lambda req: []            # reuse plane wired
    req = Request(prompt_tokens=list(range(80)), sampling=SamplingParams())
    req.num_cached_prefix_tokens = 64
    req.prefix_src_node = 3                          # remote plan in flight
    req.prefix_block_ids = [10, 11]
    sched.enqueue_prefill(req)
    decision = sched.schedule()
    assert decision.prefill_batch == []              # waits for the fetch
    assert req.num_cached_prefix_tokens == 64        # plan intact
    bm.allocate(req.request_id, 64)                  # fetch lands the prefix
    decision = sched.schedule()
    assert decision.prefill_chunks[req.request_id] == 80 - 64


def test_restore_onto_used_cluster_resets_block_state(small_model, tmp_path):
    """Restoring a checkpoint onto a cluster that has since served traffic
    must drop the live tables/refcounts, not layer the snapshot on top."""
    from repro.serving.checkpoint import load_cluster, save_cluster

    cfg, params = small_model
    donor, _ = _shared_prompts(cfg, n_followers=0)
    cluster = PDCluster(cfg, params, num_prefill=1, num_decode=1, num_blocks=128)
    r1 = Request(prompt_tokens=list(donor),
                 sampling=SamplingParams(max_new_tokens=12))
    cluster.submit(r1)
    for _ in range(3):
        cluster.step()
    save_cluster(cluster, str(tmp_path / "ckpt"))
    # serve more traffic so the live block state diverges from the snapshot
    r2 = Request(prompt_tokens=list(donor[:40]),
                 sampling=SamplingParams(max_new_tokens=12))
    cluster.submit(r2)
    for _ in range(3):
        cluster.step()
    load_cluster(cluster, str(tmp_path / "ckpt"))
    for e in cluster.engines.values():
        e.scheduler.bm.check_invariants()
    # the stale request's blocks must be gone; the snapshot's must be back
    bms = [e.scheduler.bm for e in cluster.engines.values()]
    assert not any(bm.owns(r2.request_id) for bm in bms)
    assert any(bm.owns(r1.request_id) for bm in bms)
    # and the prefix index must not advertise residency recorded before the
    # restore rewrote the pools (blocks may now hold different KV)
    assert not cluster.controller.prefix_index.has_entries


def test_admission_zeroes_stamp_without_resolver():
    """No resolver wired => no reuse data plane => a routed-in stamp must
    not survive to bill compute the engine will not skip."""
    bm = BlockManager(64, 32, "flowkv")
    sched = HybridScheduler(0, bm, max_batch_tokens=4096)
    req = Request(prompt_tokens=list(range(80)), sampling=SamplingParams())
    req.num_cached_prefix_tokens = 64               # phantom routing stamp
    sched.enqueue_prefill(req)
    decision = sched.schedule()
    assert req.num_cached_prefix_tokens == 0
    assert decision.prefill_chunks[req.request_id] == 80


# ---------------------------------------------------------------------------
# token identity + counters (real compute)
# ---------------------------------------------------------------------------
def test_local_hit_token_identity_and_exact_savings(small_model):
    cfg, params = small_model
    donor, followers = _shared_prompts(cfg)
    refs = _reference(cfg, params, [donor] + followers, steps=5)
    # single hybrid node: P==D, local handoff keeps the donor's blocks
    # resident, followers share them in place
    cluster, reqs, outs = _run_staggered(cfg, params, donor, followers,
                                         num_prefill=1, num_decode=0)
    assert outs == refs
    s = cluster.stats()
    assert s["prefix_hits"] == len(followers)
    assert s["prefix_tokens_reused"] == 64 * len(followers)
    total = sum(r.prompt_len for r in reqs)
    # THE acceptance criterion: exactly prompt_len - L tokens executed
    assert s["prefill_tokens_computed"] == total - s["prefix_tokens_reused"]
    assert s["prefix_fetches"] == 0


def test_remote_fetch_token_identity_one_fused_dispatch(small_model):
    cfg, params = small_model
    donor, followers = _shared_prompts(cfg)
    refs = _reference(cfg, params, [donor] + followers, steps=5)
    # 1P + 1D: the donor's prefix re-homes to the decode node after its
    # transfer; followers must pull it back over the transfer plane
    cluster, reqs, outs = _run_staggered(cfg, params, donor, followers,
                                         num_prefill=1, num_decode=1,
                                         hardware=WEAK)
    assert outs == refs
    s = cluster.stats()
    assert s["prefix_hits"] >= 1 and s["prefix_fetches"] >= 1
    total = sum(r.prompt_len for r in reqs)
    assert s["prefill_tokens_computed"] == total - s["prefix_tokens_reused"]
    fetches = [t for t in cluster.transfers if t.kind == "prefix_fetch"]
    assert fetches and all(t.num_dispatches == 1 for t in fetches)
    fetched = [r for r in reqs[1:] if r.prefix_fetch_dispatches]
    assert fetched and all(r.prefix_fetch_dispatches == 1 for r in fetched)


def test_reuse_off_is_cold_everywhere(small_model):
    cfg, params = small_model
    donor, followers = _shared_prompts(cfg)
    refs = _reference(cfg, params, [donor] + followers, steps=5)
    cluster, reqs, outs = _run_staggered(cfg, params, donor, followers,
                                         num_prefill=1, num_decode=0,
                                         prefix_reuse=False)
    assert outs == refs
    s = cluster.stats()
    assert s["prefix_hits"] == 0 and s["prefix_tokens_reused"] == 0
    assert s["prefill_tokens_computed"] == sum(r.prompt_len for r in reqs)


def test_stale_residency_rehomes_to_decode_node(small_model):
    """After the P->D transfer the index must advertise the DECODE node and
    nothing on the prefill node (whose blocks just freed)."""
    cfg, params = small_model
    donor, _ = _shared_prompts(cfg, n_followers=0)
    cluster = PDCluster(cfg, params, num_prefill=1, num_decode=1,
                        num_blocks=128)
    req = Request(prompt_tokens=list(donor),
                  sampling=SamplingParams(max_new_tokens=8))
    cluster.submit(req)
    for _ in range(4):
        cluster.step()
        if req.transfer_end is not None:
            break
    idx = cluster.controller.prefix_index
    assert idx.lookup(0, donor).num_tokens == 0        # P-side entry died
    m = idx.lookup(1, donor)
    assert m.num_tokens == 64 and len(m.block_ids) == 2
    # ... and SURVIVES decode finishing: the freed blocks park in the LRU
    # cache with pages intact, so the prefix stays advertised (the re-hit
    # satellite) until capacity pressure physically reclaims them
    for _ in range(40):
        cluster.step()
        if cluster.finished:
            break
    assert idx.lookup(1, donor).num_tokens == 64
    cluster.engines[1].scheduler.bm.reclaim_cache()
    assert idx.lookup(1, donor).num_tokens == 0


def test_cancel_while_shared_no_leak(small_model):
    """Cancelling the donor while a follower shares its blocks must neither
    free the shared blocks under the follower nor leak them after."""
    cfg, params = small_model
    donor, followers = _shared_prompts(cfg, n_followers=1)
    cluster = PDCluster(cfg, params, num_prefill=1, num_decode=0,
                        num_blocks=128)
    d = Request(prompt_tokens=list(donor),
                sampling=SamplingParams(max_new_tokens=40))
    f = Request(prompt_tokens=list(followers[0]),
                sampling=SamplingParams(max_new_tokens=5))
    cluster.submit(d)
    for _ in range(3):
        cluster.step()
    cluster.submit(f)
    cluster.step()                      # follower admits, sharing the prefix
    bm = cluster.engines[0].scheduler.bm
    assert f.num_cached_prefix_tokens == 64
    shared = f.prefix_block_ids
    assert shared and all(bm.refcount(b) == 2 for b in shared)
    assert cluster.cancel(d)            # donor dies mid-decode
    bm.check_invariants()
    assert all(bm.refcount(b) == 1 for b in shared)   # follower still holds
    for _ in range(60):
        cluster.step()
        if cluster.finished:
            break
    # follower finished token-identically off the shared (now cancelled-
    # donor) prefix, and the pool drained completely
    ref = _reference(cfg, params, [followers[0]], steps=5)
    assert list(cluster.finished[0].output_tokens) == ref[tuple(followers[0])]
    bm.check_invariants()
    assert bm.free_capacity == bm.num_blocks


# ---------------------------------------------------------------------------
# sim mirror: hits priced identically
# ---------------------------------------------------------------------------
def test_sim_prices_hits_and_fetch_is_one_dispatch():
    from repro.sim.cluster_sim import ClusterSim
    from repro.sim.hardware import A100

    cfg = get_smoke_config("qwen3-1.7b")
    weak_p = dataclasses.replace(A100, peak_flops=1e7)
    weak_d = dataclasses.replace(A100, hbm_bandwidth=1e5)   # long residency
    rng = np.random.RandomState(0)
    prefix = rng.randint(0, cfg.vocab_size, size=2048).tolist()
    reqs = [Request(prompt_tokens=prefix + rng.randint(0, cfg.vocab_size, 128).tolist(),
                    sampling=SamplingParams(max_new_tokens=64),
                    arrival_time=0.0 if i == 0 else 66.0 + 0.5 * i)
            for i in range(4)]
    total = sum(r.prompt_len for r in reqs)
    sim = ClusterSim(cfg, "flowkv", num_prefill=1, num_decode=1,
                     routing="load_aware", hw_prefill=weak_p, hw_decode=weak_d)
    stats = sim.run(list(reqs), t_max=500000)
    assert stats["finished"] == len(reqs)
    assert stats["prefix_hits"] >= 1
    assert stats["prefill_tokens_computed"] == total - stats["prefix_tokens_reused"]
    assert stats["prefix_fetches"] >= 1
    assert stats["mean_prefix_fetch_dispatches"] == 1.0
    for n in sim.nodes.values():
        n.bm.check_invariants()

    # baselines never claim hits (no global prefix cache)
    sim2 = ClusterSim(cfg, "flowkv", num_prefill=1, num_decode=1,
                      routing="round_robin", hw_prefill=weak_p,
                      hw_decode=weak_d)
    reqs2 = [Request(prompt_tokens=list(r.prompt_tokens),
                     sampling=SamplingParams(max_new_tokens=64),
                     arrival_time=r.arrival_time) for r in reqs]
    stats2 = sim2.run(reqs2, t_max=500000)
    assert stats2["prefix_hits"] == 0
    assert stats2["prefill_tokens_computed"] == total


# ---------------------------------------------------------------------------
# suffix flash kernel (prefix mode)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,t,blk", [
    (16, 48, 16), (13, 45, 16), (48, 48, 16),
    (8, 72, 128),      # default tiles, 64 < t < 128: regression for the
                       # padded-length/tile-divisibility crash
])
def test_flash_prefill_suffix_mode_matches_oracle(s, t, blk):
    from repro.kernels.flash_prefill import flash_prefill_op, flash_prefill_ref

    b, h, kv, hd = 2, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, t, kv, hd))
    v = jax.random.normal(ks[2], (b, t, kv, hd))
    out = flash_prefill_op(q, k, v, q_blk=blk, k_blk=blk, q_offset=t - s)
    ref = flash_prefill_ref(q, k, v, q_offset=t - s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # suffix rows == the corresponding rows of the full-sequence kernel
    if s < t:
        q_full = jnp.concatenate(
            [jax.random.normal(ks[0], (b, t - s, h, hd)), q], axis=1)
        full = flash_prefill_op(q_full, k, v, q_blk=blk, k_blk=blk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, t - s:]),
                                   atol=2e-6, rtol=2e-6)


def test_model_prefill_suffix_bit_identical(small_model):
    """Suffix prefill reproduces the full prefill's rows to float32 rounding.

    Not bit for bit: XLA picks a matmul's blocking by its operand shapes,
    so the 16-row projection of a suffix and the same rows of the 80-row
    full-prompt projection round differently in the last bits (even layer
    0's K differs). The bar is the float32 one instead: every value within
    a few hundred units of float32 rounding (2**-24 relative) of the largest
    magnitude, far below anything that moves a greedy token.
    """
    cfg, params = small_model
    model = get_model(cfg)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, cfg.vocab_size, size=80).tolist()
    logits, cache = model.prefill(params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    c = 64
    sl, sc = model.prefill_suffix(
        params, {"tokens": jnp.asarray([prompt[c:]], jnp.int32)},
        cache["k"][:, :, :c], cache["v"][:, :, :c])
    for full, suffix in ((logits, sl), (cache["k"][:, :, c:], sc["k"]),
                         (cache["v"][:, :, c:], sc["v"])):
        full, suffix = np.asarray(full), np.asarray(suffix)
        np.testing.assert_allclose(suffix, full, rtol=0,
                                   atol=256 * 2.0 ** -24 * np.abs(full).max())
