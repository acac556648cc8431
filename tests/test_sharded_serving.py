"""Sharded disaggregated serving: mesh-parallel engines + per-shard-pair
fused KV transfer.

The reproduction-critical properties:

* a TP=2 NodeEngine (single-controller emulation: per-shard params, concat
  before every combine contraction) produces BIT-IDENTICAL greedy tokens to
  the single-device engine, dense and MoE;
* a cross-degree P->D transfer (TP=2 -> TP=1, 1 -> 2, 2 -> 4, ...) lands
  bit-identical pool contents to the unsharded reference transfer and costs
  exactly one fused dispatch per overlapping (src_shard, dst_shard) pair;
* the fault plane (checksums, retries, node kills, leak audit) works
  unchanged through sharded pools.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.costmodel import sharded_transfer_calls
from repro.core.layout import KVCacheSpec
from repro.core.transfer import (ShardedTransferEngine, ShardSpec,
                                 TransferEngine, shard_pairs,
                                 verify_sharded_transfer)
from repro.models import transformer as T
from repro.serving.cluster import PDCluster
from repro.serving.request import Request, SamplingParams


@pytest.fixture(scope="module")
def dense_model():
    cfg = get_smoke_config("qwen3-1.7b")
    from repro.models.api import get_model
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def moe_model():
    cfg = get_smoke_config("granite-moe-1b-a400m")
    from repro.models.api import get_model
    params = get_model(cfg).init(jax.random.PRNGKey(1))
    return cfg, params


def _prompts(cfg, n=3, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, cfg.vocab_size, size=rng.randint(6, 24)))
            for _ in range(n)]


def _reference(cfg, params, prompts, steps):
    return {tuple(p): [int(x) for x in
                       T.greedy_generate(params, cfg,
                                         jnp.asarray([p], jnp.int32), steps)[0]]
            for p in prompts}


def _run_cluster(cfg, params, prompts, steps, **kw):
    cluster = PDCluster(cfg, params, num_prefill=1, num_decode=1,
                        num_blocks=128, **kw)
    reqs = [Request(prompt_tokens=list(p),
                    sampling=SamplingParams(max_new_tokens=steps))
            for p in prompts]
    done = cluster.run(reqs, max_cycles=120)
    assert len(done) == len(prompts)
    return cluster, done


# ---------------------------------------------------------------------------
# token identity: sharded engines vs the single-device reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("model_fixture", ["dense_model", "moe_model"])
def test_tp2_cluster_token_identity(model_fixture, request):
    """TP=2 prefill AND decode: every output token bit-identical to the
    monolithic single-device generation (dense and MoE)."""
    cfg, params = request.getfixturevalue(model_fixture)
    prompts = _prompts(cfg)
    refs = _reference(cfg, params, prompts, steps=5)
    cluster, done = _run_cluster(cfg, params, prompts, 5,
                                 tp_degrees={0: 2, 1: 2})
    for r in done:
        assert r.output_tokens == refs[tuple(r.prompt_tokens)]
    stats = cluster.stats()
    assert stats["sharded_nodes"] == 2
    assert stats["max_tp_degree"] == 2
    # same-degree tp=2 -> tp=2: 2 aligned pairs, one fused dispatch each
    assert stats["mean_transfer_dispatches"] == sharded_transfer_calls(2, 2)
    assert stats["shard_dispatches"] > 0
    assert stats["leaked_blocks"] == 0.0


def test_moe_tp2_reports_ep_degree(moe_model):
    cfg, params = moe_model
    cluster = PDCluster(cfg, params, num_prefill=1, num_decode=1,
                        num_blocks=64, tp_degrees={0: 2})
    assert cluster.engines[0].tp_degree == 2
    assert cluster.engines[0].ep_degree == 2   # experts shard with the mesh
    assert cluster.engines[1].tp_degree == 1
    assert cluster.engines[1].ep_degree == 1


@pytest.mark.parametrize("tp_degrees,expected_dispatches", [
    ({0: 2, 1: 1}, 2),   # TP=2 prefill -> TP=1 decode
    ({0: 1, 1: 2}, 2),   # TP=1 prefill -> TP=2 decode
])
def test_cross_degree_cluster_token_identity(dense_model, tp_degrees,
                                             expected_dispatches):
    """Cross-degree disaggregation: resharding happens inside the transfer
    (per-pair fused dispatches), tokens stay bit-identical."""
    cfg, params = dense_model
    prompts = _prompts(cfg, seed=2)
    refs = _reference(cfg, params, prompts, steps=5)
    cluster, done = _run_cluster(cfg, params, prompts, 5,
                                 tp_degrees=tp_degrees)
    for r in done:
        assert r.output_tokens == refs[tuple(r.prompt_tokens)]
    stats = cluster.stats()
    assert stats["mean_transfer_dispatches"] == expected_dispatches
    assert expected_dispatches == sharded_transfer_calls(
        tp_degrees.get(0, 1), tp_degrees.get(1, 1))
    assert stats["leaked_blocks"] == 0.0


def test_request_handle_surfaces_sharding(dense_model):
    from repro.serving.api import FlowKVClient
    cfg, params = dense_model
    client = FlowKVClient(cfg, params, num_prefill=1, num_decode=1,
                          num_blocks=64, tp_degrees={0: 2, 1: 1})
    handle = client.submit(_prompts(cfg, n=1, seed=4)[0],
                           SamplingParams(max_new_tokens=3))
    handle.result()
    stats = handle.stats()
    assert stats["prefill_tp_degree"] == 2
    assert stats["decode_tp_degree"] == 1
    assert stats["prefill_ep_degree"] == 1
    assert stats["shard_dispatches"] == sharded_transfer_calls(2, 1)


# ---------------------------------------------------------------------------
# cross-degree transfer bit-exactness (pure transfer plane, synthetic pools)
# ---------------------------------------------------------------------------
_SPEC = KVCacheSpec(num_layers=2, num_blocks=12, block_size=4,
                    num_kv_heads=8, head_dim=4, dtype=jnp.float32)


def _full_pool(seed):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*_SPEC.shape).astype(np.float32))


def _shard_pool(pool, tp):
    """Slice a full-width FLOWKV pool into per-shard kv-head slices (axis 3)."""
    kw = _SPEC.num_kv_heads // tp
    return [jnp.asarray(np.asarray(pool)[:, :, :, s * kw:(s + 1) * kw])
            for s in range(tp)]


def _unshard_pool(pools, tp):
    return np.concatenate([np.asarray(p) for p in pools], axis=3)


@pytest.mark.parametrize("tp_src,tp_dst", [(2, 1), (1, 2), (2, 4), (4, 2),
                                           (2, 2)])
def test_cross_degree_transfer_bit_exact(tp_src, tp_dst):
    """A sharded transfer between pools of ANY degree pair lands the exact
    bytes the unsharded reference transfer lands, in exactly one fused
    dispatch per overlapping shard pair."""
    src_full = _full_pool(seed=10)
    dst_full = _full_pool(seed=11)
    src_blocks, dst_blocks = [1, 2, 3, 7], [0, 4, 5, 9]

    # unsharded oracle: classic whole-payload engine on the full pools
    ref_engine = TransferEngine(_SPEC)
    ref_plan = ref_engine.planner.plan("flowkv", src_blocks, dst_blocks)
    ref_dst = ref_engine.execute(ref_plan, src_full, dst_full)

    engine = ShardedTransferEngine(_SPEC, _SPEC,
                                   ShardSpec(tp_src, _SPEC.num_kv_heads),
                                   ShardSpec(tp_dst, _SPEC.num_kv_heads))
    plan = engine.plan("flowkv", src_blocks, dst_blocks)
    dst_pools = engine.execute(plan, _shard_pool(src_full, tp_src),
                               _shard_pool(dst_full, tp_dst))

    expected = sharded_transfer_calls(tp_src, tp_dst)
    assert engine.num_dispatches == expected
    assert plan.num_dispatches == expected
    assert len(shard_pairs(engine.src_shard, engine.dst_shard)) == expected
    np.testing.assert_array_equal(_unshard_pool(dst_pools, tp_dst),
                                  np.asarray(ref_dst))
    # per-pair bytes partition the unsharded plan's bytes exactly
    assert plan.total_bytes == ref_plan.total_bytes
    assert verify_sharded_transfer(plan, _SPEC,
                                   _shard_pool(src_full, tp_src),
                                   _SPEC, dst_pools)


def test_per_pair_single_dispatch_accumulates():
    """num_dispatches grows by exactly the pair count per executed plan —
    the per-shard-pair single-dispatch invariant over repeated transfers."""
    engine = ShardedTransferEngine(_SPEC, _SPEC,
                                   ShardSpec(4, _SPEC.num_kv_heads),
                                   ShardSpec(2, _SPEC.num_kv_heads))
    src_pools = _shard_pool(_full_pool(3), 4)
    dst_pools = _shard_pool(_full_pool(4), 2)
    per_plan = sharded_transfer_calls(4, 2)
    for i in range(3):
        plan = engine.plan("flowkv", [i, i + 4], [i + 1, i + 5])
        dst_pools = engine.execute(plan, src_pools, dst_pools)
        assert engine.num_dispatches == (i + 1) * per_plan


def test_verify_sharded_transfer_catches_corruption():
    src_full = _full_pool(seed=20)
    engine = ShardedTransferEngine(_SPEC, _SPEC,
                                   ShardSpec(2, _SPEC.num_kv_heads),
                                   ShardSpec(1, _SPEC.num_kv_heads))
    plan = engine.plan("flowkv", [0, 1], [2, 3])
    dst_pools = engine.execute(plan, _shard_pool(src_full, 2),
                               [_full_pool(seed=21)])
    src_pools = _shard_pool(src_full, 2)
    assert verify_sharded_transfer(plan, _SPEC, src_pools, _SPEC, dst_pools)
    # flip one element inside a page the plan wrote -> per-pair digest fails
    bad = dst_pools[0].reshape(-1, _SPEC.payload)
    table = plan.to_descriptors()
    pid = int(table.page_ids(_SPEC, "dst")[0])
    bad = bad.at[pid, 0].add(1.0).reshape(dst_pools[0].shape)
    assert not verify_sharded_transfer(plan, _SPEC, src_pools, _SPEC, [bad])


# ---------------------------------------------------------------------------
# fault plane through sharded pools
# ---------------------------------------------------------------------------
def test_sharded_transfer_corruption_repaired_by_retry(dense_model):
    """Injected in-flight corruption on a sharded hop: the per-pair checksum
    catches it, the retry re-executes (repairs), tokens stay exact."""
    from repro.faults import FaultSpec
    cfg, params = dense_model
    prompts = _prompts(cfg, n=2, seed=6)
    refs = _reference(cfg, params, prompts, steps=4)
    cluster, done = _run_cluster(
        cfg, params, prompts, 4, tp_degrees={0: 2, 1: 1},
        faults=[FaultSpec("transfer_corrupt", at=0.0, count=2)])
    for r in done:
        assert r.output_tokens == refs[tuple(r.prompt_tokens)]
    stats = cluster.stats()
    assert stats["transfer_retries"] >= 1
    assert stats["degraded_to_recompute"] == 0
    assert stats["leaked_blocks"] == 0.0


def test_shard_aware_leak_audit_under_node_kill(dense_model):
    """Kill the TP=1 decode node while requests are in flight: recovery
    re-prefills on the surviving TP=2 node, every request terminates, and
    the fleet-wide block audit (including the sharded pool's shared block
    manager) reports zero leaks."""
    cfg, params = dense_model
    prompts = _prompts(cfg, n=3, seed=8)
    refs = _reference(cfg, params, prompts, steps=4)
    cluster = PDCluster(cfg, params, num_prefill=1, num_decode=1,
                        num_blocks=128, tp_degrees={0: 2, 1: 1},
                        heartbeat_timeout_cycles=2.0)
    reqs = [Request(prompt_tokens=list(p),
                    sampling=SamplingParams(max_new_tokens=4))
            for p in prompts]
    for r in reqs:
        cluster.submit(r)
    for _ in range(2):           # get KV in flight before the kill
        cluster.step()
    cluster.kill_node(1)         # the decode side dies mid-run
    cluster.run([], max_cycles=120)
    assert len(cluster.finished) == len(prompts)
    for r in cluster.finished:
        # recovery replays already-emitted tokens teacher-forced (they are
        # appended to prompt_tokens), so match requests back to their
        # ORIGINAL prompt by prefix — the output stream must still be the
        # exact greedy continuation
        p = next(pp for pp in prompts
                 if list(r.prompt_tokens[:len(pp)]) == list(pp))
        assert [int(t) for t in r.output_tokens] == refs[tuple(p)]
    assert cluster.audit_blocks() == 0
    cluster.assert_no_leaks()
    # the shared block manager behind the sharded pool stays coherent
    for shard in cluster.engines[0].kv.shards:
        assert shard.bm is cluster.engines[0].kv.bm
    cluster.engines[0].kv.check_invariants()
