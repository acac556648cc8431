"""Zero-gather decode plane: kernel parity vs the dense oracle, bounded jit
cache under ragged batches, fused batch append, and the satellite
regressions (kill_node block leak, fused prefill write, derived bandwidth
utilization)."""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.models.api import get_model
from repro.serving.cluster import PDCluster
from repro.serving.engine import NodeEngine, _next_pow2
from repro.serving.kv_cache import PagedKVCache, spec_for_model
from repro.serving.request import Request, SamplingParams


@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("qwen3-1.7b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, n, seed=0, lo=5, hi=30):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, cfg.vocab_size, size=rng.randint(lo, hi)))
            for _ in range(n)]


def _run_cluster(cfg, params, prompts, steps, *, paged_decode, allocator="flowkv"):
    cluster = PDCluster(cfg, params, num_prefill=1, num_decode=1,
                        num_blocks=128, allocator=allocator,
                        paged_decode=paged_decode)
    reqs = [Request(prompt_tokens=list(p),
                    sampling=SamplingParams(max_new_tokens=steps))
            for p in prompts]
    done = cluster.run(reqs, max_cycles=200)
    assert len(done) == len(prompts)
    return cluster, {tuple(r.prompt_tokens): list(r.output_tokens) for r in done}


# ---------------------------------------------------------------------------
# Tentpole: paged-kernel decode is token-identical to the dense oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("allocator", ["flowkv", "freelist"])
@pytest.mark.parametrize("batch", [1, 3, 8])
def test_paged_decode_matches_dense_oracle(small_model, batch, allocator):
    """Ragged prompt lengths, both allocators, batch sizes 1/3/8."""
    cfg, params = small_model
    prompts = _prompts(cfg, batch, seed=batch, lo=3, hi=40)
    _, kernel = _run_cluster(cfg, params, prompts, 5,
                             paged_decode="kernel", allocator=allocator)
    _, dense = _run_cluster(cfg, params, prompts, 5,
                            paged_decode="dense", allocator=allocator)
    assert kernel == dense


def test_paged_decode_matches_monolithic_reference(small_model):
    cfg, params = small_model
    prompts = _prompts(cfg, 4, seed=11)
    cluster, outs = _run_cluster(cfg, params, prompts, 6, paged_decode="kernel")
    for p in prompts:
        ref = T.greedy_generate(params, cfg, jnp.asarray([p], jnp.int32), 6)
        assert outs[tuple(p)] == [int(x) for x in ref[0]]
    # O(1) dispatches per decode cycle on the zero-gather path
    s = cluster.stats()
    assert s["decode_steps"] > 0
    assert s["mean_decode_dispatches_per_step"] == 1.0


@pytest.mark.parametrize("tp", [1, 2])
def test_paged_decode_matches_dense_step_sharded_and_spilled(small_model, tp):
    """n-step paged decode equals the dense ``decode_step`` in float32 on
    tp-sharded pools, across a spill and restore: three blocks force one
    request off the pool mid-decode (gather_dense out, write_prefill back)."""
    from repro.serving.api import FlowKVClient
    from repro.serving.request import RequestState
    cfg, params = small_model
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, size=20).tolist()
               for _ in range(2)]
    client = FlowKVClient(cfg, params, num_prefill=1, num_decode=1,
                          num_blocks=3, paged_decode="kernel",
                          tp_degrees={0: tp, 1: tp})
    handles = [client.submit(p, SamplingParams(max_new_tokens=20))
               for p in prompts]
    swapped = 0
    for _ in range(400):
        client.step()
        swapped += sum(h.request.state is RequestState.SWAPPED
                       for h in handles)
        if all(h.done for h in handles):
            break
    assert swapped > 0, "pool was never pressured into a spill"
    for h, p in zip(handles, prompts):
        ref = T.greedy_generate(params, cfg, jnp.asarray([p], jnp.int32), 20)
        assert h.request.output_tokens == [int(x) for x in ref[0]]
    d = client.cluster.engines[1]
    assert d.use_paged_decode and d.decode_dispatches == d.decode_steps > 0


def _pool_consumers(jaxpr, pool_vars, found):
    """Walk ``jaxpr`` (into sub-jaxprs) recording every equation that takes
    a pool value, as ``(primitive, kernel name or None)``. The outputs of
    the in-place append are pool values too. Returns the pool values."""
    from jax.extend.core import Literal
    for eqn in jaxpr.eqns:
        taken = [i for i, v in enumerate(eqn.invars)
                 if not isinstance(v, Literal) and v in pool_vars]
        if not taken:
            continue
        sub = eqn.params.get("jaxpr")
        if eqn.primitive.name != "pallas_call" and sub is not None:
            inner = getattr(sub, "jaxpr", sub)
            inner_pool = _pool_consumers(
                inner, {inner.invars[i] for i in taken}, found)
            pool_vars |= {o for o, i in zip(eqn.outvars, inner.outvars)
                          if i in inner_pool}
            continue
        name = (eqn.params["name"]
                if eqn.primitive.name == "pallas_call" else None)
        found.append((eqn.primitive.name, name))
        if name == "kv_append":
            pool_vars |= set(eqn.outvars)
    return pool_vars


def _all_eqns(jaxpr):
    from jax.extend.core import Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, Jaxpr):
                    yield from _all_eqns(inner)


def test_decode_step_reads_and_writes_the_pool_in_place(small_model):
    """Structural guard: in the traced paged decode step the pool reaches
    only the paged-attention kernel and the append's in-place write — no
    reshape, transpose, slice, dynamic_slice or gather of it — and no
    equation makes an array of one layer plane's size or more besides the
    pool itself."""
    cfg, params = small_model
    nb, b, w = 64, 2, 4
    spec = spec_for_model(cfg, nb)
    plane = nb * 2 * spec.payload
    assert plane > max(x.size for x in jax.tree.leaves(params))
    pool = jnp.zeros(spec.shape, spec.dtype)
    bt = jnp.arange(b * w, dtype=jnp.int32).reshape(b, w)
    closed = jax.make_jaxpr(
        lambda p, t, pl, bt_, ln: T.decode_step_paged(p, cfg, t, pl, bt_, ln))(
        params, jnp.zeros((b,), jnp.int32), pool, bt,
        jnp.asarray([40, 70], jnp.int32))
    jaxpr = closed.jaxpr
    pool_var = jaxpr.invars[len(jax.tree.leaves(params)) + 1]
    assert pool_var.aval.shape == spec.shape
    found = []
    _pool_consumers(jaxpr, {pool_var}, found)
    assert sorted(set(found)) == [("pallas_call", "kv_append"),
                                  ("pallas_call", "paged_attention")], found
    assert found.count(("pallas_call", "kv_append")) == 1
    big = [(e.primitive.name, o.aval.shape) for e in _all_eqns(jaxpr)
           for o in e.outvars
           if o.aval.size >= plane and o.aval.shape != spec.shape]
    assert not big, big


def test_paged_decode_moe_family_parity():
    """The zero-gather step covers every paged family, not just dense."""
    cfg = get_smoke_config("granite-moe-1b-a400m")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _prompts(cfg, 3, seed=2, lo=4, hi=20)
    _, kernel = _run_cluster(cfg, params, prompts, 4, paged_decode="kernel")
    _, dense = _run_cluster(cfg, params, prompts, 4, paged_decode="dense")
    assert kernel == dense


def test_paged_step_is_single_dispatch_per_cycle(small_model):
    cfg, params = small_model
    prompts = _prompts(cfg, 6, seed=3)
    cluster, _ = _run_cluster(cfg, params, prompts, 5, paged_decode="kernel")
    d = cluster.engines[1]
    assert d.decode_dispatches == d.decode_steps
    for r in cluster.finished:
        assert r.decode_dispatches == r.decode_steps


def test_dense_oracle_pays_per_request_dispatches(small_model):
    cfg, params = small_model
    prompts = _prompts(cfg, 4, seed=5)
    cluster, _ = _run_cluster(cfg, params, prompts, 4, paged_decode="dense")
    d = cluster.engines[1]
    # every dense cycle costs 2B+1 dispatches; with B>=1 that's >= 3 per step
    assert d.decode_dispatches >= 3 * d.decode_steps


# ---------------------------------------------------------------------------
# Jit-cache bucketing: ragged workloads compile few variants
# ---------------------------------------------------------------------------
def test_bucketed_step_bounds_jit_cache(small_model):
    cfg, params = small_model
    max_batch, max_blocks = 8, 16
    engine = NodeEngine(0, cfg, params, num_blocks=128,
                        paged_decode="kernel", max_batch_tokens=8192)
    rng = np.random.RandomState(0)
    # ragged arrival: batches of every size 1..max_batch, ragged lengths
    for wave in range(1, max_batch + 1):
        reqs = [Request(prompt_tokens=list(rng.randint(0, cfg.vocab_size,
                                                       rng.randint(3, 60))),
                        sampling=SamplingParams(max_new_tokens=3))
                for _ in range(wave)]
        for r in reqs:
            engine.scheduler.enqueue_prefill(r)
        pending = list(reqs)
        while pending:
            done, _ = engine.step()
            for r in done:
                engine.scheduler.enqueue_decode(r)
                pending.remove(r)
        finished = []
        while len(finished) < len(reqs):
            _, fin = engine.step()
            finished.extend(fin)
    bound = math.log2(max_batch) * math.log2(max_blocks)
    assert 1 <= engine.decode_compile_variants <= bound, \
        (engine.decode_compile_variants, bound)
    # and every bucket is a power of two on both axes
    for bp, wp in engine._decode_cache_keys:
        assert bp == _next_pow2(bp) and wp == _next_pow2(wp)


def test_next_pow2():
    assert [_next_pow2(n) for n in (1, 2, 3, 4, 5, 8, 9)] == [1, 2, 4, 4, 8, 8, 16]


# ---------------------------------------------------------------------------
# Fused batch append (kv_cache port over the descriptor-table kernel)
# ---------------------------------------------------------------------------
def test_append_tokens_matches_per_request_appends(small_model):
    cfg, params = small_model
    spec = spec_for_model(cfg, 32)
    a = PagedKVCache(spec, "flowkv")
    b = PagedKVCache(spec, "flowkv")
    L, KV, hd = spec.num_layers, spec.num_kv_heads, spec.head_dim
    rng = np.random.RandomState(0)
    positions = []
    for rid, ntok in ((0, 5), (1, spec.block_size), (2, 2 * spec.block_size - 1)):
        a.bm.allocate(rid, ntok + 1)
        b.bm._table[rid] = list(a.bm.get(rid))     # identical placement
        positions.append(ntok)                     # append lands after ntok
    kn = jnp.asarray(rng.randn(L, 3, KV, hd), spec.dtype)
    vn = jnp.asarray(rng.randn(L, 3, KV, hd), spec.dtype)
    a.append_tokens([0, 1, 2], kn, vn, positions)
    for i, rid in enumerate((0, 1, 2)):
        b.append_token(rid, kn[:, i], vn[:, i], positions[i])
    np.testing.assert_array_equal(np.asarray(a.pool, np.float32),
                                  np.asarray(b.pool, np.float32))
    assert a.num_pool_dispatches == 1              # one fused dispatch
    assert b.num_pool_dispatches == 3              # one per request


def test_write_prefill_single_fused_update_roundtrips(small_model):
    """Satellite: K and V land in one pool update, contents unchanged."""
    cfg, params = small_model
    spec = spec_for_model(cfg, 16)
    kv = PagedKVCache(spec, "flowkv")
    L, KV, hd = spec.num_layers, spec.num_kv_heads, spec.head_dim
    length = spec.block_size + 3                   # spans 2 blocks, padded tail
    kv.bm.allocate(7, length)
    rng = np.random.RandomState(1)
    k = jnp.asarray(rng.randn(L, length, KV, hd), spec.dtype)
    v = jnp.asarray(rng.randn(L, length, KV, hd), spec.dtype)
    kv.write_prefill(7, k, v, length)
    assert kv.num_pool_dispatches == 1
    kk, vv = kv.gather_dense(7, length)
    np.testing.assert_array_equal(np.asarray(kk, np.float32), np.asarray(k, np.float32))
    np.testing.assert_array_equal(np.asarray(vv, np.float32), np.asarray(v, np.float32))


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------
def test_kill_node_releases_paged_blocks(small_model):
    """kill_node used to clear .states but leak block-manager allocations."""
    cfg, params = small_model
    prompts = _prompts(cfg, 3, seed=9)
    cluster = PDCluster(cfg, params, num_prefill=1, num_decode=1, num_blocks=64)
    reqs = [Request(prompt_tokens=list(p), sampling=SamplingParams(max_new_tokens=8))
            for p in prompts]
    for r in reqs:
        cluster.submit(r)
    for _ in range(3):        # mid-flight: prefill node holds live blocks
        cluster.step()
    assert any(e.scheduler.bm.num_free < 64 for e in cluster.engines.values())
    for nid in list(cluster.engines):
        cluster.kill_node(nid)
        eng = cluster.engines[nid]
        assert eng.scheduler.bm.num_free == 64, \
            f"node {nid} leaked blocks after kill"
        assert eng.scheduler.bm.utilization == 0.0
        eng.scheduler.bm.check_invariants()


def test_bandwidth_util_derived_from_decoded_tokens(small_model):
    """run_decode used to pin last_bandwidth_util = 1.0 before checking
    whether the batch progressed; it is now the decoded-token fraction."""
    cfg, params = small_model
    engine = NodeEngine(0, cfg, params, num_blocks=64, paged_decode="kernel")
    req = Request(prompt_tokens=list(range(1, 9)),
                  sampling=SamplingParams(max_new_tokens=3))
    engine.scheduler.enqueue_prefill(req)
    done, _ = engine.step()
    assert done and engine.scheduler.last_bandwidth_util == 0.0  # no decode yet
    engine.scheduler.enqueue_decode(req)
    engine.step()
    assert engine.scheduler.last_bandwidth_util == 1.0           # full progress
    # a stalled batch must read as zero pressure, not be masked to 1.0
    from repro.core.scheduler.hybrid_scheduler import ScheduleDecision
    saved = engine._decode_paged
    engine._decode_paged = lambda batch: 0                       # no progress
    try:
        engine.run_decode(ScheduleDecision(kind="decode", decode_batch=[req]))
    finally:
        engine._decode_paged = saved
    assert engine.scheduler.last_bandwidth_util == 0.0
    while req.num_output < 3:
        engine.step()
    engine.step()                                                # idle cycle
    assert engine.scheduler.last_bandwidth_util == 0.0


def test_paged_decode_mode_validation(small_model):
    cfg, params = small_model
    with pytest.raises(ValueError):
        NodeEngine(0, cfg, params, paged_decode="bogus")
    # windowed attention has no kernel path: "kernel" must refuse, "auto"
    # must fall back to the dense oracle
    import dataclasses
    wcfg = dataclasses.replace(cfg, attn_window=4)
    with pytest.raises(ValueError):
        NodeEngine(0, wcfg, params, paged_decode="kernel")
    eng = NodeEngine(0, wcfg, params, paged_decode="auto")
    assert not eng.use_paged_decode
