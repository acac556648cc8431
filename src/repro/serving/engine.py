"""Per-node inference engine: executes the hybrid scheduler's decisions with
real JAX compute against the paged pool.

Two request-state transports, per DESIGN.md §4:

* paged KV path (transformer families) — prefill writes pages; decode runs
  the ZERO-GATHER step: one jitted ``Model.decode_paged`` call per cycle
  that reads pages in place through the Pallas paged-attention kernel and
  writes the batch's new K/V in place with one fused append kernel, the
  pool donated. No dense cache is materialized; device dispatches per
  decode cycle are O(1) regardless of batch size or context length. The
  old gather-dense bridge survives as the test/benchmark oracle
  (``paged_decode="dense"``) and as the fallback for windowed attention.
* state path (ssm / hybrid / encdec) — the request's cache pytree is held
  whole and shipped whole (one logical segment).

Ragged batches are padded to power-of-two buckets in BOTH batch size and
block-table width (pad lanes replicate lane 0, so their duplicate page
rewrites are idempotent), keeping the jit cache bounded at
``O(log2(max_batch) * log2(max_blocks))`` variants. ``decode_dispatches`` /
``decode_steps`` / ``decode_compile_variants`` surface through
``RequestHandle.stats()`` and ``PDCluster.stats()``.

The engine is deliberately synchronous and single-host-scale: the paper's
*timing* claims are reproduced by ``sim/cluster_sim.py`` with calibrated
cost models; this engine proves the *data path* is correct (disaggregated
generation must be token-identical to monolithic generation — see
tests/test_cluster.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_manager import BlockManager
from repro.core.scheduler.hybrid_scheduler import HybridScheduler, ScheduleDecision
from repro.distributed import tp as tp_mod
from repro.models.api import Model, get_model
from repro.models.common import ModelConfig
from repro.obs.tracing import NO_SPAN
from repro.serving.kv_cache import PagedKVCache, ShardedKVCache, spec_for_model
from repro.serving.request import Request, RequestState

PAGED_FAMILIES = ("dense", "moe", "vlm", "audio")


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


# One jitted zero-gather step per (config, donation) — engines of the same
# config share it, so a cluster of N nodes compiles each (batch, table-width)
# bucket once, not N times.
_PAGED_STEP_CACHE: Dict[Tuple[ModelConfig, bool], Any] = {}


def _paged_step_for(model: Model, cfg: ModelConfig):
    donate = jax.default_backend() in ("tpu", "gpu")
    key = (cfg, donate)
    fn = _PAGED_STEP_CACHE.get(key)
    if fn is None:
        fn = jax.jit(model.decode_paged,
                     donate_argnums=(2,) if donate else ())
        _PAGED_STEP_CACHE[key] = fn
    return fn


# Sharded twin, keyed additionally by tp degree: one jitted step covers all
# shards (per-shard kernels + full-width merge inside a single artifact).
_SHARDED_STEP_CACHE: Dict[Tuple[ModelConfig, int, bool], Any] = {}


def _sharded_step_for(cfg: ModelConfig, tp_degree: int):
    donate = jax.default_backend() in ("tpu", "gpu")
    key = (cfg, tp_degree, donate)
    fn = _SHARDED_STEP_CACHE.get(key)
    if fn is None:
        def step(shards, tok, pools, bt, lens):
            return tp_mod.sharded_decode_step_paged(
                shards, cfg, tok, pools, bt, lens)
        fn = jax.jit(step, donate_argnums=(2,) if donate else ())
        _SHARDED_STEP_CACHE[key] = fn
    return fn


class NodeEngine:
    """Role-flexible node: serves prefill AND decode from ONE block pool.

    A node's *role* ("prefill"/"decode") lives in the controller's
    ``NodeHandle`` and only biases routing and scheduler priority — the
    engine itself runs whatever its ``HybridScheduler`` admits, which is
    what lets ``GlobalController.set_role`` flip a node P<->D mid-run
    without draining it: in-flight work of the old role finishes from the
    same pool while new work of the new role is admitted.
    """

    def __init__(self, node_id: int, cfg: ModelConfig, params,
                 num_blocks: int = 256, allocator: str = "flowkv",
                 max_batch_tokens: int = 2048, max_model_len: int = 512,
                 paged_decode: str = "auto", chunked_prefill: bool = True,
                 prefill_chunk_tokens: Optional[int] = None,
                 tp_degree: int = 1):
        self.node_id = node_id
        self.cfg = cfg
        self.model: Model = get_model(cfg)
        self.params = params
        self.max_model_len = max_model_len
        self.paged = cfg.family in PAGED_FAMILIES
        # -- mesh parallelism ---------------------------------------------------------
        # tp_degree > 1 runs the model sharded over a model axis (TP for
        # attention/MLP, EP for MoE experts) with the pool split into
        # per-kv-head-slice shard pools; see distributed/tp.py for why the
        # result is bit-identical to the tp=1 engine.
        self.tp_degree = tp_degree
        self.ep_degree = tp_mod.ep_degree(cfg, tp_degree)
        self.shard_params: Optional[List[Any]] = None
        if tp_degree > 1:
            if not self.paged:
                raise ValueError("tp_degree > 1 requires a paged-KV family, "
                                 f"got {cfg.family!r}")
            tp_mod.validate_tp(cfg, tp_degree)
            self.shard_params = tp_mod.shard_params(params, cfg, tp_degree)
        if self.paged:
            if tp_degree > 1:
                self.kv = ShardedKVCache(spec_for_model(cfg, num_blocks),
                                         tp_degree, allocator)
            else:
                self.kv = PagedKVCache(spec_for_model(cfg, num_blocks),
                                       allocator)
            bm = self.kv.bm
        else:
            # state path: block manager still gates admission (token budget),
            # but state lives in a per-request pytree store.
            self.kv = None
            bm = BlockManager(num_blocks, cfg.block_size, allocator)
        self.states: Dict[int, Any] = {}        # request_id -> cache pytree (state path)
        # Chunked prefill needs the suffix data plane: an intermediate chunk
        # is exactly a suffix prefill (q_offset = tokens done) over the
        # paged pool. State families and windowed-attention configs have no
        # suffix kernel, so their scheduler runs whole-prompt admission.
        self.supports_chunked_prefill = \
            self.paged and self.model.prefill_suffix is not None
        self.scheduler = HybridScheduler(
            node_id, bm, max_batch_tokens=max_batch_tokens,
            chunked_prefill=chunked_prefill and self.supports_chunked_prefill,
            prefill_chunk_tokens=prefill_chunk_tokens)
        # -- spill path (decode memory pressure) --------------------------------------
        # request_id -> (k, v, length) saved host-side when the scheduler
        # preempts a decode request; restored into fresh blocks on resume so
        # generation continues token-identically. Paged engines only — the
        # state path keeps its pytree in ``self.states`` across a swap.
        self.spilled: Dict[int, Tuple[np.ndarray, np.ndarray, int]] = {}
        if self.paged:
            self.scheduler.on_spill = self._spill_kv
            self.scheduler.on_resume = self._restore_kv
            self.scheduler.on_discard = \
                lambda req: self.spilled.pop(req.request_id, None)
        # -- zero-gather decode plane ------------------------------------------------
        # paged_decode: "auto" (kernel when supported), "kernel", "dense" (oracle).
        if paged_decode not in ("auto", "kernel", "dense"):
            raise ValueError(f"paged_decode must be auto|kernel|dense, got {paged_decode!r}")
        # decode_paged is None for both state families and windowed-attention
        # configs (the kernel has no window mask) — see models/api.py
        kernel_ok = self.paged and self.model.decode_paged is not None
        if paged_decode == "kernel" and not kernel_ok:
            raise ValueError("paged_decode='kernel' unsupported for this config "
                             "(state family or windowed attention)")
        self.use_paged_decode = kernel_ok and paged_decode != "dense"
        self._paged_step = None
        if self.use_paged_decode:
            self._paged_step = (_sharded_step_for(cfg, tp_degree)
                                if tp_degree > 1
                                else _paged_step_for(self.model, cfg))
        self.decode_steps = 0          # decode cycles executed
        self.decode_dispatches = 0     # device dispatches those cycles issued
        self._decode_cache_keys: Set[Tuple[int, int]] = set()   # jit buckets seen
        self._prefill_shapes: Set[Tuple[int, int]] = set()      # (offset, chunk) seen
        # -- prefix-reuse data plane ---------------------------------------------------
        # A prefix-cache hit only skips work on the paged path with a
        # suffix-capable model (windowed attention and state families
        # recompute); the runtime consults this before wiring the node into
        # the reuse plane (resolver hook + index recording).
        self.supports_prefix_reuse = self.paged and self.model.prefill_suffix is not None
        # Optional repro.serving.host_tier.TierManager, attached by the
        # cluster when host_tier_blocks > 0 (paged, reuse-capable engines
        # only): the node's host-DRAM tier for demoted prefix blocks. The
        # engine itself never branches on it — demotion hangs off
        # bm.on_evict and promotion runs from the cluster's pre-admission
        # pass — but checkpoint/teardown tooling finds it here.
        self.tier = None
        self.prefill_tokens_computed = 0   # prompt tokens actually forwarded
        self.prefix_hits = 0               # prefills that reused a resident prefix
        self.prefix_tokens_reused = 0      # prompt tokens NOT recomputed
        # -- observability ------------------------------------------------------------
        # Optional repro.obs.tracing.SpanRecorder; read at emission time, so
        # attach_tracer() can instrument a live engine. The engine emits the
        # "prefill" span (it is where prefill runs and where wall-clock
        # stamps originate) and the step spans of a prefill chunk and a
        # decode step; queue/transfer/decode spans come from the cluster,
        # admission spans from the controller.
        self.tracer = None

    @property
    def decode_compile_variants(self) -> int:
        """Distinct (batch, block-table-width) buckets the step compiled."""
        return len(self._decode_cache_keys)

    # -- prefill ------------------------------------------------------------------
    def run_prefill(self, decision: ScheduleDecision,
                    now: Optional[float] = None) -> List[Request]:
        """Execute the prefill batch; returns requests that finished prefill.

        Honors the scheduler's per-request CHUNK budget
        (``decision.prefill_chunks``): an intermediate chunk runs as a
        suffix prefill — prefix K/V gathered from the paged pool, the
        chunk's tokens forwarded at ``q_offset = tokens_done``, the new
        pages written back at ``start = tokens_done`` — which is
        bit-identical to the monolithic forward over the same positions
        (tests/test_chunked_prefill.py). A chunk that starts at 0 and
        covers the whole prompt takes the monolithic path, so unchunked
        behavior is byte-for-byte the old code.

        The first output token is produced by the FINAL chunk (prefill's
        last forward emits it), so this is also where TTFT is stamped when
        a clock is supplied — not at transfer time.
        """
        done: List[Request] = []
        for req in decision.prefill_batch:   # simple per-request prefill (no padding waste)
            if now is not None and req.prefill_start is None:
                req.prefill_start = now
            if req.prefill_start_wall is None:
                req.prefill_start_wall = time.monotonic()
            offset = self.scheduler.prefill_tokens_done(req)
            chunk = decision.prefill_chunks.get(
                req.request_id, req.prompt_len - offset)
            chunk = min(chunk, req.prompt_len - offset)
            if chunk <= 0:
                continue
            final = offset + chunk == req.prompt_len
            if final:
                req.last_prefill_chunk_tokens = chunk
            cached = req.num_cached_prefix_tokens if self.supports_prefix_reuse else 0
            new_shape = (offset, chunk) not in self._prefill_shapes
            self._prefill_shapes.add((offset, chunk))
            tracer = self.tracer
            with (tracer.span("prefill_chunk", req.request_id, self.node_id,
                              offset=offset, tokens=chunk,
                              prompt_len=req.prompt_len, final=final,
                              new_shape=new_shape)
                  if tracer is not None else NO_SPAN) as span:
                if span is not None:
                    span.start_cycle = span.end_cycle = now
                self._prefill_chunk(req, offset, chunk, final, cached)
            self.prefill_tokens_computed += chunk
            # report ONLY the tokens this cycle actually forwarded:
            # prefill_progressed seeds progress at num_cached_prefix_tokens,
            # so reporting prompt_len here double-counted the hit and let the
            # chunked-prefill budget diverge from executed work
            if self.scheduler.prefill_progressed(req, chunk):
                if now is not None and req.first_token_time is None:
                    req.first_token_time = now
                wall = time.monotonic()
                req.prefill_end_wall = wall
                if req.first_token_wall is None:
                    req.first_token_wall = wall
                if self.tracer is not None:
                    self.tracer.emit(
                        req.request_id, "prefill",
                        start_cycle=req.prefill_start, end_cycle=now,
                        start_wall_s=req.prefill_start_wall,
                        end_wall_s=wall, node_id=self.node_id,
                        attrs={"prompt_len": req.prompt_len,
                               "cached_prefix_tokens": cached})
                done.append(req)
        self.scheduler.last_compute_util = 1.0 if decision.prefill_batch else 0.0
        return done

    def _prefill_chunk(self, req: Request, offset: int, chunk: int,
                       final: bool, cached: int) -> None:
        """Forward ``prompt[offset:offset+chunk]`` and write its pages; the
        final chunk of a fresh prompt also emits the first output token."""
        tracer = self.tracer
        if offset > 0:
            # Suffix chunk: resident prefix = cached-prefix blocks
            # (shared ref-counted or landed by a remote fetch) plus any
            # previously-executed chunks' pages. Forward ONLY
            # prompt[offset:offset+chunk], attending over the resident
            # K/V, and write only this chunk's pages — a prefix-cache
            # hit skips real compute, a chunk continuation resumes it.
            with (tracer.span("prefill.gather_prefix")
                  if tracer is not None else NO_SPAN):
                k_pre, v_pre = self.kv.gather_prefix(req.request_id, offset)
            with (tracer.span("prefill.forward")
                  if tracer is not None else NO_SPAN):
                tokens = jnp.asarray(
                    [req.prompt_tokens[offset:offset + chunk]], jnp.int32)
                if self.tp_degree > 1:
                    logits, cache = tp_mod.sharded_prefill_suffix(
                        self.shard_params, self.cfg, tokens,
                        k_pre[:, None], v_pre[:, None])
                else:
                    logits, cache = self.model.prefill_suffix(
                        self.params, {"tokens": tokens},
                        k_pre[:, None], v_pre[:, None])
            with (tracer.span("prefill.write")
                  if tracer is not None else NO_SPAN):
                self.kv.write_prefill(req.request_id, cache["k"][:, 0],
                                      cache["v"][:, 0], chunk, start=offset)
            if offset == cached and cached > 0:
                # first executed chunk of a prefix-hit request
                self.prefix_hits += 1
                self.prefix_tokens_reused += cached
        else:
            with (tracer.span("prefill.forward")
                  if tracer is not None else NO_SPAN):
                tokens = jnp.asarray([req.prompt_tokens[:chunk]], jnp.int32)
                if self.tp_degree > 1:
                    logits, cache = tp_mod.sharded_prefill(
                        self.shard_params, self.cfg, tokens)
                else:
                    logits, cache = self.model.prefill(self.params,
                                                       {"tokens": tokens})
            with (tracer.span("prefill.write")
                  if tracer is not None else NO_SPAN):
                if self.paged:
                    self.kv.write_prefill(req.request_id, cache["k"][:, 0],
                                          cache["v"][:, 0], chunk)
                else:
                    self.states[req.request_id] = jax.tree.map(lambda x: x, cache)
        if final and not req.output_tokens:
            # only the last chunk's last position is the real next-token
            # distribution; intermediate chunks' logits are discarded.
            # A RECOVERY prefill (reset_for_retry folded emitted tokens
            # into the prompt) re-predicts a token the client already
            # has — output_tokens is non-empty, so the duplicate append
            # is skipped and decode resumes from the kept token.
            with (tracer.span("prefill.sample")
                  if tracer is not None else NO_SPAN):
                req.output_tokens.append(int(jnp.argmax(logits[0])))

    # -- decode --------------------------------------------------------------------
    def run_decode(self, decision: ScheduleDecision) -> List[Request]:
        """One decode step for the running batch; returns finished requests."""
        batch = decision.decode_batch
        if not batch:
            return []
        finished: List[Request] = []
        tracer = self.tracer
        with (tracer.span("decode.step", node_id=self.node_id, batch=len(batch))
              if tracer is not None else NO_SPAN):
            if self.paged:
                decoded = self._decode_paged(batch)
            else:
                decoded = self._decode_state(batch)
        for req in batch:
            last = req.output_tokens[-1]
            eos = req.sampling.eos_token_id
            if req.num_output >= req.sampling.max_new_tokens or (eos is not None and last == eos):
                finished.append(req)
                if not self.paged:
                    self.states.pop(req.request_id, None)
                self.scheduler.decode_finished(req)
        # bandwidth pressure = fraction of the admitted batch that actually
        # decoded a token this cycle (was: pinned 1.0 before checking whether
        # the batch progressed). A fully-progressing batch still reads 1.0 —
        # decode streams the full weights regardless of batch size — but any
        # future path where requests stall mid-cycle now shows up in the load
        # scorer instead of being masked.
        self.scheduler.last_bandwidth_util = decoded / max(1, len(batch))
        return finished

    def _decode_paged(self, batch: List[Request]) -> int:
        if self.use_paged_decode:
            return self._decode_paged_kernel(batch)
        return self._decode_paged_dense(batch)

    def _decode_paged_kernel(self, batch: List[Request]) -> int:
        """Zero-gather step: ONE jitted dispatch for the whole batch.

        Batch and block-table width are padded to power-of-two buckets; pad
        lanes replicate lane 0 (same token / length / block-table row), so
        their append descriptors duplicate lane 0's writes bit-identically
        instead of aiming at block 0.
        """
        b = len(batch)
        tracer = self.tracer
        with (tracer.span("decode.prepare") if tracer is not None else NO_SPAN):
            # KV cached so far = prompt + all outputs except the newest
            # token, whose KV is written by THIS step at position total-1.
            lens = [r.total_len - 1 for r in batch]
            toks = [r.output_tokens[-1] for r in batch]
            rids = [r.request_id for r in batch]
            tables = self.kv.export_block_tables(rids)
            bp = _next_pow2(b)
            wp = _next_pow2(tables.shape[1])
            bt = np.zeros((bp, wp), np.int32)
            bt[:b, :tables.shape[1]] = tables
            bt[b:] = bt[0]
            tok_arr = np.full((bp,), toks[0], np.int32)
            tok_arr[:b] = toks
            len_arr = np.full((bp,), lens[0], np.int32)
            len_arr[:b] = lens
            tok_arr, bt, len_arr = (jnp.asarray(tok_arr), jnp.asarray(bt),
                                    jnp.asarray(len_arr))
        new_bucket = (bp, wp) not in self._decode_cache_keys
        self._decode_cache_keys.add((bp, wp))
        # decode_dispatches counts host-issued device computations, by
        # construction: this branch launches exactly ONE (the jitted step —
        # paged attention + fused append inside a single artifact; the argmax
        # below is a host read, not a launch). Anyone adding a second device
        # call to this path must bump the increment or the O(1) claim that
        # benchmarks/decode_throughput.py --check enforces becomes a lie.
        with (tracer.span("decode.dispatch", bucket=[bp, wp],
                          new_bucket=new_bucket)
              if tracer is not None else NO_SPAN):
            if self.tp_degree > 1:
                logits, new_pools = self._paged_step(
                    self.shard_params, tok_arr,
                    tuple(s.pool for s in self.kv.shards), bt, len_arr)
                for shard, pool in zip(self.kv.shards, new_pools):
                    shard.pool = pool
            else:
                logits, self.kv.pool = self._paged_step(
                    self.params, tok_arr, self.kv.pool, bt, len_arr)
        self.kv.num_pool_dispatches += 1
        self.decode_steps += 1
        self.decode_dispatches += 1
        with (tracer.span("decode.readback") if tracer is not None else NO_SPAN):
            nxt = np.argmax(np.asarray(logits, np.float32)[:b], axis=-1)
        for i, r in enumerate(batch):
            r.output_tokens.append(int(nxt[i]))
            r.decode_steps += 1
            r.decode_dispatches += 1
        return b

    def _decode_paged_dense(self, batch: List[Request]) -> int:
        """Gather-dense oracle: densify pages per request, decode, write back
        per request — O(batch) dispatches per step. Kept as the reference
        the zero-gather step must match token-for-token."""
        max_len = max(r.total_len for r in batch) + 1
        ks, vs, lens, toks = [], [], [], []
        for r in batch:
            k, v = self.kv.gather_dense(r.request_id, max_len)
            ks.append(k); vs.append(v)
            lens.append(r.total_len - 1)
            toks.append(r.output_tokens[-1])
        cache = {
            "k": jnp.stack(ks, axis=1),            # (L, B, T, KV, hd)
            "v": jnp.stack(vs, axis=1),
            "length": jnp.asarray(lens, jnp.int32),
        }
        logits, new_cache = self.model.decode(
            self.params, jnp.asarray(toks, jnp.int32), cache)
        nxt = jnp.argmax(logits, axis=-1)
        step_dispatches = 2 * len(batch) + 1   # B gathers + decode + B appends
        for i, r in enumerate(batch):
            pos = lens[i]
            k_new = new_cache["k"][:, i, pos]
            v_new = new_cache["v"][:, i, pos]
            self.kv.append_token(r.request_id, k_new, v_new, pos)
            r.output_tokens.append(int(nxt[i]))
            r.decode_steps += 1
            r.decode_dispatches += step_dispatches
        self.decode_steps += 1
        self.decode_dispatches += step_dispatches
        return len(batch)

    def _decode_state(self, batch: List[Request]) -> int:
        n = len(batch)
        for r in batch:   # state caches are per-request pytrees
            cache = self.states[r.request_id]
            logits, cache = self.model.decode(
                self.params, jnp.asarray([r.output_tokens[-1]], jnp.int32), cache)
            self.states[r.request_id] = cache
            r.output_tokens.append(int(jnp.argmax(logits[0])))
            r.decode_steps += 1
            # per-request semantics match serving/api.py: dispatches issued
            # by the cycles this request rode in — the state path runs one
            # decode per request, so every rider is charged the whole cycle
            r.decode_dispatches += n
        self.decode_steps += 1
        self.decode_dispatches += n
        return n

    # -- spill path (scheduler hooks) ------------------------------------------------
    def _spill_kv(self, req: Request) -> None:
        """Save a preempted request's KV off-pool before its blocks free.

        KV cached at preemption time covers positions [0, total_len-1): the
        newest output token's KV would have been written by the decode step
        that could not run (same accounting as ``_decode_paged_kernel``).
        """
        length = req.total_len - 1
        k, v = self.kv.gather_dense(req.request_id, length)
        self.spilled[req.request_id] = (np.asarray(k), np.asarray(v), length)

    def _restore_kv(self, req: Request) -> None:
        """Refill fresh blocks with the saved KV when a swap resumes."""
        entry = self.spilled.pop(req.request_id, None)
        if entry is None:
            return   # nothing was spilled (e.g. prefill-side swap, no KV yet)
        k, v, length = entry
        self.kv.write_prefill(req.request_id, jnp.asarray(k), jnp.asarray(v),
                              length)

    # -- transfer hooks (TransferBackend ports; see core/transfer.py) -------------------
    def export_state(self, req: Request):
        """State-path transfer payload (shipped whole, one segment)."""
        return self.export_state_by_id(req.request_id)

    def import_state(self, req: Request, state) -> None:
        self.import_state_by_id(req.request_id, state)

    def export_state_by_id(self, request_id: int):
        return self.states.pop(request_id)

    def import_state_by_id(self, request_id: int, state) -> None:
        self.states[request_id] = state

    def register_transfer_in(self, req: Request, num_tokens: int) -> List[int]:
        """Destination-side block registration ahead of a paged transfer."""
        return self.scheduler.bm.register(req.request_id, num_tokens)

    # -- lifecycle -----------------------------------------------------------------------
    def release(self, req: Request) -> bool:
        """Drop every trace of a request from this node (cancel path).

        Frees KV blocks, removes the request from all scheduler queues and
        discards any state-path pytree. Safe to call on nodes that never saw
        the request. Returns True if anything was released.
        """
        removed = self.scheduler.remove_request(req)
        if self.states.pop(req.request_id, None) is not None:
            removed = True
        return removed

    # -- cycle -----------------------------------------------------------------------
    def step(self, now: Optional[float] = None) -> Tuple[List[Request], List[Request]]:
        """One scheduling cycle. Returns (prefill_done, decode_finished)."""
        decision = self.scheduler.schedule()
        pre = self.run_prefill(decision, now=now) if decision.prefill_batch else []
        fin = self.run_decode(decision) if decision.decode_batch else []
        if not decision.prefill_batch:
            self.scheduler.last_compute_util = 0.0
        if not decision.decode_batch:
            self.scheduler.last_bandwidth_util = 0.0
        return pre, fin
