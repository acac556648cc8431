"""Drive the program through its front door and record what happened.

The system under test is the program's own serving entry:
``FlowKVClient.submit`` / ``step`` over a ``PDCluster`` with one prefill and
one decode ``NodeEngine`` on the chip. The benchmark adds only its own spans:
it wraps the bound methods ``run_prefill`` and ``run_decode`` of each engine
and ``_transfer`` of the cluster on the instances it built, blocks on the
pool before stamping a span's end, and names each span for the profiler
(``jax.profiler.TraceAnnotation``) so that idle device time can be laid to
what the host was doing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from harness import traffic as T

clock = time.monotonic


@dataclasses.dataclass
class Span:
    start: float
    end: float
    attrs: Dict[str, Any]


@dataclasses.dataclass
class Record:
    """One request as the client saw it."""
    planned: T.Planned
    handle: Any
    due: float                  # open loop: when it was due; closed: sent
    sent: float
    deliveries: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    seen: int = 0

    @property
    def request(self):
        return self.handle.request


class Recorder:
    """Spans of the wrapped layer boundaries and compile events, on the host
    clock (``time.monotonic``)."""

    def __init__(self):
        self.spans: Dict[str, List[Span]] = {"prefill": [], "decode": [], "transfer": []}
        self.compiles: List[Tuple[float, float]] = []     # (end, seconds)
        self.misses: List[float] = []     # persistent-cache misses: real compiles
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_miss)

    def _on_event(self, event: str, duration: float, **_) -> None:
        # one event per backend compile request, a persistent-cache load included
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((clock(), duration))

    def _on_miss(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses.append(clock())

    def add(self, kind: str, start: float, end: float, **attrs) -> None:
        self.spans[kind].append(Span(start, end, attrs))

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        jax.monitoring.unregister_event_listener(self._on_miss)


def instrument(client, rec: Recorder) -> None:
    """Wrap the layer boundaries of ``client``'s cluster with spans."""
    cluster = client.cluster
    for engine in cluster.engines.values():
        _wrap_engine(engine, rec)
    inner = cluster._transfer

    def transfer(req):
        src = cluster.engines[req.prefill_node]
        blocks = len(src.scheduler.bm.get(req.request_id))
        with jax.profiler.TraceAnnotation("bench.transfer"):
            t0 = clock()
            inner(req)
            jax.block_until_ready([e.kv.pool for e in cluster.engines.values()])
            t1 = clock()
        rec.add("transfer", t0, t1, blocks=blocks, request_id=req.request_id)

    cluster._transfer = transfer


def _wrap_engine(engine, rec: Recorder) -> None:
    run_prefill, run_decode = engine.run_prefill, engine.run_decode

    def prefill(decision, now=None):
        work = []
        for req in decision.prefill_batch:
            off = engine.scheduler.prefill_tokens_done(req)
            left = req.prompt_len - off
            chunk = min(decision.prefill_chunks.get(req.request_id, left), left)
            if chunk > 0:
                work.append((off, chunk))
        with jax.profiler.TraceAnnotation("bench.prefill"):
            t0 = clock()
            out = run_prefill(decision, now=now)
            jax.block_until_ready(engine.kv.pool)
            t1 = clock()
        rec.add("prefill", t0, t1, work=work, node=engine.node_id)
        return out

    def decode(decision):
        lens = [r.total_len - 1 for r in decision.decode_batch]
        with jax.profiler.TraceAnnotation("bench.decode"):
            t0 = clock()
            out = run_decode(decision)
            jax.block_until_ready(engine.kv.pool)
            t1 = clock()
        if lens:
            rec.add("decode", t0, t1, lens=lens, node=engine.node_id)
        return out

    engine.run_prefill = prefill
    engine.run_decode = decode


# -- warm-up -----------------------------------------------------------------------
def _dummy(prompt_len: int):
    from repro.serving.request import Request, SamplingParams
    return Request(prompt_tokens=[0] * prompt_len,
                   sampling=SamplingParams(max_new_tokens=1 << 30))


def warm_decode(engine, batch: int, width: int) -> None:
    """One decode step in the (batch, table-width) bucket: one stand-in
    request ``width // 2 + 1`` blocks long, the others one block."""
    from repro.core.scheduler.hybrid_scheduler import ScheduleDecision
    bs = engine.cfg.block_size
    bm = engine.scheduler.bm
    reqs = []
    for i in range(batch):
        blocks = width // 2 + 1 if i == 0 and width > 1 else 1
        req = _dummy(blocks * bs - 2)
        req.output_tokens = [0]
        req.block_ids = bm.allocate(req.request_id, blocks * bs)
        reqs.append(req)
    engine.run_decode(ScheduleDecision(kind="decode", decode_batch=reqs))
    for req in reqs:
        bm.free(req.request_id)


def pow2_range(lo: int, hi: int) -> List[int]:
    """The power-of-two buckets that sizes from ``lo`` to ``hi`` fall in."""
    up = lambda n: 1 << max(0, n - 1).bit_length()
    out, p = [], up(lo)
    while p <= up(hi):
        out.append(p)
        p *= 2
    return out


def warm_up(client, mix: Dict[str, Any], max_batch: int) -> Dict[str, int]:
    """Compile (or load from the persistent cache) what a deployment knows
    before its first request: the decode step's (batch, table-width)
    buckets, from the shortest prompt to the longest request of the mix.

    Prefill and transfer shapes follow the prompt lengths, which no
    deployment knows in advance: they compile, or load from the persistent
    cache, when the traffic first needs them, in the lead-in or the window,
    where ``compiles_in_window`` counts them."""
    dst = client.cluster.engines[1]
    bs = dst.cfg.block_size
    shortest = int(mix["prompt_clip"][0]) + 2
    widths = pow2_range(-(-shortest // bs), -(-(T.longest_total(mix) + 1) // bs))
    batches = pow2_range(1, max_batch)
    for b in batches:
        for w in widths:
            warm_decode(dst, b, w)
    return {"decode_buckets": len(batches) * len(widths)}


# -- the load generator -------------------------------------------------------------
class LoadGenerator:
    """Sends the mix's replay set through the client and records deliveries.

    Open loop: a request is sent once its due time has passed and is timed
    from when it was due. Closed loop: each of ``clients`` callers sends its
    next request as soon as its previous one finished, timed from the send.
    Tokens are stamped with the wall time at which ``client.step()`` returned
    them.
    """

    def __init__(self, client, mix: Dict[str, Any], seed: int, vocab: int,
                 clients: int = 0):
        self.client = client
        self.mix = mix
        self.open = mix["loop"] == "open"
        self.planned = T.plan(mix)
        self.seed, self.vocab = seed, vocab
        self.clients = clients
        self.next = 0
        self.records: List[Record] = []
        self.active: Dict[int, Record] = {}
        self.slots: Dict[int, Optional[Record]] = {c: None for c in range(clients)}
        self.first_of: Dict[int, Record] = {}
        self.start: Optional[float] = None
        self.lateness: List[float] = []

    def _send(self, due: Optional[float]) -> Record:
        from repro.serving.request import SamplingParams
        if self.next >= len(self.planned):
            raise RuntimeError("the mix's replay set ran out; raise its 'requests'")
        p = self.planned[self.next]
        self.next += 1
        tokens = T.prompt_tokens(self.seed, p, self.vocab)
        sent = clock()
        handle = self.client.submit(tokens, SamplingParams(max_new_tokens=p.output_len))
        rec = Record(p, handle, due=sent if due is None else due, sent=sent)
        if due is not None:
            self.lateness.append(sent - due)
        self.records.append(rec)
        self.active[handle.request_id] = rec
        return rec

    def _admit(self) -> None:
        if self.start is None:
            self.start = clock()
        if self.open:
            while self.next < len(self.planned):
                due = self.start + self.planned[self.next].due_s
                if due > clock():
                    break
                self._send(due)
        else:
            for c, rec in self.slots.items():
                if rec is None:
                    self.slots[c] = self._send(None)
                    self.first_of.setdefault(c, self.slots[c])

    def step(self, deadline: float) -> None:
        self._admit()
        if not self.active:
            if self.open and self.next < len(self.planned):
                wait = self.start + self.planned[self.next].due_s - clock()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(wait, deadline - clock())))
            return
        with jax.profiler.TraceAnnotation("bench.step"):
            self.client.step()
        self._observe(clock())

    def _observe(self, t: float) -> None:
        for rid, rec in list(self.active.items()):
            n = rec.request.num_output
            if n > rec.seen:
                rec.deliveries.append((t, n - rec.seen))
                rec.seen = n
            if rec.handle.done:
                rec.finished = t
                del self.active[rid]
                for c, r in self.slots.items():
                    if r is rec:
                        self.slots[c] = None

    def lead_in(self, max_s: float) -> None:
        """Open loop: run the schedule for ``lead_in_s``. Closed loop: until
        every caller has had its first token."""
        t0 = clock()
        limit = t0 + max_s
        if self.open:
            end = t0 + float(self.mix.get("lead_in_s", 0.0))
            while clock() < end:
                self.step(end)
            return
        while clock() < limit:
            self._admit()
            if len(self.first_of) == self.clients and all(
                    r.deliveries for r in self.first_of.values()):
                return
            self.step(limit)
        raise RuntimeError(f"lead-in did not finish within {max_s} s")

    def run(self, seconds: float) -> Tuple[float, float]:
        """The measured window: returns its (start, end) on the host clock."""
        w0 = clock()
        end = w0 + seconds
        with jax.profiler.TraceAnnotation("bench.window"):
            while clock() < end:
                self.step(end)
        return w0, clock()

    def until_finished(self, since: float, max_s: float) -> None:
        """Step on until some request finished after ``since`` (at most
        ``max_s`` seconds): the check needs at least one finished request."""
        limit = clock() + max_s
        while clock() < limit and not any(
                r.finished is not None and r.finished >= since for r in self.records):
            self.step(limit)
