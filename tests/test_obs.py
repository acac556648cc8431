"""Observability plane: span tracing, trace capture/replay, calibration,
bench history (``src/repro/obs/``)."""
import dataclasses
import json

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.costmodel import TransportProfile, predicted_ttft_s
from repro.core.transfer import check_bucket
from repro.obs import attach_tracer, read_trace, write_trace
from repro.obs.calibrate import fit_compute, fit_hardware, fit_transport
from repro.obs.history import AREAS, check, check_metrics, load, record
from repro.obs.replay import capture, per_request_stats, replay
from repro.obs.tracing import (COMPILE_EVENT, SPAN_NAMES, STEP_SPAN_NAMES,
                               TRACE_SCHEMA_VERSION, Span, SpanRecorder,
                               detach_tracer, request_record)
from repro.sim.cluster_sim import ClusterSim
from repro.sim.hardware import A100
from repro.sim.workload import SIMULATED, generate


@pytest.fixture(scope="module")
def cfg8b():
    return get_config("llama31-8b")


def _requests(n=10, seed=3):
    wl = dataclasses.replace(SIMULATED["1k"], num_requests=n)
    return generate(wl, rps=2.0, seed=seed)


# -- span schema / JSONL round-trip ------------------------------------------------
def test_span_record_roundtrip_drops_none():
    s = Span(trace_id=7, name="prefill", start_cycle=1.0, end_cycle=3.5,
             node_id=0, attrs={"prompt_len": 64})
    rec = s.to_record()
    assert "start_wall_s" not in rec          # None fields omitted
    assert Span.from_record(rec) == s
    assert s.duration_cycles() == 2.5
    assert s.duration_wall_s() is None


def test_trace_jsonl_roundtrip(tmp_path):
    rec = SpanRecorder()
    for i in range(3):
        rec.emit(i, SPAN_NAMES[i], start_cycle=float(i), end_cycle=i + 1.0,
                 start_wall_s=0.5 * i, end_wall_s=0.5 * i + 0.1,
                 node_id=i % 2, attrs={"k": i})
    reqs = [request_record(i, 0.25 * i, 100 + i, 64) for i in range(3)]
    path = write_trace(tmp_path / "t.jsonl", rec.spans, reqs,
                       meta={"system": "flowkv"})
    trace = read_trace(path)
    assert trace.schema == TRACE_SCHEMA_VERSION
    assert trace.meta["system"] == "flowkv"
    assert trace.requests == reqs
    assert trace.spans == rec.spans
    # header must be the first record and carry a supported schema
    lines = path.read_text().splitlines()
    assert json.loads(lines[0])["kind"] == "header"


def test_read_trace_rejects_bad_schema_and_kind(tmp_path):
    p = tmp_path / "bad_schema.jsonl"
    p.write_text('{"kind": "header", "schema": 999}\n')
    with pytest.raises(ValueError, match="schema"):
        read_trace(p)
    p2 = tmp_path / "bad_kind.jsonl"
    p2.write_text('{"kind": "header", "schema": %d}\n{"kind": "mystery"}\n'
                  % TRACE_SCHEMA_VERSION)
    with pytest.raises(ValueError, match="mystery"):
        read_trace(p2)
    p3 = tmp_path / "headless.jsonl"
    p3.write_text('{"kind": "span", "trace_id": 1, "name": "queue"}\n')
    with pytest.raises(ValueError, match="header"):
        read_trace(p3)


def test_trace_schema_v1_still_reads(tmp_path):
    # v2 added span kinds only — pre-existing v1 captures must keep reading
    p = tmp_path / "v1.jsonl"
    p.write_text('{"kind": "header", "schema": 1}\n'
                 '{"kind": "span", "trace_id": 1, "name": "prefill", '
                 '"start_cycle": 0.0, "end_cycle": 1.0}\n')
    trace = read_trace(p)
    assert trace.schema == 1
    assert len(trace.spans) == 1


def test_chunk_and_layer_window_span_kinds_roundtrip(tmp_path):
    assert "prefill_chunk" in SPAN_NAMES
    assert "transfer_layer_window" in SPAN_NAMES
    rec = SpanRecorder()
    rec.emit(1, "prefill_chunk", start_cycle=0.0, end_cycle=1.0, node_id=0,
             attrs={"offset": 0, "tokens": 32, "prompt_len": 96,
                    "final": False})
    rec.emit(1, "transfer_layer_window", start_cycle=0.5, end_cycle=0.9,
             node_id=0, attrs={"layer_lo": 0, "layer_hi": 8, "hidden": True})
    path = write_trace(tmp_path / "t2.jsonl", rec.spans)
    assert read_trace(path).spans == rec.spans


@pytest.mark.parametrize("schema", [2, 3, 4, 5])
def test_traces_before_span_ids_still_read(tmp_path, schema):
    p = tmp_path / f"v{schema}.jsonl"
    p.write_text('{"kind": "header", "schema": %d}\n'
                 '{"kind": "span", "trace_id": 1, "name": "transfer", '
                 '"start_wall_s": 0.0, "end_wall_s": 1.0}\n' % schema)
    trace = read_trace(p)
    assert trace.schema == schema
    assert trace.spans[0].span_id is None and trace.spans[0].parent_id is None


def test_nested_spans_set_parent_ids_and_roundtrip(tmp_path):
    rec = SpanRecorder()
    with rec.span("cluster.step", cycle=1.0) as step:
        with rec.span("transfer", trace_id=7, node_id=0) as xfer:
            with rec.span("transfer.verify", host_bytes=64) as verify:
                pass
            emitted = rec.emit(7, "transfer_retry")
        with rec.span("decode.step", node_id=1) as decode:
            pass
    assert step.parent_id is None
    assert xfer.parent_id == step.span_id
    assert verify.parent_id == xfer.span_id
    assert emitted.parent_id == xfer.span_id
    assert decode.parent_id == step.span_id
    # trace and node ids default to the parent's (-1 at the top)
    assert (step.trace_id, verify.trace_id, decode.trace_id) == (-1, 7, -1)
    assert (verify.node_id, decode.node_id) == (0, 1)
    assert len({s.span_id for s in rec.spans}) == len(rec.spans) == 5
    assert [s.name for s in rec.children(step)] == ["transfer", "decode.step"]
    assert step.start_wall_s <= xfer.start_wall_s <= verify.start_wall_s \
        <= verify.end_wall_s <= xfer.end_wall_s <= step.end_wall_s
    path = write_trace(tmp_path / "v6.jsonl", rec.spans)
    trace = read_trace(path)
    assert trace.schema == TRACE_SCHEMA_VERSION == 6
    assert trace.spans == rec.spans


def test_span_closes_when_the_work_raises():
    rec = SpanRecorder()
    with pytest.raises(RuntimeError):
        with rec.span("decode.step"):
            raise RuntimeError("device lost")
    assert rec.spans[0].end_wall_s is not None
    with rec.span("cluster.step") as step:
        pass
    assert step.parent_id is None          # the failed span was popped


def test_compiles_charged_to_innermost_open_span():
    import jax
    rec = SpanRecorder()
    rec.listen()
    try:
        jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 0.5)
        with rec.span("prefill_chunk"):
            with rec.span("prefill.forward"):
                jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 1.0)
                jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 2.0)
                jax.monitoring.record_event_duration_secs("/other", 9.0)
    finally:
        rec.unlisten()
    jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 4.0)
    assert rec.compile_by_span() == {
        None: {"count": 1, "seconds": 0.5},
        "prefill.forward": {"count": 2, "seconds": 3.0}}


# -- step spans of the real cluster ----------------------------------------------------
def _served(cfg, params, trace, lengths=(40, 70), new_tokens=4):
    """Serve prompts of ``lengths`` on a 1P+1D cluster; returns the tokens
    and the recorder (None untraced)."""
    from repro.serving.cluster import PDCluster
    from repro.serving.request import Request, SamplingParams
    cluster = PDCluster(cfg, params, num_prefill=1, num_decode=1,
                        num_blocks=64, prefill_chunk_tokens=32)
    rec = attach_tracer(cluster) if trace else None
    rng = np.random.RandomState(0)
    reqs = [Request(prompt_tokens=rng.randint(0, cfg.vocab_size, n).tolist(),
                    sampling=SamplingParams(max_new_tokens=new_tokens))
            for n in lengths]
    cluster.run(reqs, max_cycles=60)
    if trace:
        assert detach_tracer(cluster) is rec
        assert cluster.tracer is None
    assert all(r.num_output == new_tokens for r in reqs)
    return [list(r.output_tokens) for r in reqs], rec


@pytest.fixture(scope="module")
def small_qwen():
    import jax
    from repro.configs import get_smoke_config
    from repro.models.api import get_model
    cfg = get_smoke_config("qwen3-1.7b")
    return cfg, get_model(cfg).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def served_both(small_qwen, tmp_path_factory):
    """The same prompts served untraced, then traced under the profiler;
    counts the profiler annotations and compile listeners each run made."""
    import glob
    import jax
    cfg, params = small_qwen
    made = {"annotations": 0, "listeners": 0}
    real_annotation = jax.profiler.TraceAnnotation
    real_register = jax.monitoring.register_event_duration_secs_listener

    def annotation(name, **kw):
        made["annotations"] += name.startswith("flowkv.")
        return real_annotation(name, **kw)

    def register(fn):
        made["listeners"] += 1
        return real_register(fn)

    mp = pytest.MonkeyPatch()
    mp.setattr(jax.profiler, "TraceAnnotation", annotation)
    mp.setattr(jax.monitoring, "register_event_duration_secs_listener", register)
    try:
        untraced, none = _served(cfg, params, trace=False)
        untraced_made = dict(made)
        log_dir = str(tmp_path_factory.mktemp("profile"))
        jax.profiler.start_trace(log_dir)
        try:
            traced, rec = _served(cfg, params, trace=True)
        finally:
            jax.profiler.stop_trace()
    finally:
        mp.undo()
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[-1]
    return {"untraced": untraced, "untraced_made": untraced_made,
            "traced_made": made, "traced": traced, "rec": rec, "none": none,
            "profile": jax.profiler.ProfileData.from_file(path)}


def test_untraced_cluster_creates_no_spans_or_listeners(served_both):
    assert served_both["none"] is None
    assert served_both["untraced_made"] == {"annotations": 0, "listeners": 0}
    # the same count sees the traced run's annotations and its listener
    spanned = STEP_SPAN_NAMES + ("prefill_chunk", "transfer")
    assert served_both["traced_made"]["annotations"] == sum(
        s.name in spanned for s in served_both["rec"].spans)
    assert served_both["traced_made"]["listeners"] == 1


def test_tracing_does_not_change_served_tokens(served_both):
    assert served_both["traced"] == served_both["untraced"]


def test_step_spans_nest_on_the_cluster(served_both):
    rec = served_both["rec"]
    # a 70-token prompt in 32-token chunks takes suffix chunks, so every
    # step span kind of the paged path appears
    assert set(STEP_SPAN_NAMES) <= {s.name for s in rec.spans}
    by_id = {s.span_id: s for s in rec.spans}
    for child, want in (("decode.prepare", "decode.step"),
                        ("decode.dispatch", "decode.step"),
                        ("decode.readback", "decode.step"),
                        ("decode.step", "cluster.step"),
                        ("prefill.gather_prefix", "prefill_chunk"),
                        ("prefill.forward", "prefill_chunk"),
                        ("prefill.write", "prefill_chunk"),
                        ("prefill.sample", "prefill_chunk"),
                        ("prefill_chunk", "cluster.step"),
                        ("transfer.plan", "transfer"),
                        ("transfer.execute", "transfer"),
                        ("transfer.verify", "transfer"),
                        ("transfer", "cluster.step")):
        assert all(by_id[s.parent_id].name == want
                   for s in rec.by_name(child)), child
    for xfer in rec.by_name("transfer"):
        kids = rec.children(xfer)
        assert [k.name for k in kids] == ["transfer.plan", "transfer.execute",
                                          "transfer.verify"]
        assert xfer.trace_id >= 0
        assert all(k.trace_id == xfer.trace_id for k in kids)
        assert kids[1].attrs["bytes"] == xfer.attrs["bytes"] > 0
        assert kids[1].attrs["pages"] > 0
        # the check reads the moved pages on both sides and one flag back
        pages = kids[1].attrs["pages"]
        assert kids[2].attrs == {"device_bytes": 2 * xfer.attrs["bytes"],
                                 "host_bytes": 1, "pages": pages,
                                 "bucket": check_bucket(pages)}
        assert "est_latency_s" not in xfer.attrs
    for step in rec.by_name("decode.step"):
        dispatch = [k for k in rec.children(step) if k.name == "decode.dispatch"]
        assert dispatch[0].attrs["bucket"][0] >= step.attrs["batch"]
    assert any(c.attrs["new_shape"] for c in rec.by_name("prefill_chunk"))


def _host_events(profile, name):
    return [(line.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events if ev.name == name]


@pytest.mark.parametrize("outer,inner", [
    ("flowkv.decode.step", "flowkv.decode.readback"),
    ("flowkv.transfer", "flowkv.transfer.verify"),
    ("flowkv.cluster.step", "flowkv.prefill_chunk"),
])
def test_step_spans_land_on_the_profiler_host_plane(served_both, outer, inner):
    profile = served_both["profile"]
    outers, inners = _host_events(profile, outer), _host_events(profile, inner)
    assert len(outers) == len(served_both["rec"].by_name(outer[len("flowkv."):]))
    assert len(inners) == len(served_both["rec"].by_name(inner[len("flowkv."):]))
    for line, a, b in inners:
        assert any(ol == line and oa <= a and b <= ob for ol, oa, ob in outers)


def test_new_prefill_shape_compiles_are_charged_to_prefill(small_qwen):
    cfg, params = small_qwen
    # 32 + 11 tokens: the (32, 11) suffix chunk is a shape no other test
    # of this module prefills
    _, rec = _served(cfg, params, trace=True, lengths=(43,), new_tokens=2)
    fresh = [c for c in rec.by_name("prefill_chunk")
             if c.attrs["offset"] == 32 and c.attrs["new_shape"]]
    assert len(fresh) == 1
    lo, hi = fresh[0].start_wall_s, fresh[0].end_wall_s
    inside = [c for c in rec.compiles if lo <= c.end_wall_s <= hi]
    assert inside and all(c.span.startswith("prefill") for c in inside)
    assert sum(c.seconds for c in inside) > 0


# -- sim tracing ---------------------------------------------------------------------
def test_sim_emits_lifecycle_spans(cfg8b):
    sim = ClusterSim(cfg8b, "flowkv", num_prefill=1, num_decode=1)
    rec = attach_tracer(sim)
    reqs = _requests()
    sim.run(reqs, t_max=50_000)
    by_name = {n: rec.by_name(n) for n in ("queue", "prefill", "transfer",
                                           "decode")}
    for name, spans in by_name.items():
        assert len(spans) == len(reqs), name
        for s in spans:
            # sim spans run on the simulated clock only
            assert s.start_wall_s is None and s.end_wall_s is None
            assert s.duration_cycles() is not None
            assert s.duration_cycles() >= 0.0
    # every request's spans are causally ordered: queue ends where prefill
    # starts; decode starts at transfer end
    for r in reqs:
        spans = {s.name: s for s in rec.for_trace(r.request_id)}
        assert spans["queue"].end_cycle == spans["prefill"].start_cycle
        assert spans["transfer"].end_cycle == spans["decode"].start_cycle


def test_sim_emits_layer_window_spans(cfg8b):
    sim = ClusterSim(cfg8b, "flowkv", num_prefill=1, num_decode=1,
                     same_host=False, layer_window=8)
    rec = attach_tracer(sim)
    reqs = _requests(n=4, seed=9)
    sim.run(reqs, t_max=50_000)
    n_layers = sim.kv_spec.num_layers
    windows_per_xfer = -(-n_layers // 8)
    wspans = rec.by_name("transfer_layer_window")
    assert len(wspans) == windows_per_xfer * len(rec.by_name("transfer"))
    for r in reqs:
        ws = sorted((s for s in rec.for_trace(r.request_id)
                     if s.name == "transfer_layer_window"),
                    key=lambda s: s.attrs["layer_lo"])
        xfer = [s for s in rec.for_trace(r.request_id)
                if s.name == "transfer"][0]
        # windows tile the layer axis and the last window lands exactly at
        # the parent transfer span's end (the exposed remainder)
        assert ws[0].attrs["layer_lo"] == 0
        assert ws[-1].attrs["layer_hi"] == n_layers
        for a, b in zip(ws, ws[1:]):
            assert a.attrs["layer_hi"] == b.attrs["layer_lo"]
        assert ws[-1].end_cycle == pytest.approx(xfer.end_cycle)
        # overlap is real: at least one window fully hidden behind prefill
        assert any(s.attrs["hidden"] for s in ws)
        assert xfer.attrs["hidden_s"] > 0.0


# -- replay ---------------------------------------------------------------------------
def test_replay_is_deterministic(cfg8b, tmp_path):
    reqs = _requests()
    sim = ClusterSim(cfg8b, "flowkv", num_prefill=1, num_decode=1)
    path = tmp_path / "cap.jsonl"
    capture(sim, reqs, path=path, meta={"config": "llama31-8b"})
    r1 = replay(path, policy="load_aware")
    r2 = replay(path, policy="load_aware")
    assert r1["per_request"] == r2["per_request"]
    assert r1["stats"] == r2["stats"]
    # and the replay reproduces the ORIGINAL run, not merely itself
    assert r1["per_request"] == per_request_stats(reqs)


def test_replay_policy_changes_schedule_not_workload(cfg8b, tmp_path):
    reqs = _requests(n=8, seed=5)
    sim = ClusterSim(cfg8b, "flowkv", num_prefill=2, num_decode=2)
    path = tmp_path / "cap.jsonl"
    capture(sim, reqs, path=path, meta={"config": "llama31-8b"})
    la = replay(path, policy="load_aware")
    rr = replay(path, policy="round_robin")
    assert la["stats"]["offered"] == rr["stats"]["offered"] == 8
    assert la["policy"] == "load_aware" and rr["policy"] == "round_robin"
    # same request shapes either way
    for rid, row in la["per_request"].items():
        assert row["prompt_len"] == rr["per_request"][rid]["prompt_len"]


def test_replay_rejects_spans_only_trace(tmp_path):
    rec = SpanRecorder()
    rec.emit(1, "queue", start_cycle=0.0, end_cycle=1.0)
    path = write_trace(tmp_path / "spans_only.jsonl", rec.spans)
    with pytest.raises(ValueError, match="request"):
        replay(path)


# -- calibration ----------------------------------------------------------------------
def test_fit_transport_recovers_known_profile():
    truth = TransportProfile(name="truth", per_call_s=75e-6,
                             bandwidth_Bps=20e9, fixed_s=3e-4)
    rng = np.random.RandomState(0)
    samples = [(int(c), int(b), truth.latency(int(c), int(b)))
               for c, b in zip(rng.randint(1, 500, 12),
                               rng.randint(1 << 10, 1 << 28, 12))]
    fit = fit_transport(samples)
    assert fit.per_call_s == pytest.approx(truth.per_call_s, rel=1e-6)
    assert fit.bandwidth_Bps == pytest.approx(truth.bandwidth_Bps, rel=1e-6)
    assert fit.fixed_s == pytest.approx(truth.fixed_s, rel=1e-6)
    with pytest.raises(ValueError, match=">= 3"):
        fit_transport(samples[:2])


def test_fit_hardware_recovers_known_coefficients():
    eff_truth, ovh_truth = 150e9, 2.5e-3
    samples = [(f, ovh_truth + f / eff_truth)
               for f in (1e9, 5e9, 2e10, 1e11)]
    eff, ovh = fit_compute(samples)
    assert eff == pytest.approx(eff_truth, rel=1e-6)
    assert ovh == pytest.approx(ovh_truth, rel=1e-6)
    hw = fit_hardware(samples, base=A100, name="fit")
    # the fitted profile's prefill_time (== predicted_ttft_s) reproduces
    # the samples — calibration lands exactly in the controller's formula
    for f, t in samples:
        assert hw.prefill_time(f) == pytest.approx(t, rel=1e-6)
        assert predicted_ttft_s(0.0, f, hw.peak_flops * hw.mfu_prefill,
                                hw.step_overhead_s) == pytest.approx(t, rel=1e-6)


# -- wall-clock request stats (the satellite bugfix) -----------------------------------
def test_timing_breakdown_has_wall_fields():
    from repro.serving.request import Request
    r = Request(prompt_tokens=[1, 2, 3])
    bd = r.timing_breakdown()
    for key in ("queue_wall_s", "prefill_wall_s", "transfer_wall_s",
                "decode_wall_s", "ttft_wall_s", "e2e_wall_s"):
        assert key in bd and bd[key] is None   # nothing stamped yet
    r.arrival_wall, r.first_token_wall, r.finish_wall = 1.0, 3.5, 7.25
    bd = r.timing_breakdown()
    assert bd["ttft_wall_s"] == 2.5
    assert bd["e2e_wall_s"] == 6.25


# -- bench history ---------------------------------------------------------------------
def test_history_record_and_check(tmp_path):
    m = {"flowkv_calls": 1.0, "flowkv_dispatches": 1.0, "flowkv_wall_s": 0.1}
    record("transfer", m, root=tmp_path)
    data = load("transfer", root=tmp_path)
    assert data["baseline"] == m and len(data["entries"]) == 1
    # identical follow-up: passes
    record("transfer", dict(m), root=tmp_path)
    assert check("transfer", root=tmp_path) == []
    # structural drift: exact metric fails
    record("transfer", {**m, "flowkv_calls": 2.0}, root=tmp_path)
    failures = check("transfer", root=tmp_path)
    assert failures and "flowkv_calls" in failures[0]
    # wall-clock drift alone: informational, never fails
    record("transfer", {**m, "flowkv_wall_s": 99.0}, root=tmp_path)
    assert check("transfer", root=tmp_path) == []


def test_history_modes_le_ge():
    base = {"imbalance_load_aware_goodput": 0.8,
            "imbalance_load_aware_p95_ttft_s": 10.0}
    ok = check_metrics("scenarios", base, {
        "imbalance_load_aware_goodput": 0.79,      # within 2% tolerance
        "imbalance_load_aware_p95_ttft_s": 10.4})  # within 5%
    assert ok == []
    bad = check_metrics("scenarios", base, {
        "imbalance_load_aware_goodput": 0.7,
        "imbalance_load_aware_p95_ttft_s": 12.0})
    assert len(bad) == 2
    # a metric the baseline never saw is not gated; a missing one is
    assert check_metrics("scenarios", base,
                         {"imbalance_load_aware_goodput": 0.8}) != []


def test_history_schema_guard(tmp_path):
    p = tmp_path / "BENCH_transfer.json"
    p.write_text('{"schema": 999, "area": "transfer"}')
    with pytest.raises(ValueError, match="schema"):
        load("transfer", root=tmp_path)
    with pytest.raises(ValueError, match="unknown area"):
        record("nonsense", {}, root=tmp_path)
    assert all(spec.mode in ("exact", "le", "ge", "info")
               for specs in AREAS.values() for spec in specs.values())
