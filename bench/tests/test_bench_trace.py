"""Trace reduction on a small synthetic trace: busy union, idle share,
idle gaps by host activity, time of the operations a matcher picks."""
from types import SimpleNamespace as NS

import pytest

from harness import trace as T


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def profile():
    # window 1000..11000 ns; device 0 runs [1000,3000) and overlapping
    # [2000,4000), then [8000,9000); an op before the window is ignored.
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", lines=None, events=[ev("jit_step", 1000, 10000)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 0, 500),
            ev("paged_attention_kernel", 1000, 2000, hlo_module="jit_step"),
            ev("fusion.2", 2000, 2000),
            ev("kv_transfer_kernel", 8000, 1000),
        ])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 10000),
        ev("bench.step", 1000, 6000),
        ev("bench.transfer", 4000, 3000),
        ev("bench.wait", 9000, 2000),
        ev("PjitFunction(step)", 1200, 10),
    ])])
    other = NS(name="/device:TPU_NON_CORE:0", lines=[NS(name="XLA Ops", events=[ev("x", 1000, 9000)])])
    return NS(planes=[dev, host, other])


def test_union_merges_overlaps_and_clips():
    assert T.union_ns([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert T.union_ns([(0, 10), (5, 20), (30, 40)], 8, 35) == 17
    assert T.union_ns([], 0, 10) == 0


def test_reduce_window_busy_and_idle():
    red = T.reduce(profile())
    assert (red.t0, red.t1, red.devices) == (1000, 11000, 1)
    assert [o.name for o in red.ops] == ["paged_attention_kernel", "fusion.2", "kv_transfer_kernel"]
    # [1000,4000) + [8000,9000) = 4000 ns busy of a 10000 ns window
    assert red.busy_s() == pytest.approx(4000e-9)
    assert red.window_s == pytest.approx(10000e-9)
    assert 1 - red.busy_s() / red.window_s == pytest.approx(0.6)


def test_idle_gaps_laid_to_innermost_host_annotation():
    red = T.reduce(profile())
    idle = dict(red.idle_by_host())
    # gap [4000,8000): mid 6000 inside bench.step and bench.transfer -> transfer
    # gap [9000,11000): mid 10000 inside bench.wait
    assert idle == pytest.approx({"bench.transfer": 4000e-9, "bench.wait": 2000e-9})
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s())


def test_top_ops_by_summed_time():
    red = T.reduce(profile())
    assert red.top_ops() == [["paged_attention_kernel", pytest.approx(2000e-9)],
                             ["fusion.2", pytest.approx(2000e-9)],
                             ["kv_transfer_kernel", pytest.approx(1000e-9)]]


def test_no_window_annotation_reduces_to_nothing():
    p = profile()
    p.planes[1].lines[0].events = [e for e in p.planes[1].lines[0].events
                                   if e.name != "bench.window"]
    assert T.reduce(p) is None


# Custom calls as the TPU trace names them (operands abbreviated to the
# shapes the readers look at; layouts as the chip prints them).
ATTN = ("%closed_call.8 = (bf16[2,8,2,128]{3,2,1,0:T(2,128)(2,1)S(1)}, "
        "f32[2,8,2]{2,1,0:T(8,128)S(1)}, f32[2,8,2]{2,1,0:T(8,128)S(1)}) "
        "custom-call(s32[2,512]{1,0:T(2,128)S(1)} %gte.1, s32[2]{0:T(128)} %gte.2)")
XFER = ("%fn.1 = bf16[96000,128,128]{2,1,0:T(8,128)(2,1)} custom-call("
        "s32[2304]{0:T(1024)S(1)} %src_pages.1, s32[2304]{0:T(1024)S(1)} %dst_pages.1, "
        "bf16[96000,128,128]{2,1,0:T(8,128)(2,1)} %reshape.3, "
        "bf16[96000,128,128]{2,1,0:T(8,128)(2,1)} %reshape.4), custom_call_target=\"tpu_custom_call\"")
APPEND = ("%_lambda_.1 = bf16[1612800,8,128]{2,1,0:T(8,128)(2,1)} custom-call("
          "s32[112]{0:T(128)S(1)} %iota.7, s32[112]{0:T(128)S(1)} %copy-done.8, "
          "bf16[112,8,128]{2,1,0:T(8,128)(2,1)} %x, bf16[1612800,8,128]{2,1,0:T(8,128)(2,1)} %p)")


def kernel_run(shape, decode_lens):
    from harness.window import Run
    ops = [T.Op(ATTN.split("{")[0], 0, 1000, 0, ATTN),
           T.Op(ATTN.split("{")[0], 2000, 3000, 0, ATTN),
           T.Op(XFER.split("{")[0], 5000, 4000, 0, XFER),
           T.Op(APPEND.split("{")[0], 9000, 500, 0, APPEND),
           T.Op("%fusion.1 = bf16[2]", 9500, 100, 0, "")]
    red = T.Reduced(0, 10000, ops, 1, [])
    span = NS(start=1.0, end=1.5, attrs={"lens": decode_lens})
    return Run(0.0, 10.0, [], {"prefill": [], "decode": [span], "transfer": []}, [],
               shape, {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}, 0.0, red)


def test_reduce_keeps_the_text_of_custom_calls_only():
    p = profile()
    p.planes[0].lines[1].events += [ev(XFER, 1500, 10), ev("%fusion.9 = f32[2] fusion()", 1600, 10)]
    red = T.reduce(p)
    texts = {o.name.split(" ")[0]: o.text for o in red.ops}
    assert texts["%fn.1"] == XFER and texts["%fusion.9"] == ""


def test_paged_attention_roofline_from_trace_and_spans():
    from harness.spec import load_module
    mod = load_module("metrics", "paged_attn_roofline")
    shape = {"family": "dense", "layers": 2, "heads": 16, "kv_heads": 8, "head_dim": 128}
    run = kernel_run(shape, [100, 300])
    call = (8, 2, 128, 0)
    # the two attention events, 4000 ns; the transfer and append calls are not its
    assert sum(o.dur for o, _ in run.trace.kernels(mod.CALL)) == 4000
    # bytes: K,V of 400 tokens, 8 heads of 128, bf16 = 1,638,400; q and out
    # 2 x 16 x 128 x 2 B and m, l 2 x 16 x 4 B, for 2 sequences = 16,640
    assert mod.call_bytes(call, [100, 300]) == 1_638_400 + 16_640
    assert mod.call_flops(call, [100, 300]) == 4 * 16 * 128 * 400
    # bound by bytes at 1e9 B/s: 2 layers x 1,655,040 ns over 4000 ns of device time
    assert mod.read(run) == pytest.approx(100 * 2 * 1_655_040 / 4000)


def test_paged_attention_of_another_shape_is_not_counted():
    from harness.spec import load_module
    mod = load_module("metrics", "paged_attn_roofline")
    run = kernel_run({"family": "dense", "layers": 2, "heads": 16, "kv_heads": 8, "head_dim": 64},
                     [100])
    assert mod.read(run) is None


def test_kv_transfer_roofline_counts_pool_to_pool_calls_only():
    from harness.spec import load_module
    mod = load_module("metrics", "kv_transfer_roofline")
    run = kernel_run({"family": "dense", "layers": 2, "heads": 16, "kv_heads": 8,
                      "head_dim": 128}, [1])
    calls = run.trace.kernels(mod.CALL)
    assert [int(m.group(4)) for _, m in calls] == [2304]
    # 2304 pages of 128 x 128 bf16, read and written: 150,994,944 B at 1e9 B/s
    assert mod.call_bytes(2304, 128, 128) == 150_994_944
    assert mod.read(run) == pytest.approx(100 * 150_994_944 / 4000)


def test_kernel_readers_are_silent_without_a_trace():
    from harness.spec import load_module
    run = kernel_run({"family": "dense", "layers": 2, "heads": 16, "kv_heads": 8,
                      "head_dim": 128}, [1])
    run.trace = None
    for name in ("paged_attn_roofline", "kv_transfer_roofline", "device_idle_frac"):
        assert load_module("metrics", name).read(run) is None
