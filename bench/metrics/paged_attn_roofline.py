"""Share of its roofline that the paged decode-attention kernel reached in
the window, in percent.

The kernel (``repro.kernels.paged_attention``) runs once per layer in every
decode step. In the device trace it is the custom call that returns the
grouped output and its online-softmax state,
``(bf16[B,KV,G,hd], f32[B,KV,G], f32[B,KV,G])``, with the configuration's
KV heads, query group ``G = heads / KV`` and head_dim.

The least time of one call is the larger of its operations over the chip's
peak bf16 rate and its bytes over the peak HBM rate, for the logical work of
the requests in the step, not the padded bucket: a sequence with ``n``
cached tokens costs ``4 * heads * head_dim * n`` operations (scores and
weighted sum) and reads ``n`` tokens of K and V; each sequence's query,
output and softmax state are read or written once. The share is the summed
least time of the window's decode steps, ``layers`` calls each, over the
summed device time of the kernel's events in the window.
"""
import re

CALL = re.compile(r"%\S+ = \(bf16\[(\d+),(\d+),(\d+),(\d+)\]\{[^}]*\}, "
                  r"f32\[\1,\2,\3\]\{[^}]*\}, f32\[\1,\2,\3\]\{[^}]*\}\) custom-call\(")


def call_flops(s, lens):
    return sum(4 * s["heads"] * s["head_dim"] * n for n in lens)


def call_bytes(s, lens):
    kv = sum(2 * s["kv_heads"] * s["head_dim"] * n * 2 for n in lens)
    per_seq = 2 * s["heads"] * s["head_dim"] * 2 + 2 * s["heads"] * 4
    return kv + len(lens) * per_seq


def least_s(s, lens, peaks):
    return max(call_flops(s, lens) / peaks["bf16_flops_per_s"],
               call_bytes(s, lens) / peaks["hbm_bytes_per_s"])


def ours(s, m):
    kv, g, hd = int(m.group(2)), int(m.group(3)), int(m.group(4))
    return (kv, g, hd) == (s["kv_heads"], s["heads"] // s["kv_heads"], s["head_dim"])


def read(run):
    if run.trace is None:
        return None
    device_s = sum(o.dur for o, m in run.trace.kernels(CALL) if ours(run.shape, m)) * 1e-9
    steps = run.spans_in("decode")
    if device_s <= 0 or not steps:
        return None
    least = sum(run.shape["layers"] * least_s(run.shape, s.attrs["lens"], run.peaks)
                for s in steps)
    return 100.0 * least / device_s
