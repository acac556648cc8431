"""Share of its roofline that the paged decode-attention kernel reached in
the window, in percent.

The kernel (``repro.kernels.paged_attention``) runs once per attention call
of every decode step; the configuration's family module lists a step's calls
(``reference/<family>.py`` ``attention_calls``: KV heads, query group
``G``, head_dim and window of each). In the device trace a call is the
custom call that returns the grouped output and its online-softmax state,
``(bf16[B,KV,G,hd], f32[B,KV,G], f32[B,KV,G])``; a kernel counts where its
``(KV, G, hd)`` is one of the step's calls.

The least time of one call is the larger of its operations over the chip's
peak bf16 rate and its bytes over the peak HBM rate, for the logical work of
the requests in the step, not the padded bucket: a sequence with ``n``
cached tokens (at most the call's window, where it has one) costs
``4 * heads * head_dim * n`` operations (scores and weighted sum) and reads
``n`` tokens of K and V; each sequence's query, output and softmax state are
read or written once. The share is the summed least time of the window's
decode steps, every call of each, over the summed device time of the
kernel's events in the window.
"""
import re
from collections import Counter

CALL = re.compile(r"%\S+ = \(bf16\[(\d+),(\d+),(\d+),(\d+)\]\{[^}]*\}, "
                  r"f32\[\1,\2,\3\]\{[^}]*\}, f32\[\1,\2,\3\]\{[^}]*\}\) custom-call\(")


def seen(call, lens):
    """Cached tokens each sequence's call reads: all, or its window's."""
    window = call[3]
    return [min(n, window) for n in lens] if window else lens


def call_flops(call, lens):
    kv, g, hd, _ = call
    return sum(4 * kv * g * hd * n for n in seen(call, lens))


def call_bytes(call, lens):
    kv, g, hd, _ = call
    heads = kv * g
    kv_bytes = sum(2 * kv * hd * n * 2 for n in seen(call, lens))
    per_seq = 2 * heads * hd * 2 + 2 * heads * 4
    return kv_bytes + len(lens) * per_seq


def least_s(call, lens, peaks):
    return max(call_flops(call, lens) / peaks["bf16_flops_per_s"],
               call_bytes(call, lens) / peaks["hbm_bytes_per_s"])


def kernel(m):
    """(KV, G, hd) of a matched kernel event."""
    return int(m.group(2)), int(m.group(3)), int(m.group(4))


def read(run):
    if run.trace is None:
        return None
    calls = Counter(run.model.attention_calls(run.shape))
    ours = {call[:3] for call in calls}
    device_s = sum(o.dur for o, m in run.trace.kernels(CALL) if kernel(m) in ours) * 1e-9
    steps = run.spans_in("decode")
    if device_s <= 0 or not steps:
        return None
    least = sum(sum(k * least_s(call, s.attrs["lens"], run.peaks) for call, k in calls.items())
                for s in steps)
    return 100.0 * least / device_s
