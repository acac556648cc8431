"""Model FLOPs the window's work needed, over the window's seconds times the
chip's peak bf16 rate.

The work is every prefill chunk forwarded and every decode token produced in
the window. A token costs two FLOPs per multiply-add of the weights it
passes through, with a mixture of experts counted at its ``top_k`` experts
(not the all-expert product that dense dispatch computes) and its router;
causal attention over ``n`` keys costs ``4 * heads * head_dim * n`` per layer
(scores and weighted sum). The logits head counts once per prefill call's
last position and once per decode token.
"""


def layer_params(s):
    """Weights one token multiplies through in one layer."""
    d, H, KV, hd, f = s["d_model"], s["heads"], s["kv_heads"], s["head_dim"], s["d_ff"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    if s["family"] == "moe":
        return attn + d * s["experts"] + s["top_k"] * 3 * d * f
    return attn + 3 * d * f


def attn_pairs(offset, chunk):
    """(query, key) pairs of a causal chunk of ``chunk`` queries at ``offset``."""
    return chunk * offset + chunk * (chunk + 1) // 2


def prefill_flops(s, offset, chunk):
    L, H, hd = s["layers"], s["heads"], s["head_dim"]
    return (L * (2 * layer_params(s) * chunk + 4 * H * hd * attn_pairs(offset, chunk))
            + 2 * s["vocab"] * s["d_model"])


def decode_flops(s, cached):
    """One token with ``cached`` tokens already in the cache (it attends to
    those and to itself)."""
    L, H, hd = s["layers"], s["heads"], s["head_dim"]
    return (L * (2 * layer_params(s) + 4 * H * hd * (cached + 1))
            + 2 * s["vocab"] * s["d_model"])


def window_flops(run):
    total = 0
    for span in run.spans_in("prefill"):
        total += sum(prefill_flops(run.shape, off, c) for off, c in span.attrs["work"])
    for span in run.spans_in("decode"):
        total += sum(decode_flops(run.shape, n) for n in span.attrs["lens"])
    return total


def read(run):
    flops = window_flops(run)
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"])
