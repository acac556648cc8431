"""Model FLOPs the window's work needed, over the window's seconds times the
chip's peak bf16 rate.

The work is every prefill chunk forwarded and every decode token produced in
the window, counted by the configuration's family module
(``reference/<family>.py`` ``prefill_flops`` and ``decode_flops``). A token
costs two FLOPs per multiply-add of the weights it passes through, with a
mixture of experts counted at its ``top_k`` experts (not the all-expert
product that dense dispatch computes) and its router; causal attention over
``n`` keys costs ``4 * heads * head_dim * n`` per layer (scores and weighted
sum). The logits head counts once per prefill call's last position and once
per decode token.
"""


def window_flops(run):
    model, s = run.model, run.shape
    total = 0
    for span in run.spans_in("prefill"):
        total += sum(model.prefill_flops(s, off, c) for off, c in span.attrs["work"])
    for span in run.spans_in("decode"):
        total += sum(model.decode_flops(s, n) for n in span.attrs["lens"])
    return total


def read(run):
    flops = window_flops(run)
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"])
