"""Share of its roofline that the P->D transfer kernel reached in the
window, in percent.

The kernel (``repro.kernels.kv_gather.kv_transfer``) moves one page, one
(block, layer, K or V) slice, per grid step from the source pool to the
destination pool, both viewed as ``(pages, rows, lanes)``. In the device
trace a P->D transfer is the custom call
``bf16[P,R,C] custom-call(s32[n], s32[n], bf16[P,R,C], bf16[P,R,C])``: two
page-id tables of ``n`` entries, then the two pools. (The decode step's
append goes through the same kernel with a source of ``n`` rows, not a
pool, and is not counted.)

It does no arithmetic, so its least time is its bytes over the chip's peak
HBM rate: ``n`` pages of ``R * C`` bf16 elements, read once and written
once. The share is the summed least time over the summed device time of
the window's transfer calls.
"""
import re

CALL = re.compile(r"%\S+ = bf16\[(\d+),(\d+),(\d+)\]\{[^}]*\} custom-call\("
                  r"s32\[(\d+)\]\{[^}]*\} [^,]+, s32\[\4\]\{[^}]*\} [^,]+, "
                  r"bf16\[\1,\2,\3\]\{")


def call_bytes(pages, rows, lanes):
    return pages * rows * lanes * 2 * 2


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.kernels(CALL)
    device_s = sum(o.dur for o, _ in calls) * 1e-9
    if device_s <= 0:
        return None
    least = sum(call_bytes(int(m.group(4)), int(m.group(2)), int(m.group(3)))
                for _, m in calls) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / device_s
