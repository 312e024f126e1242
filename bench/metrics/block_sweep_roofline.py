"""The block sweep kernel's share of its roofline, in percent.

The kernel is bound by HBM bandwidth (its segmented sum needs one add per
message; see ``bench/roofline.py``), so its least time is the bytes its
calls move over the chip's HBM bandwidth. Each call's bytes come from its
compiled instruction, as the trace event names it (operands read once,
result written once): the dispatch buckets compile the kernel at
different shapes. The time is the kernel events' device time in the
traced stretch. Nothing is returned when the trace holds no call.
"""
from bench import harness, roofline


def read(run):
    tr = run.trace
    if tr is None or not run.jobs:
        return None
    byts = secs = 0.0
    for text, calls in tr.kernel_calls.items():
        (b,) = roofline.kernel_call_bytes(text, harness.KERNEL).values()
        byts += calls * b
        secs += tr.kernel_s[text]
    if secs <= 0:
        return None
    return 100.0 * byts / run.peaks["hbm_bytes_per_s"] / secs
