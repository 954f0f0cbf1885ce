"""The matchrank kernel's share of its roofline: the least time the chip
could take for the launches' work (``work.py``: the problem's operations
and bytes, not the padded implementation's), over the kernel's device
time in the trace. Nothing is returned where the trace shows no kernel."""

from benchmarks.chip import trace, work

#: the kernel's device operation: the Mosaic custom call of the jitted
#: launch ``matchrank_batched`` makes (the kernel has no name of its own in
#: the trace)
KERNEL = ("_dispatch_batched", "[tpu_custom_call]")


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds = trace.kernel_seconds(run.trace, *KERNEL)
    ops, nbytes = run.kernel_work
    if not seconds or not ops:
        return None
    share, _ = work.roofline_share(ops, nbytes, seconds, run.peaks)
    return share
