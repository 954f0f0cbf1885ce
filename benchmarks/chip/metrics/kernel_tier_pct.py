"""Requests answered by the stacked kernel over all requests the broker's
batched selection answered, in the window: ``batched_kernel_requests`` over
the sum of the ``batched_<tier>_requests`` counters of the five tiers."""

TIERS = ("kernel", "sparse", "sharded", "columnar", "interp")


def read(run):
    b = run.broker
    total = sum(b.get(f"batched_{t}_requests", 0.0) for t in TIERS)
    return 100.0 * b.get("batched_kernel_requests", 0.0) / total if total else None
