"""Requests answered by the stacked kernel with a plan that has guarded
terms or fallback rank alternatives (the default read ad's), over all
requests the broker's batched selection answered, in the window:
``batched_kernel_guarded_requests`` over the sum of the
``batched_<tier>_requests`` counters of the five tiers. A program without
the counter reads nothing."""

TIERS = ("kernel", "sparse", "sharded", "columnar", "interp")


def read(run):
    b = run.broker
    if "batched_kernel_guarded_requests" not in b:
        return None
    total = sum(b.get(f"batched_{t}_requests", 0.0) for t in TIERS)
    return 100.0 * b["batched_kernel_guarded_requests"] / total if total else None
