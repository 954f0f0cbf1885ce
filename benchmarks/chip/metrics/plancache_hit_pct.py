"""Plan-cache hits (negative hits included) over lookups in the window."""


def read(run):
    pc = run.plan_cache
    hits = pc.get("hits", 0.0) + pc.get("negative_hits", 0.0)
    total = hits + pc.get("misses", 0.0)
    return 100.0 * hits / total if total else None
