"""Requests lowered from their snapshot epoch's stored usage-policy row
index, over all requests lowered, in the window: the broker's
``policy_index_reuses`` / (``policy_index_builds`` + ``policy_index_reuses``).
A program without the per-epoch index counts neither and reads nothing."""


def read(run):
    b = run.broker
    if "policy_index_builds" not in b:
        return None
    reuses = b.get("policy_index_reuses", 0.0)
    total = b["policy_index_builds"] + reuses
    return 100.0 * reuses / total if total else None
