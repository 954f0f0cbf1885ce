"""Snapshot rows whose ClassAd was made, on its first read, over the rows
put into snapshots, in the window: the broker's ``snapshot_ads_built`` /
``snapshot_rows``. A program without the counters reads nothing."""


def read(run):
    b = run.broker
    if "snapshot_rows" not in b:
        return None
    rows = b["snapshot_rows"]
    return 100.0 * b.get("snapshot_ads_built", 0.0) / rows if rows else None
