"""Matchmaking throughput: interpreter vs columnar vs Pallas kernel path.

The paper's §6 claims ClassAds are "an efficient environment for
matching, querying, and ranking". This benchmark quantifies the Match
Phase at fleet scale: one request matched+ranked against S replica ads,

  * interp    — the paper-faithful tree-walking matchmaker,
  * columnar  — the ClassAd→columnar compiler under numpy (f64),
  * kernel    — conjunctive-threshold lowering through the fused
                matchrank kernel (interpret-mode Pallas on CPU; on TPU the
                same call runs compiled — see DESIGN.md §3),
  * batched   — the multi-request launch (DESIGN.md §4): B = 64 requests
                vs one resident snapshot, answered by the host evaluation
                the CPU runs in place of the kernel
                (``match_batched_dense_b64_s10k``).

Rows: (name, µs/call, derived = matches/sec per 1k candidates — for
batched rows, request·candidates/sec; for speedup rows, the ratio).
Every figure is a host-CPU timing, not a device number.
"""

import time

import numpy as np

from repro.core.broker import ReplicaView
from repro.core.catalog import PhysicalFile
from repro.core.classads import ClassAd, parse_classad
from repro.core.compile import vectorized_match
from repro.core.ldif import entry_to_classad
from repro.core.matchmaker import Matchmaker
from repro.kernels.matchrank.ops import lower_request, matchrank

REQUEST_SRC = """
reqdSpace = 5G;
rank = other.AvgRDBandwidth;
requirements = other.availableSpace > 5G && other.MaxRDBandwidth >= 50K;
"""

NAMES = ["availablespace", "maxrdbandwidth", "avgrdbandwidth", "loadfactor"]


def make_world(s, seed=0):
    rng = np.random.default_rng(seed)
    attrs = np.stack(
        [
            rng.uniform(0, 20 * 1024**3, s),
            rng.uniform(0, 200 * 1024, s),
            rng.uniform(0, 100e6, s),
            rng.uniform(0, 8, s),
        ],
        axis=1,
    ).astype(np.float32)
    valid = np.ones((s, 4), bool)
    views = []
    for i in range(s):
        entry = {"endpoint": f"ep{i:05d}"}
        entry.update({n: float(attrs[i, j]) for j, n in enumerate(NAMES)})
        views.append(ReplicaView(PhysicalFile(entry["endpoint"], "/p", 1), entry,
                                 entry_to_classad(entry)))
    return attrs, valid, views


def _time(fn, reps, *, tol=0.25, max_warm=8):
    """Warm until two consecutive calls agree within ``tol`` (relative),
    so jit compilation / cache-fill time can't leak into the first timed
    rep on fresh shapes; bounded by ``max_warm`` calls for noisy-fast fns."""
    prev = None
    for _ in range(max_warm):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        if prev is not None and abs(dt - prev) <= tol * max(dt, prev):
            break
        prev = dt
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6  # µs


def run():
    rows = []
    request = parse_classad(REQUEST_SRC)
    for s in (100, 1000, 10000):
        attrs, valid, views = make_world(s)
        mm = Matchmaker()
        ads = [v.ad for v in views]
        reps = max(2, 2000 // s)

        us_i = _time(lambda: mm.match(request, ads, require_symmetric=False), reps)
        # cold columnar: compile + build columns + match, per call
        us_c = _time(lambda: vectorized_match(request, views), reps)
        # steady state: the fleet scenario — columns are built once per
        # GRIS/GIIS snapshot and the compiled program is reused across
        # many selections (one per shard fetch)
        from repro.core.compile import build_columns, compile_program
        present = {n for v in views for n in (k.lower() for k in v.entry)}
        prog = compile_program(request, column_names=lambda n: n in present)
        tbl = build_columns([v.entry for v in views], sorted(present))
        import numpy as _np

        def steady():
            mask, rank = prog.run(tbl, _np)
            return int(_np.argmax(_np.where(mask, rank, -_np.inf)))

        us_w = _time(steady, max(reps, 20))
        plan = lower_request(request, NAMES)
        us_k = _time(lambda: matchrank(attrs, valid, plan), max(reps, 10))

        rows.append((f"match_interp_s{s}", us_i, s / us_i * 1e6))
        rows.append((f"match_columnar_cold_s{s}", us_c, s / us_c * 1e6))
        rows.append((f"match_columnar_steady_s{s}", us_w, s / us_w * 1e6))
        # kernel timing on CPU is interpret-mode (Python per-block) —
        # reported for completeness; the perf claim is the columnar path,
        # which is the same program the kernel runs compiled on TPU.
        rows.append((f"match_kernel_interpret_s{s}", us_k, s / us_k * 1e6))
        rows.append((f"match_speedup_steady_vs_interp_s{s}", 0.0, us_i / us_w))

    # ---- batched launch: snapshot + plan cache, host evaluation ----
    # The fleet scenario (DESIGN.md §4): B concurrent requests answered
    # against ONE resident snapshot. Snapshot build and plan lowering
    # happen once per GRIS epoch / request shape, so they sit outside the
    # timed region, like the steady columnar row.
    from repro.core.plancache import PlanCache
    from repro.core.snapshot import ReplicaSnapshot
    from repro.kernels.matchrank.ops import matchrank_batched

    s = 10000
    _, _, views = make_world(s)
    snap = ReplicaSnapshot([v.entry for v in views])
    pc = PlanCache()
    plans64 = [
        pc.kernel_plan(
            parse_classad(REQUEST_SRC.replace("5G", f"{4 + i % 4}G")),
            snap.vocab_key(),
        )
        for i in range(64)
    ]
    da, dv, dn = snap.device_columns()

    def dense():
        return matchrank_batched(da, dv, plans64, n_rows=dn, k=1, use_kernel=False)

    us_d = _time(dense, 2, max_warm=3)
    rows.append(("match_batched_dense_b64_s10k", us_d, 64 * s / us_d * 1e6))

    # LDIF→ClassAd conversion throughput (the §6 'not cumbersome' claim)
    _, _, views = make_world(1000, seed=1)
    entries = [v.entry for v in views]
    us = _time(lambda: [entry_to_classad(e) for e in entries], 5)
    rows.append(("ldif_to_classad_1k", us, 1000 / us * 1e6))
    return rows
