"""The candidate-row launch: each request's candidate rows go in, mask and
score at those rows come out, in one packed transfer each way. It must
rank exactly as the dense launch over a ``[B, S]`` admit pre-mask, as the
grouped host evaluation and as the ``ref.py`` oracle, top-k included; a
broker's flushes must reuse the programs a dense warm-up compiled, and
make one put and one fetch a launch."""

import jax
import numpy as np
import pytest

import repro.kernels.matchrank.ops as mr_ops
from repro.core.broker import default_read_request
from repro.core.classads import parse_classad
from repro.core.snapshot import ReplicaSnapshot
from repro.kernels.matchrank.kernel import matchrank_batched_pallas
from repro.kernels.matchrank.ops import (
    NO_ROW,
    _matchrank_batched_dense_host,
    admit_matrix,
    candidate_bucket,
    lower_request,
    matchrank_batched,
    matchrank_candidates,
    stack_plans,
)
from repro.kernels.matchrank.ref import matchrank_batched_ref
from repro.storage.endpoint import build_demo_grid

CLIENT = "client://reader"
MiB = 1 << 20
VOCAB = [
    "availablespace", "avgrdbandwidth", "breakeropentosource", "disktransferrate",
    "ewmardbandwidthtosource", "lastrdbandwidth", "loadfactor", "maxrdbandwidth",
]
S, BLOCK_S = 300, 128  # S_PAD = 384
S_PAD = 384


def _columns(seed, s=S):
    """``s`` rows of attributes that are whole multiples of powers of two
    (exact in f32), a third of the cells Undefined."""
    rng = np.random.default_rng(seed)
    attrs = np.stack([
        rng.integers(0, 64, s) * float(1 << 30), rng.integers(0, 64, s) * float(MiB),
        rng.choice([0.0, 1.0, 0.5], s), rng.integers(1, 6, s) * 2e8,
        rng.integers(-4, 64, s) * float(MiB), rng.integers(0, 64, s) * float(MiB),
        rng.choice([-1.0, 0.0, 0.5, 1.0, 2.0, 7.0], s), rng.integers(0, 64, s) * float(MiB),
    ], 1).astype(np.float32)
    return attrs, rng.random(attrs.shape) > 0.3


def _plain_plans(seed, b):
    """Random threshold requirements that about three in four valid rows
    pass, and linear ranks."""
    rng = np.random.default_rng(seed)
    attrs, _ = _columns(seed)
    quantile = {"<": 75, "<=": 75, ">": 25, ">=": 25, "!=": 50}
    plans = []
    for _ in range(b):
        terms = [
            f"other.{VOCAB[c]} {op} {float(np.percentile(attrs[:, c], quantile[op])):.1f}"
            for c in rng.choice(len(VOCAB), size=int(rng.integers(1, 3)), replace=False)
            for op in [list(quantile)[rng.integers(len(quantile))]]
        ]
        rank = " + ".join(
            f"{int(rng.integers(-3, 4))} * other.{VOCAB[c]}"
            for c in rng.choice(len(VOCAB), size=2, replace=False)
        )
        ad = parse_classad(f"rank = {rank}; requirements = {' && '.join(terms)};")
        plans.append(lower_request(ad, VOCAB))
    return plans


def _default_plans(seed, b):
    """The default read ad's guarded clauses and rank chains, beside an
    analysis plan."""
    kinds = [
        lower_request(default_read_request(CLIENT, min_bandwidth=mb, rank=rk), VOCAB)
        for mb in (0.0, 32.0 * MiB) for rk in ("predicted", "static", "last")
    ]
    kinds.append(lower_request(parse_classad(
        "rank = other.diskTransferRate; requirements = other.loadFactor < 3"), VOCAB))
    return [kinds[(seed + i) % len(kinds)] for i in range(b)]


def _rows(seed, counts, slots=0):
    """Distinct rows a request, ``counts[i]`` of them, with ``slots`` empty
    slots mixed into each non-empty list."""
    rng = np.random.default_rng(seed)
    out = []
    for n in counts:
        rows = [int(r) for r in rng.choice(S, size=n, replace=False)]
        for _ in range(slots if n else 0):
            rows.insert(int(rng.integers(len(rows) + 1)), NO_ROW)
        out.append(rows)
    return out


#: name → (candidate counts a request, empty slots a list, expected C)
CASES = {
    "empty_lists": ([0, 0, 0], 0, 8),
    "some_empty": ([3, 0, 5, 0], 0, 8),
    "padding_slots": ([2, 4, 1], 2, 8),
    "next_bucket": ([3, 11, 6, 2, 9], 1, 16),
    "all_rows": ([S, 7, S], 0, S_PAD),
}


def _dense_admit(rows):
    admit = np.zeros((len(rows), S), np.float32)
    for bi, r in enumerate(rows):
        admit[bi, [x for x in r if x < S]] = 1.0
    return admit


def _dense_kernel(attrs, valid, plans, admit, k):
    """The dense launch: the kernel over a ``[B, S_PAD]`` admit matrix."""
    ap, vp, s_pad = mr_ops.pad_columns(attrs, valid, 128, BLOCK_S)
    bp = stack_plans(plans)
    admit_p = np.zeros((bp.b, s_pad), np.float32)
    admit_p[:, :S] = admit
    mask, score, ts, ti = matchrank_batched_pallas(
        ap, vp, admit_p, bp.sel, bp.op_codes, bp.thresholds, bp.term_role,
        bp.weights, bp.bias, block_s=BLOCK_S, k=k,
    )
    return (np.asarray(mask)[:, :S], np.asarray(score)[:, :S], np.asarray(ti),
            np.asarray(ts))


def _ranking(rows, mask, score):
    """Matched candidates by score, then row — the broker's order."""
    got = [(r, float(score[j])) for j, r in enumerate(rows) if mask[j]]
    return sorted(got, key=lambda rs: (-rs[1], rs[0]))


@pytest.mark.parametrize("plans_of", [_plain_plans, _default_plans], ids=["plain", "default_ads"])
@pytest.mark.parametrize("case", list(CASES))
def test_candidate_launch_matches_dense(case, plans_of):
    counts, slots, c = CASES[case]
    seed = sum(counts) + len(case)
    attrs, valid = _columns(seed)
    plans = plans_of(seed, len(counts))
    rows = _rows(seed, counts, slots)
    admit = _dense_admit(rows)
    k = 3

    cm, cs, ci, ct = matchrank_candidates(
        attrs, valid, plans, rows, k=k, block_s=BLOCK_S, use_kernel=True
    )
    assert cm.shape == cs.shape == (len(rows), c) == (len(rows), candidate_bucket(max(counts) + slots, S_PAD))
    assert cm.any() == (sum(counts) > 0)  # the batch matches somewhere
    dm, ds, di, dt = _dense_kernel(attrs, valid, plans, admit, k)
    hm, hs, hi, ht = _matchrank_batched_dense_host(attrs, valid, stack_plans(plans), admit, S, k)
    bm, bs, bi_, bt = matchrank_batched(attrs, valid, plans, admit=admit, k=k, block_s=BLOCK_S)
    for m, sc in ((dm, ds), (hm, hs), (bm, bs)):
        for bi, r in enumerate(rows):
            n = len(r)
            real = np.array([x < S for x in r], bool)
            at = np.array([x if x < S else 0 for x in r], np.intp)
            np.testing.assert_array_equal(cm[bi, :n], real & m[bi, at])
            want = np.where(real & m[bi, at], sc[bi, at], np.float32(-np.inf))
            np.testing.assert_array_equal(cs[bi, :n].view(np.int32), want.view(np.int32))
            assert not cm[bi, n:].any() and (cs[bi, n:] == -np.inf).all()
            assert _ranking(r, cm[bi], cs[bi]) == _ranking(
                [x for x in r if x < S], m[bi, at[real]], sc[bi, at[real]]
            )
    # the dense wrapper lays the same launch back out over every row
    np.testing.assert_array_equal(bm, dm)
    np.testing.assert_array_equal(bs.view(np.int32), ds.view(np.int32))
    for ti, ts in ((ci, ct), (di, dt), (bi_, bt)):
        np.testing.assert_array_equal(ti, hi)
        np.testing.assert_array_equal(ts.view(np.int32), ht.view(np.int32))
    # the host evaluation answers in the same candidate form
    xm, xs, _, _ = matchrank_candidates(
        attrs, valid, plans, rows, k=k, block_s=BLOCK_S, use_kernel=False
    )
    n = min(c, xm.shape[1])
    np.testing.assert_array_equal(xm[:, :n], cm[:, :n])
    np.testing.assert_array_equal(xs[:, :n].view(np.int32), cs[:, :n].view(np.int32))
    assert not xm[:, n:].any() and not cm[:, n:].any()


def test_candidate_bucket():
    assert [candidate_bucket(n, 15_360) for n in (0, 1, 8, 9, 16, 17, 100)] == [
        8, 8, 8, 16, 16, 32, 128
    ]
    assert candidate_bucket(300, 384) == 384 and candidate_bucket(3, 4) == 4


# ----------------------------------------- top-k against the ref.py oracle
def _oracle(attrs, valid, plans, rows, k):
    """``ref.py``'s batched oracle over the admit matrix the candidate
    lists stand for. → (mask [B,S], score [B,S], topk_idx, topk_scores)."""
    s = attrs.shape[0]
    bp = stack_plans(plans)
    ap, vp, s_pad = mr_ops.pad_columns(attrs, valid, bp.a_pad, BLOCK_S)
    admit = np.zeros((bp.b, s_pad), np.float32)
    admit[:, :s] = admit_matrix(rows, s)
    m, sc, ts, ti = matchrank_batched_ref(
        ap, vp, admit, bp.sel, bp.op_codes, bp.thresholds, bp.term_role,
        bp.weights, bp.bias, k=k,
    )
    return np.asarray(m)[:, :s], np.asarray(sc)[:, :s], np.asarray(ti), np.asarray(ts)


def _launch_matches_oracle(attrs, valid, plans, rows, k):
    """The launch, on the kernel (interpret mode here) and on the host
    evaluation, against the oracle: mask and score at every candidate
    column, and the top-k. → the kernel's (mask, score, idx, scores)."""
    s = attrs.shape[0]
    m, sc, oi, ot = _oracle(attrs, valid, plans, rows, k)
    out = None
    for use_kernel in (True, False):
        cm, cs, ci, ct = matchrank_candidates(
            attrs, valid, plans, rows, k=k, block_s=BLOCK_S, use_kernel=use_kernel
        )
        for bi, r in enumerate(rows):
            real = np.array([x < s for x in r], bool)
            at = np.array([x if x < s else 0 for x in r], np.intp)
            np.testing.assert_array_equal(cm[bi, : len(r)], real & m[bi, at])
            want = np.where(real & m[bi, at], sc[bi, at], np.float32(-np.inf))
            np.testing.assert_array_equal(cs[bi, : len(r)], want)
            assert not cm[bi, len(r) :].any()
        np.testing.assert_array_equal(ci, oi)
        np.testing.assert_array_equal(ct, ot)
        out = out or (cm, cs, ci, ct)
    return out


TOPK_NAMES = ["x"]


class TestCandidateTopK:
    """The cases the rank-order walk was held to, asked of the launch."""

    @pytest.mark.parametrize("s", [5, 257, 1000])
    @pytest.mark.parametrize("k", [1, 3])
    def test_topk_matches_dense(self, s, k):
        seed = s * 10 + k
        rng = np.random.default_rng(seed)
        attrs, valid = _columns(seed, s)
        plans = _plain_plans(seed, 4) + _default_plans(seed, 2)
        rows = [
            [int(r) for r in rng.choice(s, size=int(rng.integers(0, min(s, 8) + 1)), replace=False)]
            for _ in plans
        ]
        _launch_matches_oracle(attrs, valid, plans, rows, k)

    def test_random_admit_as_candidate_lists(self):
        rng = np.random.default_rng(7)
        attrs, valid = _columns(7, 400)
        plans = _plain_plans(7, 4)
        rows = [np.flatnonzero(rng.random(400) > 0.7).tolist() for _ in plans]
        cm, _, _, _ = _launch_matches_oracle(attrs, valid, plans, rows, 2)
        assert cm.shape[1] == candidate_bucket(max(map(len, rows)), 512)

    def test_ne_plan(self):
        """A ``!=`` term, which the rank-order walk could not canonicalize."""
        attrs, valid = _columns(8)
        ne = lower_request(parse_classad(
            "rank = other.avgRDBandwidth; requirements = other.loadFactor != 0.5;"), VOCAB)
        rows = [list(range(0, S, 3)), list(range(1, 40))]
        cm, _, _, _ = _launch_matches_oracle(attrs, valid, [ne, ne], rows, 3)
        lf = VOCAB.index("loadfactor")
        for bi, r in enumerate(rows):
            want = valid[r, lf] & (attrs[r, lf] != 0.5)
            np.testing.assert_array_equal(cm[bi, : len(r)], want)

    def test_absent_attribute_never_matches(self):
        attrs, valid = _columns(9)
        bad = lower_request(parse_classad("requirements = other.noSuchAttr > 1;"), VOCAB)
        ok = lower_request(parse_classad("rank = other.loadFactor; requirements = true;"), VOCAB)
        rows = [list(range(20))] * 2
        cm, _, ci, ct = _launch_matches_oracle(attrs, valid, [bad, ok], rows, 2)
        assert not cm[0].any() and np.isneginf(ct[0]).all()
        assert cm[1, :20].any() and np.isfinite(ct[1]).all()

    def test_strict_op_boundaries(self):
        """``x > 5`` excludes exactly 5.0; ``x >= 5`` includes it."""
        attrs = np.array([[5.0], [np.nextafter(5.0, 6.0, dtype=np.float32)], [4.0]], np.float32)
        valid = np.ones((3, 1), bool)
        gt = lower_request(parse_classad("rank = other.x; requirements = other.x > 5;"), TOPK_NAMES)
        ge = lower_request(parse_classad("rank = other.x; requirements = other.x >= 5;"), TOPK_NAMES)
        cm, _, ci, ct = _launch_matches_oracle(attrs, valid, [gt, ge], [[0, 1, 2]] * 2, 3)
        assert cm[0, :3].tolist() == [False, True, False]
        assert cm[1, :3].tolist() == [True, True, False]
        assert ci[0, :1].tolist() == [1] and ci[1, :2].tolist() == [1, 0]

    def test_constant_rank_ties_to_lowest_rows(self):
        """Every score ties: the top-k names the lowest rows, whatever the
        order the candidate list gives them in."""
        rng = np.random.default_rng(10)
        attrs = np.ones((50, len(VOCAB)), np.float32)
        valid = np.ones((50, len(VOCAB)), bool)
        plan = lower_request(parse_classad("rank = 7; requirements = true;"), VOCAB)
        rows = [rng.permutation(50).tolist(), rng.permutation(np.arange(10, 50)).tolist()]
        _, _, ci, ct = _launch_matches_oracle(attrs, valid, [plan, plan], rows, 4)
        assert ci.tolist() == [[0, 1, 2, 3], [10, 11, 12, 13]]
        assert (ct == 7.0).all()

    def test_update_rows_reaches_next_launch(self):
        """``update_rows`` on a snapshot shows in the next launch over its
        ``device_columns()``."""
        attrs, valid = _columns(11)
        entries = [
            {"endpoint": f"ep{i:03d}", **{n: float(attrs[i, j]) for j, n in enumerate(VOCAB) if valid[i, j]}}
            for i in range(S)
        ]
        snap = ReplicaSnapshot(entries, VOCAB)
        plan = lower_request(parse_classad(
            "rank = other.diskTransferRate; requirements = true;"), snap.vocab_key())
        rows = [[3, 10, 200]]

        def launch(use_kernel):
            a, v, n = snap.device_columns()
            return matchrank_candidates(a, v, [plan], rows, n_rows=n, use_kernel=use_kernel)

        version = snap.version
        for use_kernel in (True, False):
            _, _, ti, _ = launch(use_kernel)
            assert ti[0, 0] != 10 or snap.entries[10].get("disktransferrate") == max(
                snap.entries[r].get("disktransferrate", 0.0) for r in rows[0])
        snap.update_rows({10: {"diskTransferRate": 2.0**40}})
        assert snap.version == version + 1
        for use_kernel in (True, False):
            cm, cs, ti, ts = launch(use_kernel)
            assert ti[0, 0] == 10 and ts[0, 0] == np.float32(2.0**40)
            assert cs[0, 1] == np.float32(2.0**40)


# ------------------------------------------------ the broker's served launch
N_BATCH = 4


def _broker():
    grid = build_demo_grid(12, 3, seed=5)
    grid.add_client(CLIENT, zone="zone1")
    grid.endpoints["gsiftp://ep001"].gris.set_static("requirements", "other.reqdSpace <= 2G")
    lfns = []
    for f in range(N_BATCH):
        lfn = f"lfn-{f}"
        grid.replicate(lfn, bytes([f]) * (1 << 12), [f"gsiftp://ep{(f + j) % 12:03d}" for j in range(3)])
        lfns.append(lfn)
    return grid.broker_for(CLIENT, batch_use_kernel=True), grid, lfns


REQUESTS = [
    parse_classad("reqdSpace = 1G; rank = other.diskTransferRate; requirements = other.availableSpace > 1M;"),
    parse_classad("reqdSpace = 5G; rank = 0 - other.loadFactor; requirements = other.availableSpace > 2M;"),
]


def test_served_launch_reuses_the_warm_programs_and_transfers_once(monkeypatch):
    broker, grid, lfns = _broker()
    for req in REQUESTS:
        req["clientUrl"] = CLIENT
    snap = broker.warm_snapshot(grid.alive_endpoints())
    attrs, valid, n_rows = snap.device_columns()
    plans = [lower_request(r, snap.vocab_key(), env=broker.env) for r in REQUESTS]
    # the benchmark's warm-up: a dense zero admit at every batch size
    for b in range(1, N_BATCH + 1):
        matchrank_batched(
            attrs, valid, [plans[i % len(plans)] for i in range(b)],
            admit=np.zeros((b, n_rows), np.float32), n_rows=n_rows, use_kernel=True,
        )
    programs = mr_ops._dispatch_batched._cache_size()

    compiles = []

    def listen(event, duration, **kw):
        if event in ("/jax/core/compile/jaxpr_trace_duration",
                     "/jax/core/compile/backend_compile_duration"):
            compiles.append(event)

    puts, fetches = [], []
    to_device, to_host = mr_ops._to_device, mr_ops._to_host
    monkeypatch.setattr(mr_ops, "_to_device", lambda x: puts.append(x.shape) or to_device(x))
    monkeypatch.setattr(mr_ops, "_to_host", lambda x: fetches.append(x.shape) or to_host(x))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for b in range(1, N_BATCH + 1):
            puts.clear()
            fetches.clear()
            queries = [(lfns[i], REQUESTS[i % len(REQUESTS)]) for i in range(b)]
            with jax.transfer_guard_host_to_device("disallow"):  # no implicit puts
                got = broker.select_many(queries, strict=False)
            assert broker.explain(broker.last_request_ids[0]).kernel_path == "batched_kernel"
            assert all(not isinstance(r, Exception) for r in got)
            assert len(puts) == 1 and len(fetches) == 1, (puts, fetches)
            assert puts[0][0] == b and fetches[0][0] == b
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert mr_ops._dispatch_batched._cache_size() == programs
    assert compiles == []
    assert broker.stats["kernel_launches"] == N_BATCH
    assert broker.stats["kernel_launches_candidate"] == N_BATCH
    # the policy-refused replica is an empty slot, and stays unmatched
    rec = broker.explain(broker.last_request_ids[1])
    fates = {c.endpoint: c.matched for c in rec.scores}
    assert fates["gsiftp://ep001"] is False
