"""The broker's default read ad on the kernel tier: lowering, the kernel
against the paper-faithful interpreter on grids with transfer history,
site averages and circuit breakers, and the fallbacks that stay."""

import numpy as np
import pytest

from repro.core.broker import default_read_request
from repro.core.classads import parse_classad
from repro.core.compile import (
    CompileError,
    extract_conjunctive_terms,
    extract_rank_alternatives,
)
from repro.kernels.matchrank.ops import lower_request, matchrank_batched, stack_plans
from repro.storage.endpoint import build_demo_grid

CLIENT = "client://reader"
#: the kernel ranks in f32, the interpreter in f64: a rank may differ by
#: the f32 rounding of its inputs and of one division (a few 1e-8)
RANK_RTOL = 1e-6
#: loadFactor draws; -1 makes the static branch's denominator 0
LOADS = np.array([-1.0, -0.5, 0.0, 0.5, 1.0, 2.0, 3.0, 7.0])
MiB = 1 << 20
VOCAB = [
    "availablespace", "avgrdbandwidth", "breakeropentosource", "disktransferrate",
    "ewmardbandwidthtosource", "lastrdbandwidth", "loadfactor", "maxrdbandwidth",
]


def _branch(entry):
    """Which branch of the predicted rank a published entry takes."""
    for k in ("EwmaRDBandwidthToSource", "AvgRDBandwidth"):
        v = entry.get(k)
        if isinstance(v, (int, float)) and v > 0:
            return k
    return "static"


def history_grid(n, seed, files=24, replicas=24):
    """``build_demo_grid`` (five disk rates, a usage policy on every third
    site) with, per endpoint, seeded draws of: this client's per-source
    EWMA (positive, zero, negative, a source entry without it, no entry),
    the site summary (average positive, zero or negative, with
    MaxRDBandwidth; or no summary), the breaker this client published
    (0, 0.5, 1 or none) and loadFactor from ``LOADS``. Values are whole
    multiples of powers of two, exact in f32. → (grid, lfns, entries)."""
    rng = np.random.default_rng(seed)
    grid = build_demo_grid(n, 8, seed=seed)
    grid.add_client(CLIENT, zone="zone0")
    urls = list(grid.endpoints)
    entries = {}
    for url in urls:
        ep = grid.endpoints[url]
        ep.active_transfers = float(LOADS[rng.integers(len(LOADS))])  # published as loadFactor
        e = {}
        kind = rng.choice(["pos", "zero", "neg", "no_ewma", "none"], p=[0.35, 0.1, 0.1, 0.1, 0.35])
        if kind != "none":
            src = {"lastRDBandwidth": float(rng.integers(1, 4096) * MiB), "lastRDurl": "",
                   "lastWRBandwidth": 0.0, "lastWRurl": ""}
            if kind != "no_ewma":
                src["EwmaRDBandwidthToSource"] = {
                    "pos": float(rng.integers(1, 2048) * MiB), "zero": 0.0,
                    "neg": -float(rng.integers(1, 64) * MiB)}[kind]
            ep.gris.publish_source_bandwidth(CLIENT, src)
            e.update(src)
        kind = rng.choice(["pos", "zero", "neg", "none"], p=[0.35, 0.1, 0.1, 0.45])
        if kind != "none":
            avg = {"pos": float(rng.integers(1, 2048) * MiB), "zero": 0.0,
                   "neg": -float(rng.integers(1, 64) * MiB)}[kind]
            summary = {"MaxRDBandwidth": float(rng.integers(0, 2048) * MiB),
                       "MinRDBandwidth": 0.0, "AvgRDBandwidth": avg,
                       "MaxWRBandwidth": 0.0, "MinWRBandwidth": 0.0, "AvgWRBandwidth": 0.0}
            ep.gris.publish_bandwidth_summary(summary)
            e.update(summary)
        breaker = rng.choice([0.0, 0.5, 1.0, None], p=[0.1, 0.1, 0.1, 0.7])
        if breaker is not None:
            ep.gris.publish_source_health(CLIENT, {"breakerOpenToSource": float(breaker)})
            e["breakerOpenToSource"] = float(breaker)
        entries[url] = e
    lfns = [f"lfn-{f:03d}" for f in range(files)]
    from repro.core.catalog import PhysicalFile

    for lfn in lfns:
        for i in rng.choice(n, size=min(replicas, n), replace=False):
            grid.catalog.register_replica(lfn, PhysicalFile(urls[i], f"/d/{lfn}", 1 << 20))
    return grid, lfns, entries


def _ranking(result):
    return [(rr.pfn.endpoint, float(rr.rank)) for rr in result]


def _assert_same(got, want):
    """Identical rankings (order and membership) and ranks within rtol."""
    assert [u for u, _ in got] == [u for u, _ in want]
    np.testing.assert_allclose(
        [r for _, r in got], [r for _, r in want], rtol=RANK_RTOL, atol=0.0
    )


def _no_near_ties(want):
    """The data leaves no two distinct interpreter ranks within f32
    rounding of each other, so the rankings must agree exactly."""
    ranks = sorted({r for _, r in want})
    for a, b in zip(ranks, ranks[1:]):
        assert b - a > RANK_RTOL * max(abs(b), 1.0), (a, b)


# ------------------------------------------------------------------ lowering
@pytest.mark.parametrize("rank", ["predicted", "static", "last"])
@pytest.mark.parametrize("min_bw", [0.0, 512.0 * MiB])
def test_default_read_ad_lowers(rank, min_bw):
    plan = lower_request(default_read_request(CLIENT, min_bandwidth=min_bw, rank=rank), VOCAB)
    assert not plan.plain  # the breaker clause lets an Undefined breaker pass
    act = plan.term_role > 0
    n_req = int((plan.term_role == 1).sum())
    assert n_req == (2 if min_bw > 0 else 1)  # the bandwidth gate folds away at 0
    assert int((plan.term_role > 1).sum()) == (2 if rank == "predicted" else 0)
    assert (plan.op_codes[plan.term_role == 1] >= 8).all()  # both clauses are guarded
    assert act.sum() <= plan.t_pad == 16  # the shapes every plan has
    assert plan.weights.shape == (6, 128) and plan.bias.shape == (6,)


def test_rank_chain_alternatives():
    req = default_read_request(CLIENT)
    alts = extract_rank_alternatives(req["rank"], req)
    assert [len(a.gate) for a in alts] == [1, 1, 0]
    assert [(t.attr, t.op, t.threshold) for a in alts for t in a.gate] == [
        ("ewmardbandwidthtosource", ">", 0.0), ("avgrdbandwidth", ">", 0.0)]
    assert alts[2].num == {"disktransferrate": 1.0}
    assert alts[2].den == {"": 1.0, "loadfactor": 1.0}


@pytest.mark.parametrize(
    "ad",
    [
        # a general || between two attributes
        "requirements = other.loadFactor < 2 || other.availableSpace > 5; rank = other.diskTransferRate",
        # a gate that is not guarded: Undefined would make the rank Undefined
        "rank = ifThenElse(other.AvgRDBandwidth > 0, other.AvgRDBandwidth, other.diskTransferRate)",
        # a value that is not linear
        "rank = other.diskTransferRate * other.loadFactor",
        # a guard on one attribute, a threshold on another
        "requirements = isUndefined(other.a) || other.b > 1",
        # a chain longer than the rank slots
        "rank = ifThenElse(!isUndefined(other.a) && other.a > 0, other.a,"
        " ifThenElse(!isUndefined(other.b) && other.b > 0, other.b,"
        " ifThenElse(!isUndefined(other.c) && other.c > 0, other.c, other.d)))",
    ],
)
def test_richer_ads_still_raise(ad):
    with pytest.raises(CompileError):
        lower_request(parse_classad(ad), VOCAB + ["a", "b", "c", "d"])


def test_guarded_clause_shapes():
    req = parse_classad("r = 3; requirements = (isUndefined(other.x) || my.r <= 0 || other.x >= my.r)"
                        " && (other.y < 1 || my.r > 2)")
    (t,) = extract_conjunctive_terms(req["requirements"], req)  # the y clause folds to true
    assert (t.attr, t.op, t.threshold, t.undefined_passes) == ("x", ">=", 3.0, True)
    req = parse_classad("requirements = isUndefined(other.x) || false")
    (t,) = extract_conjunctive_terms(req["requirements"], req)
    assert t.undefined_passes and t.threshold == float("-inf")  # only Undefined passes


# -------------------------------------------------------- kernel parity
@pytest.mark.parametrize("n,seed", [(64, 1), (512, 2)])
@pytest.mark.parametrize("shards", [0, 2])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_default_ads_match_interpreter(n, seed, shards, use_kernel):
    """select_many on the kernel tier (the Pallas kernel in interpret mode,
    or the host evaluator) against the interpreter's select, for the
    default read ad and its variants, flat and over a 2-shard snapshot."""
    grid, lfns, entries = history_grid(n, seed)
    served = grid.broker_for(CLIENT, batch_use_kernel=use_kernel, snapshot_shards=shards)
    interp = grid.broker_for(CLIENT)
    ads = [None] + [
        default_read_request(CLIENT, min_bandwidth=mb, rank=rk)
        for mb in (0.0, 512.0 * MiB)
        for rk in ("predicted", "static", "last")
    ]
    queries = [(lfn, ads[i % len(ads)]) for i, lfn in enumerate(lfns)]
    top_k = 3 if shards else None  # a sharded broker's top-k takes the same launch
    got = served.select_many(queries, top_k=top_k, strict=False)
    assert served.stats["batched_kernel_requests"] == len(queries)
    assert served.stats["batched_kernel_guarded_requests"] == len(queries)
    branches = {"EwmaRDBandwidthToSource": 0, "AvgRDBandwidth": 0, "static": 0}
    zero_den = 0
    for (lfn, req), g in zip(queries, got):
        want = _ranking(interp.select(lfn, req))
        _no_near_ties(want)
        _assert_same(_ranking(g), want[:top_k] if top_k else want)
        if req is None or "ifThenElse" in repr(req["rank"]).replace("ifthenelse", "ifThenElse"):
            for url, _ in want:
                branches[_branch(entries[url])] += 1
                zero_den += _branch(entries[url]) == "static" and grid.endpoints[url].active_transfers == -1.0
    assert min(branches.values()) > 0, branches  # every branch of the chain is taken
    assert zero_den > 0


def test_breakers_and_bandwidth_gate_exclude():
    grid, lfns, entries = history_grid(128, 5)
    b = grid.broker_for(CLIENT, batch_use_kernel=True)
    req = default_read_request(CLIENT, min_bandwidth=512.0 * MiB)
    for res in b.select_many([(lfn, req) for lfn in lfns], strict=False):
        for url, _ in _ranking(res):
            e = entries[url]
            assert e.get("breakerOpenToSource", 0.0) < 1
            assert e.get("MaxRDBandwidth", np.inf) >= 512.0 * MiB


# ------------------------------------------------------ shapes and fallbacks
def test_plan_interval_none_for_guarded_and_chains():
    """Guarded terms, quotients and rank chains are not ``plain``: the
    launch counts them as guarded (``batched_kernel_guarded_requests``)."""
    plain = lower_request(parse_classad(
        "rank = other.diskTransferRate; requirements = other.loadFactor < 3"), VOCAB)
    assert plain.plain
    for ad in (
        default_read_request(CLIENT),  # guarded terms and a chain
        default_read_request(CLIENT, rank="static"),  # guarded terms, a quotient
        parse_classad("rank = other.diskTransferRate / (1 + other.loadFactor)"),  # a quotient
        parse_classad("rank = ifThenElse(!isUndefined(other.avgRDBandwidth),"
                      " other.avgRDBandwidth, other.diskTransferRate)"),  # a chain
    ):
        plan = lower_request(ad, VOCAB)
        assert not plan.plain
        assert stack_plans([plain, plan]).b == 2  # one launch takes both


@pytest.mark.parametrize("b", [1, 5, 64])
def test_mixed_batch_stacks_to_plain_shapes(b):
    """One compiled launch per batch size: a batch mixing default-ad plans
    and analysis plans has the operand shapes of an all-analysis batch."""
    analysis = lower_request(parse_classad(
        "reqdSpace = 1G; rank = other.diskTransferRate - 1e8 * other.loadFactor;"
        " requirements = other.availableSpace > 5G && other.loadFactor < 30"), VOCAB)
    default = lower_request(default_read_request(CLIENT, min_bandwidth=1.0), VOCAB)
    plain = stack_plans([analysis] * b)
    mixed = stack_plans([default if i % 4 else analysis for i in range(b)])
    for name in ("sel", "op_codes", "thresholds", "term_role", "weights", "bias"):
        x, y = getattr(plain, name), getattr(mixed, name)
        assert x.shape == y.shape and x.dtype == y.dtype, name


def test_kernel_and_host_agree_on_mixed_batch():
    rng = np.random.default_rng(4)
    s = 300
    attrs = np.stack([rng.integers(0, 64, s) * float(1 << 30), rng.integers(0, 64, s) * float(MiB),
                      rng.choice([0.0, 1.0, 0.5], s), rng.integers(1, 6, s) * 2e8,
                      rng.integers(-4, 64, s) * float(MiB), rng.integers(0, 64, s) * float(MiB),
                      rng.choice(LOADS, s), rng.integers(0, 64, s) * float(MiB)], 1).astype(np.float32)
    valid = rng.random(attrs.shape) > 0.3
    plans = [lower_request(default_read_request(CLIENT, min_bandwidth=mb, rank=rk), VOCAB)
             for mb in (0.0, 32.0 * MiB) for rk in ("predicted", "static", "last")]
    plans.append(lower_request(parse_classad("rank = other.diskTransferRate;"
                                             " requirements = other.loadFactor < 3"), VOCAB))
    mk, sk, ik, tk = matchrank_batched(attrs, valid, plans, k=4, block_s=128, use_kernel=True)
    mh, sh, ih, th = matchrank_batched(attrs, valid, plans, k=4, block_s=128, use_kernel=False)
    np.testing.assert_array_equal(mk, mh)
    np.testing.assert_array_equal(sk, sh)
    np.testing.assert_array_equal(ik, ih)
    assert mk.any(axis=1).all()


def test_general_or_takes_columnar():
    grid, lfns, _ = history_grid(64, 6, files=4)
    b = grid.broker_for(CLIENT, batch_use_kernel=True)
    general = parse_classad(
        "reqdSpace = 0; rank = other.diskTransferRate;"
        " requirements = other.loadFactor < 2 || other.availableSpace > 5")
    got = b.select_many([(lfns[0], general), (lfns[1], None)])
    assert b.stats["batched_columnar_requests"] == 1
    assert b.stats["batched_kernel_requests"] == 1
    assert b.explain(b.last_request_ids[0]).kernel_path == "batched_columnar"
    _assert_same(_ranking(got[0]), _ranking(grid.broker_for(CLIENT).select(lfns[0], general)))


def test_spans_carry_guarded():
    grid, lfns, _ = history_grid(64, 7, files=4)
    b = grid.broker_for(CLIENT, batch_use_kernel=True)
    plain = parse_classad(
        "reqdSpace = 0; rank = other.diskTransferRate; requirements = other.loadFactor < 9")
    b.select_many([(lfns[0], None), (lfns[1], plain), (lfns[2], None)])
    (launch,) = b.tracer.spans("broker.kernel_launch")
    assert launch.args["guarded"] == 2 and launch.args["batch"] == 3
    assert [sp.args["guarded"] for sp in b.tracer.spans("broker.lowering.plan")] == [True, False, True]
    assert b.stats["batched_kernel_guarded_requests"] == 2
    assert "broker_batched_kernel_guarded_requests_total 2" in b.metrics.expose_text()


def test_default_ad_parsed_once_a_batch(monkeypatch):
    import repro.core.broker as broker_mod

    grid, lfns, _ = history_grid(64, 8, files=4)
    b = grid.broker_for(CLIENT, batch_use_kernel=True)
    calls = []

    def counted(client_url, **kw):
        calls.append(client_url)
        return default_read_request(client_url, **kw)

    monkeypatch.setattr(broker_mod, "default_read_request", counted)
    got = b.select_many([(lfn, None) for lfn in lfns])
    assert calls == [CLIENT]
    ref = grid.broker_for(CLIENT)
    for lfn, res in zip(lfns, got):
        _assert_same(_ranking(res), _ranking(ref.select(lfn, None)))
