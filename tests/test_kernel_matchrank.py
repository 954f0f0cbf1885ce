"""matchrank Pallas kernel: shape/dtype sweeps vs the pure-jnp oracle,
plus end-to-end parity with the ClassAd interpreter."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # degrade: property tests skip, sweeps still run
    HAVE_HYPOTHESIS = False

    def given(*a, **k):
        return lambda f: f

    def settings(*a, **k):
        return lambda f: f

    class _St:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _St()

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed (requirements-dev.txt)"
)

from repro.core.classads import parse_classad
from repro.core.matchmaker import Matchmaker
from repro.kernels.matchrank.ops import (
    lower_request,
    matchrank,
    matchrank_batched,
    matchrank_batched_topk,
    matchrank_topk,
    stack_plans,
)
from repro.kernels.matchrank.sparse import canonicalize_plans

NAMES = ["availablespace", "maxrdbandwidth", "avgrdbandwidth", "loadfactor"]


def random_cols(rng, s, invalid_frac=0.1):
    attrs = np.stack(
        [
            rng.uniform(0, 20 * 1024**3, s),
            rng.uniform(0, 200 * 1024, s),
            rng.uniform(0, 100e6, s),
            rng.uniform(0, 8, s),
        ],
        axis=1,
    ).astype(np.float32)
    valid = rng.random((s, 4)) > invalid_frac
    return attrs, valid


REQUEST = parse_classad(
    """
reqdSpace = 5G;
rank = other.avgRDBandwidth + 0.5 * other.maxRDBandwidth;
requirements = other.availableSpace > 5G && other.maxRDBandwidth >= 50K
    && other.loadFactor <= 6;
"""
)


class TestKernelVsRef:
    @pytest.mark.parametrize("s", [1, 7, 64, 512, 513, 2048])
    @pytest.mark.parametrize("block_s", [256, 512])
    def test_shape_sweep(self, s, block_s):
        rng = np.random.default_rng(s * 1000 + block_s)
        attrs, valid = random_cols(rng, s)
        plan = lower_request(REQUEST, NAMES)
        mk, sk, bsk, bik = matchrank(attrs, valid, plan, block_s=block_s, use_kernel=True)
        mr, sr, bsr, bir = matchrank(attrs, valid, plan, block_s=block_s, use_kernel=False)
        np.testing.assert_array_equal(mk, mr)
        np.testing.assert_allclose(sk[mk], sr[mr], rtol=1e-6)
        assert bik == bir
        if mk.any():
            np.testing.assert_allclose(bsk, bsr, rtol=1e-6)

    @pytest.mark.filterwarnings("error")
    @pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32])
    def test_dtype_coercion(self, dtype):
        rng = np.random.default_rng(0)
        attrs, valid = random_cols(rng, 128)
        if np.issubdtype(dtype, np.integer):
            # clip into the target's representable range before the cast;
            # float32 spacing at 2^31 is 256, so clipping to exactly
            # info.max would round back out of range — leave headroom
            info = np.iinfo(dtype)
            attrs = np.clip(attrs, info.min, info.max - 1024)
        attrs = attrs.astype(dtype)
        plan = lower_request(REQUEST, NAMES)
        mk, sk, _, bik = matchrank(np.asarray(attrs, np.float32), valid, plan)
        mr, sr, _, bir = matchrank(np.asarray(attrs, np.float32), valid, plan, use_kernel=False)
        np.testing.assert_array_equal(mk, mr)
        assert bik == bir

    def test_no_matches(self):
        rng = np.random.default_rng(1)
        attrs, valid = random_cols(rng, 100)
        req = parse_classad("requirements = other.loadFactor > 1000; rank = 1")
        plan = lower_request(req, NAMES)
        mk, sk, bs, bi = matchrank(attrs, valid, plan)
        assert not mk.any()
        assert bs == -np.inf

    def test_admit_premask(self):
        rng = np.random.default_rng(2)
        attrs, valid = random_cols(rng, 64, invalid_frac=0.0)
        plan = lower_request(parse_classad("requirements = true; rank = other.loadfactor"), NAMES)
        admit = np.zeros(64)
        admit[10] = 1
        mk, _, _, bi = matchrank(attrs, valid, plan, admit=admit)
        assert mk.sum() == 1 and bi == 10

    def test_topk(self):
        rng = np.random.default_rng(3)
        attrs, valid = random_cols(rng, 300, invalid_frac=0.0)
        plan = lower_request(parse_classad("requirements = true; rank = other.avgrdbandwidth"), NAMES)
        idx, vals = matchrank_topk(attrs, valid, plan, 5)
        order = np.argsort(-attrs[:, 2])
        np.testing.assert_array_equal(idx, order[:5])


@needs_hypothesis
class TestKernelVsInterpreter:
    """The kernel path must reproduce the interpreter's selections."""

    @given(st.integers(0, 10_000), st.integers(2, 40))
    @settings(max_examples=25, deadline=None)
    def test_best_matches_interpreter(self, seed, s):
        rng = np.random.default_rng(seed)
        attrs, valid = random_cols(rng, s, invalid_frac=0.2)
        plan = lower_request(REQUEST, NAMES)
        mk, sk, bs, bi = matchrank(attrs, valid, plan)

        ads = []
        for i in range(s):
            ad = parse_classad(f'name = "ep{i:04d}"')
            for j, n in enumerate(NAMES):
                if valid[i, j]:
                    ad[n] = float(attrs[i, j])
            ads.append(ad)
        res = Matchmaker().match(REQUEST, ads, require_symmetric=False)
        got = {int(m.name[2:]) for m in res}
        assert got == set(np.nonzero(mk)[0].tolist())
        if res:
            # f32 rank ties can reorder; best score must agree to f32 eps
            assert abs(res[0].rank - bs) <= 1e-6 * max(abs(res[0].rank), 1.0) + 1e-3


def _ads_from_cols(attrs, valid):
    ads = []
    for i in range(attrs.shape[0]):
        ad = parse_classad(f'name = "ep{i:04d}"')
        for j, n in enumerate(NAMES):
            if valid[i, j]:
                ad[n] = float(attrs[i, j])
        ads.append(ad)
    return ads


REQUEST_BATCH = [
    REQUEST,
    parse_classad("rank = other.avgRDBandwidth; requirements = other.loadFactor <= 4;"),
    parse_classad(
        "reqdSpace = 1G;"
        "rank = 2 * other.maxRDBandwidth - other.loadFactor;"
        "requirements = other.availableSpace >= my.reqdSpace && other.avgRDBandwidth > 1M;"
    ),
    parse_classad("rank = other.loadFactor; requirements = true;"),
]


class TestBatched:
    """Multi-request kernel: one launch must equal B sequential launches."""

    @pytest.mark.parametrize("s", [1, 63, 512, 700])
    @pytest.mark.parametrize("use_kernel", [True, False])
    def test_batched_vs_sequential(self, s, use_kernel):
        rng = np.random.default_rng(s + int(use_kernel))
        attrs, valid = random_cols(rng, s, invalid_frac=0.15)
        plans = [lower_request(r, NAMES) for r in REQUEST_BATCH]
        mask_b, score_b, topk_i, topk_s = matchrank_batched(
            attrs, valid, plans, k=3, block_s=256, use_kernel=use_kernel
        )
        assert mask_b.shape == (len(plans), s)
        for i, p in enumerate(plans):
            m, sc, bs, bi = matchrank(attrs, valid, p, block_s=256, use_kernel=False)
            np.testing.assert_array_equal(mask_b[i], m)
            np.testing.assert_allclose(score_b[i][m], sc[m], rtol=1e-6)
            if m.any():
                assert topk_i[i, 0] == bi
                np.testing.assert_allclose(topk_s[i, 0], bs, rtol=1e-6)
            else:
                assert topk_s[i, 0] == -np.inf

    @pytest.mark.parametrize("use_kernel", [True, False])
    def test_batched_topk_matches_unbatched(self, use_kernel):
        rng = np.random.default_rng(9)
        attrs, valid = random_cols(rng, 600, invalid_frac=0.0)
        plans = [lower_request(r, NAMES) for r in REQUEST_BATCH]
        _, _, topk_i, topk_s = matchrank_batched(
            attrs, valid, plans, k=5, block_s=256, use_kernel=use_kernel
        )
        for i, p in enumerate(plans):
            idx, vals = matchrank_topk(attrs, valid, p, 5, block_s=256, use_kernel=False)
            matched = vals > -np.inf
            np.testing.assert_array_equal(topk_i[i][matched], idx[matched])
            np.testing.assert_allclose(topk_s[i][matched], vals[matched], rtol=1e-6)

    def test_topk_empty_slots_follow_lax_top_k(self):
        """Fewer matches than k, across several S-blocks: slots past the
        match count hold -inf at the lowest unmatched rows, exactly as
        ``lax.top_k`` (and the host evaluator) order them."""
        rng = np.random.default_rng(21)
        attrs, valid = random_cols(rng, 700, invalid_frac=0.0)
        admit = np.zeros((3, 700), np.float32)
        admit[0, [650, 300]] = 1  # two matches, both past the first block
        admit[2, 5] = 1
        plan = lower_request(
            parse_classad("requirements = true; rank = other.loadfactor"), NAMES
        )
        plans = [plan] * 3
        _, _, ki, ks = matchrank_batched(
            attrs, valid, plans, admit=admit, k=5, block_s=256, use_kernel=True
        )
        _, _, hi, hs = matchrank_batched(
            attrs, valid, plans, admit=admit, k=5, block_s=256, use_kernel=False
        )
        np.testing.assert_array_equal(ks, hs)
        np.testing.assert_array_equal(ki, hi)
        assert list(ki[1]) == [0, 1, 2, 3, 4]  # nothing admitted

    def test_batched_admit_premask(self):
        rng = np.random.default_rng(4)
        attrs, valid = random_cols(rng, 64, invalid_frac=0.0)
        plan = lower_request(
            parse_classad("requirements = true; rank = other.loadfactor"), NAMES
        )
        admit = np.zeros((2, 64), np.float32)
        admit[0, 5] = 1
        admit[1, 40:44] = 1
        mask, _, topk_i, _ = matchrank_batched(
            attrs, valid, stack_plans([plan, plan]), admit=admit, k=1
        )
        assert mask[0].sum() == 1 and topk_i[0, 0] == 5
        assert mask[1].sum() == 4 and 40 <= topk_i[1, 0] < 44

    def test_stack_plans_mixed_t_pad(self):
        many_terms = parse_classad(
            "requirements = "
            + " && ".join(f"other.loadFactor < {i + 100}" for i in range(20))
            + "; rank = 1"
        )
        plans = [lower_request(REQUEST, NAMES), lower_request(many_terms, NAMES)]
        assert plans[0].t_pad != plans[1].t_pad
        bp = stack_plans(plans)
        assert bp.t_pad == max(p.t_pad for p in plans)
        rng = np.random.default_rng(0)
        attrs, valid = random_cols(rng, 100, invalid_frac=0.0)
        mask_b, _, _, _ = matchrank_batched(attrs, valid, bp, use_kernel=False)
        m0, _, _, _ = matchrank(attrs, valid, plans[0], use_kernel=False)
        m1, _, _, _ = matchrank(attrs, valid, plans[1], use_kernel=False)
        np.testing.assert_array_equal(mask_b[0], m0)
        np.testing.assert_array_equal(mask_b[1], m1)

    def test_vocab_mismatch_rejected(self):
        p1 = lower_request(REQUEST, NAMES)
        p2 = lower_request(REQUEST, NAMES[:2])
        with pytest.raises(ValueError):
            stack_plans([p1, p2])


class TestUnknownAttributeEncodings:
    """lower_request's encodings for attributes outside the vocabulary
    must agree with the interpreter: a requirements term on an absent
    attribute ⇒ no candidate matches; a rank weight on an unknown
    attribute ⇒ rank Undefined ⇒ 0.0 for all candidates."""

    def _check(self, request, attrs, valid, expect_rank_zero=False):
        plan = lower_request(request, NAMES)
        ads = _ads_from_cols(attrs, valid)
        res = Matchmaker().match(request, ads, require_symmetric=False)
        expected = {int(m.name[2:]) for m in res}

        for use_kernel in (True, False):
            mk, sk, bs, bi = matchrank(
                attrs, valid, plan, block_s=256, use_kernel=use_kernel
            )
            assert set(np.nonzero(mk)[0].tolist()) == expected
            if expect_rank_zero:
                assert np.all(sk[mk] == 0.0)
            # batched path must encode identically
            mb, sb, _, _ = matchrank_batched(
                attrs, valid, [plan, plan], block_s=256, use_kernel=use_kernel
            )
            np.testing.assert_array_equal(mb[0], mk)
            np.testing.assert_array_equal(mb[1], mk)
            np.testing.assert_allclose(sb[0][mk], sk[mk], rtol=1e-6)
        if res and expect_rank_zero:
            assert all(m.rank == 0.0 for m in res)

    def test_absent_requirement_attr_no_match(self):
        rng = np.random.default_rng(11)
        attrs, valid = random_cols(rng, 80, invalid_frac=0.1)
        req = parse_classad(
            "requirements = other.noSuchAttr > 1 && other.loadFactor < 6; rank = 1"
        )
        self._check(req, attrs, valid)
        plan = lower_request(req, NAMES)
        mk, _, _, _ = matchrank(attrs, valid, plan, use_kernel=False)
        assert not mk.any()

    def test_unknown_rank_attr_rank_zero(self):
        rng = np.random.default_rng(12)
        attrs, valid = random_cols(rng, 80, invalid_frac=0.1)
        req = parse_classad(
            "requirements = other.loadFactor < 6; rank = other.noSuchAttr * 3"
        )
        self._check(req, attrs, valid, expect_rank_zero=True)

    @needs_hypothesis
    @given(st.integers(0, 10_000), st.integers(1, 50), st.booleans())
    @settings(max_examples=25, deadline=None)
    def test_property_absent_attrs_match_interpreter(self, seed, s, in_rank):
        rng = np.random.default_rng(seed)
        attrs, valid = random_cols(rng, s, invalid_frac=0.25)
        if in_rank:
            req = parse_classad(
                "requirements = other.availableSpace > 2G;"
                "rank = other.ghostAttr + other.avgRDBandwidth * 0"
            )
            self._check(req, attrs, valid, expect_rank_zero=True)
        else:
            req = parse_classad(
                "requirements = other.ghostAttr >= 1 && other.loadFactor < 7; rank = 1"
            )
            self._check(req, attrs, valid)

class TestSparseTopK:
    """The rank-order sparse walk must be selection-identical to the
    dense batched launch (same scores, same lowest-index tie-break)."""

    @pytest.mark.parametrize("s", [5, 257, 1000])
    @pytest.mark.parametrize("k", [1, 3])
    def test_matches_dense(self, s, k):
        rng = np.random.default_rng(s * 10 + k)
        attrs, valid = random_cols(rng, s, invalid_frac=0.15)
        plans = [lower_request(r, NAMES) for r in REQUEST_BATCH]
        ti, ts = matchrank_batched_topk(attrs, valid, plans, k=k)
        _, _, di, ds = matchrank_batched(attrs, valid, plans, k=k, use_kernel=False)
        matched = ts > -np.inf
        np.testing.assert_array_equal(ti[matched], np.asarray(di, np.int64)[matched])
        np.testing.assert_allclose(ts[matched], np.asarray(ds)[matched], rtol=1e-5)
        # unmatched slots are explicit on the sparse path
        assert (ti[~matched] == -1).all()

    def test_admit_premask(self):
        rng = np.random.default_rng(7)
        attrs, valid = random_cols(rng, 400, invalid_frac=0.0)
        plans = [lower_request(r, NAMES) for r in REQUEST_BATCH]
        admit = rng.random((len(plans), 400)) > 0.7
        ti, ts = matchrank_batched_topk(attrs, valid, plans, k=2, admit=admit)
        _, _, di, ds = matchrank_batched(
            attrs, valid, plans, k=2, admit=admit.astype(np.float32), use_kernel=False
        )
        matched = ts > -np.inf
        np.testing.assert_array_equal(ti[matched], np.asarray(di, np.int64)[matched])
        for bi in range(len(plans)):
            got = ti[bi][ti[bi] >= 0]
            assert admit[bi][got].all()

    def test_ne_term_falls_back_to_dense(self):
        rng = np.random.default_rng(8)
        attrs, valid = random_cols(rng, 300, invalid_frac=0.0)
        ne = lower_request(
            parse_classad(
                "rank = other.avgrdbandwidth; requirements = other.loadfactor != 3;"
            ),
            NAMES,
        )
        assert canonicalize_plans([ne], len(NAMES)) is None
        ti, ts = matchrank_batched_topk(attrs, valid, [ne], k=1)
        _, _, di, ds = matchrank_batched(attrs, valid, [ne], k=1, use_kernel=False)
        np.testing.assert_array_equal(ti, np.asarray(di, np.int64))
        from repro.core.compile import CompileError

        with pytest.raises(CompileError):
            matchrank_batched_topk(attrs, valid, [ne], k=1, use_sparse=True)

    def test_absent_attr_never_matches(self):
        rng = np.random.default_rng(9)
        attrs, valid = random_cols(rng, 128, invalid_frac=0.0)
        bad = lower_request(
            parse_classad("requirements = other.noSuchAttr > 1;"), NAMES
        )
        ok = lower_request(
            parse_classad("rank = other.loadfactor; requirements = true;"), NAMES
        )
        ti, ts = matchrank_batched_topk(attrs, valid, [bad, ok], k=2)
        assert (ti[0] == -1).all() and np.isneginf(ts[0]).all()
        assert (ti[1] >= 0).all()

    def test_strict_op_boundaries(self):
        # x > 5 must exclude exactly 5.0; x >= 5 must include it
        attrs = np.array([[5.0], [np.nextafter(5.0, 6.0, dtype=np.float32)], [4.0]],
                         np.float32)
        valid = np.ones((3, 1), bool)
        names = ["x"]
        gt = lower_request(parse_classad("rank = other.x; requirements = other.x > 5;"), names)
        ge = lower_request(parse_classad("rank = other.x; requirements = other.x >= 5;"), names)
        ti, ts = matchrank_batched_topk(attrs, valid, [gt, ge], k=3)
        assert set(ti[0][ti[0] >= 0].tolist()) == {1}
        assert set(ti[1][ti[1] >= 0].tolist()) == {0, 1}

    def test_tie_break_is_lowest_index(self):
        # constant rank => every score ties; both paths must pick the
        # lowest candidate indices, in order
        attrs = np.ones((50, 4), np.float32)
        valid = np.ones((50, 4), bool)
        plan = lower_request(parse_classad("rank = 7; requirements = true;"), NAMES)
        ti, ts = matchrank_batched_topk(attrs, valid, [plan], k=4)
        _, _, di, _ = matchrank_batched(attrs, valid, [plan], k=4, use_kernel=False)
        np.testing.assert_array_equal(ti[0], [0, 1, 2, 3])
        np.testing.assert_array_equal(np.asarray(di, np.int64)[0], [0, 1, 2, 3])
        np.testing.assert_allclose(ts[0], 7.0)

    def test_snapshot_rank_order_cache(self):
        from repro.core.snapshot import ReplicaSnapshot

        rng = np.random.default_rng(11)
        attrs, valid = random_cols(rng, 300, invalid_frac=0.1)
        entries = []
        for i in range(300):
            e = {"endpoint": f"ep{i:04d}"}
            e.update({n: float(attrs[i, j]) for j, n in enumerate(NAMES) if valid[i, j]})
            entries.append(e)
        snap = ReplicaSnapshot(entries, NAMES)
        la, lv = snap.logical_columns()
        plans = [lower_request(r, snap.attr_names) for r in REQUEST_BATCH]
        ti1, ts1 = matchrank_batched_topk(la, lv, plans, k=2, rank_order=snap.rank_order)
        ti2, ts2 = matchrank_batched_topk(la, lv, plans, k=2)  # uncached order
        np.testing.assert_array_equal(ti1, ti2)
        np.testing.assert_allclose(ts1, ts2, rtol=1e-6)
        # a row update invalidates the cached order and logical columns
        snap.update_rows({0: {NAMES[2]: 1e12}})
        la2, lv2 = snap.logical_columns()
        assert la2[0, 2] == np.float32(1e12)
        order, svals = snap.rank_order(np.array([0, 0, 1, 0], np.float32))
        assert order[0] == 0 and svals[0] == np.float32(1e12)
