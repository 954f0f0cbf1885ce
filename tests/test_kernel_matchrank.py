"""matchrank Pallas kernel: shape/dtype sweeps vs the pure-jnp oracle,
plus end-to-end parity with the ClassAd interpreter."""

import jax
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
    NO_ROW,
    lower_request,
    matchrank,
    matchrank_batched,
    matchrank_candidates,
    matchrank_topk,
    stack_plans,
)

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

class TestKernelTopK:
    """The kernel's in-tile top-k: the per-request carry across S-blocks
    must give exactly what ``lax.top_k`` gives over the kernel's own
    scores — score descending, ties to the lowest row, and -inf slots past
    a request's matches at the lowest unmatched rows."""

    S, BLOCK_S = 700, 256  # three S-blocks: the carry crosses two boundaries
    TIE_S, TIE_K = 200, 5  # one compiled program for every tie-break draw

    @pytest.mark.parametrize("k", [1, 3, 7])
    def test_kernel_topk_matches_lax_top_k(self, k):
        rng = np.random.default_rng(k)
        attrs, valid = random_cols(rng, self.S, invalid_frac=0.1)
        attrs[:, 2] = np.round(attrs[:, 2] / 25e6) * 25e6  # many exact ties
        attrs[:, 3] = np.round(attrs[:, 3])
        plans = [lower_request(r, NAMES) for r in REQUEST_BATCH]
        _, score, ti, ts = matchrank_batched(
            attrs, valid, plans, k=k, block_s=self.BLOCK_S, use_kernel=True
        )
        want_s, want_i = jax.lax.top_k(score, k)
        np.testing.assert_array_equal(ti, np.asarray(want_i))
        np.testing.assert_array_equal(ts, np.asarray(want_s))

    def test_padding_columns_and_empty_slots_never_win(self):
        """Candidate lists shorter than their bucket, with empty slots
        mixed in: only real candidate rows fill top-k slots."""
        rng = np.random.default_rng(5)
        attrs, valid = random_cols(rng, 300, invalid_frac=0.0)
        plan = lower_request(
            parse_classad("requirements = true; rank = other.loadfactor"), NAMES
        )
        rows = [[17, NO_ROW, 250, NO_ROW, 3], [NO_ROW, 99], [NO_ROW] * 4]
        cm, cs, ti, ts = matchrank_candidates(
            attrs, valid, [plan] * 3, rows, k=7, block_s=128, use_kernel=True
        )
        assert cm.shape == (3, 8)  # the bucket: columns past each list are padding
        for bi, r in enumerate(rows):
            real = [x for x in r if x != NO_ROW]
            np.testing.assert_array_equal(cm[bi, : len(r)], [x != NO_ROW for x in r])
            assert not cm[bi, len(r) :].any() and np.isneginf(cs[bi, len(r) :]).all()
            live = ts[bi] > -np.inf
            assert live.sum() == len(real)
            want = sorted(real, key=lambda x: -attrs[x, 3])
            np.testing.assert_array_equal(ti[bi][live], want)
            assert NO_ROW not in ti[bi].tolist()

    def test_k_above_match_count_leaves_neg_inf_slots(self):
        rng = np.random.default_rng(6)
        attrs, valid = random_cols(rng, 300, invalid_frac=0.0)
        attrs[[5, 41], 3] = 7.0  # refused by the requirement below
        plan = lower_request(
            parse_classad("requirements = other.loadfactor < 6; rank = other.avgrdbandwidth"),
            NAMES,
        )
        rows = [[5, 40, 41, 290], [7]]
        _, _, ti, ts = matchrank_candidates(
            attrs, valid, [plan] * 2, rows, k=7, block_s=128, use_kernel=True
        )
        _, _, hi, hs = matchrank_candidates(
            attrs, valid, [plan] * 2, rows, k=7, block_s=128, use_kernel=False
        )
        np.testing.assert_array_equal(ti, hi)
        np.testing.assert_array_equal(ts, hs)
        for bi, r in enumerate(rows):
            matched = [x for x in r if attrs[x, 3] < 6]
            n = len(matched)
            assert np.isfinite(ts[bi, :n]).all() and np.isneginf(ts[bi, n:]).all()
            assert sorted(ti[bi, :n].tolist()) == sorted(matched)
            # the empty slots name the lowest unmatched rows, as lax.top_k does
            rest = [x for x in range(300) if x not in matched][: 7 - n]
            assert ti[bi, n:].tolist() == rest

    def _check_against_stable_sort(self, seed):
        """Eight requests with drawn thresholds and rank columns over a few
        distinct values: the kernel's top-k must equal a stable descending
        sort of scores worked out in numpy."""
        rng = np.random.default_rng(seed)
        s, k = self.TIE_S, self.TIE_K
        attrs = rng.choice([0.0, 1.0, 2.0, 3.0], size=(s, 4)).astype(np.float32)
        valid = rng.random((s, 4)) > 0.1
        thr = rng.integers(1, 5, size=8)
        rank_col = rng.integers(1, 3, size=8)  # maxrdbandwidth or avgrdbandwidth
        plans = [
            lower_request(
                parse_classad(
                    f"requirements = other.loadfactor < {t}; rank = other.{NAMES[c]}"
                ),
                NAMES,
            )
            for t, c in zip(thr, rank_col)
        ]
        _, _, ti, ts = matchrank_batched(
            attrs, valid, plans, k=k, block_s=128, use_kernel=True
        )
        for bi, (t, c) in enumerate(zip(thr, rank_col)):
            mask = valid[:, 3] & (attrs[:, 3] < t)
            rank = np.where(valid[:, c], attrs[:, c], np.float32(0.0))
            score = np.where(mask, rank, np.float32(-np.inf)).astype(np.float32)
            want = np.argsort(-score, kind="stable")[:k]
            np.testing.assert_array_equal(ti[bi], want)
            np.testing.assert_array_equal(ts[bi], score[want])

    def test_matches_stable_sort_seeded(self):
        for seed in range(12):
            self._check_against_stable_sort(seed)

    @needs_hypothesis
    @settings(max_examples=20, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_tie_break_property(self, seed):
        self._check_against_stable_sort(seed)
