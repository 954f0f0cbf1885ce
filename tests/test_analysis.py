"""Static analysis subsystem: ClassAd/schema analyzer, repo lint,
kernel BlockSpec checks, broker/GRIS wiring, and the CLI gate.

The seeded defect corpus pins the contract from the issue: every known-bad
ad produces exactly the expected diagnostic (rule-for-rule, no extras),
and the clean tree plus the exemplar ads produce zero findings.
"""

import json
import os

import pytest

from repro.analysis import (
    Report,
    Severity,
    build_report,
    check_ad_file,
    check_ad_text,
    check_kernel_source,
    check_policy_source,
    check_request_ad,
    check_resource_ad,
    lint_source,
    main,
)
from repro.core.broker import AdValidationError, default_read_request
from repro.core.classads import parse_classad
from repro.core.gris import Clock, StorageGRIS
from repro.core.schema import SchemaError
from repro.storage.endpoint import build_demo_grid

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO_ROOT, "src", "repro")
ADS_DIR = os.path.join(REPO_ROOT, "examples", "ads")


def rules(diags):
    return sorted(d.rule for d in diags)


# ---------------------------------------------------------------- bad corpus
# Each entry: (name, ad source, perspective, exact expected rule list).
BAD_ADS = [
    (
        "undefined-attr",
        "requirements = other.availabelSpace > 5G; rank = other.AvgRDBandwidth;",
        "request",
        ["AD101"],
    ),
    (
        "cis-compared-as-number",
        "requirements = other.mountPoint > 5; rank = other.AvgRDBandwidth;",
        "request",
        ["AD102"],
    ),
    (
        "contradictory-interval",
        "requirements = other.availableSpace > 10G && other.availableSpace < 1G;"
        " rank = other.AvgRDBandwidth;",
        "request",
        ["AD104"],
    ),
    (
        "trivially-false",
        "requirements = 1 > 2; rank = other.AvgRDBandwidth;",
        "request",
        ["AD104"],
    ),
    (
        "tautology",
        "requirements = 2 > 1; rank = other.AvgRDBandwidth;",
        "request",
        ["AD105"],
    ),
    (
        "constant-rank",
        "reqdSpace = 5G;"
        " requirements = other.availableSpace >= my.reqdSpace;"
        " rank = my.reqdSpace / 1G;",
        "request",
        ["AD106"],
    ),
    (
        "string-rank",
        "requirements = other.availableSpace > 1G; rank = other.mountPoint;",
        "request",
        ["AD108"],
    ),
    (
        "unknown-function",
        "requirements = other.availableSpace > 1G;"
        " rank = frobnicate(other.AvgRDBandwidth);",
        "request",
        ["AD103"],
    ),
    (
        "missing-requirements",
        "reqdSpace = 5G; rank = other.AvgRDBandwidth;",
        "request",
        ["AD107"],
    ),
    (
        "numeric-operand-to-and",
        "requirements = other.availableSpace && other.MaxRDBandwidth > 1;"
        " rank = other.AvgRDBandwidth;",
        "request",
        ["AD102"],
    ),
    (
        # the paper's §4 storage ad, mutated: availableSpace typo'd away
        # so the ServerVolume MUST set is violated
        "storage-ad-missing-must",
        'objectClass = "Grid::Storage::ServerVolume";'
        ' mountPoint = "/homes"; totalSpace = 50G; availabelSpace = 20G;'
        " diskTransferRate = 75K; drdTime = 10.5; dwrTime = 11.5;"
        " requirements = other.reqdSpace <= 10G;",
        "resource",
        ["ADS01"],
    ),
    (
        # site policy with a cis/cisfloat confusion: comparing the
        # requester's URL (a string) with a number
        "storage-ad-policy-type-confusion",
        'objectClass = "Grid::Storage::ServerVolume";'
        ' mountPoint = "/homes"; totalSpace = 50G; availableSpace = 20G;'
        " diskTransferRate = 75K; drdTime = 10.5; dwrTime = 11.5;"
        " requirements = other.clientUrl > 5;",
        "resource",
        ["AD102"],
    ),
    (
        "storage-ad-unknown-class",
        'objectClass = "Grid::Compute::Node"; totalSpace = 50G;',
        "resource",
        ["ADS03"],
    ),
]


class TestBadAdCorpus:
    @pytest.mark.parametrize(
        "name,src,perspective,expected",
        BAD_ADS,
        ids=[b[0] for b in BAD_ADS],
    )
    def test_exact_diagnostics(self, name, src, perspective, expected):
        diags = check_ad_text(src, name=name)
        assert rules(diags) == expected, [d.render() for d in diags]

    def test_corpus_is_large_enough(self):
        assert len(BAD_ADS) >= 10

    def test_syntax_error_ad(self):
        diags = check_ad_text("requirements = other.availableSpace >;")
        assert rules(diags) == ["ADS02"]
        assert diags[0].severity is Severity.ERROR
        assert diags[0].span is not None

    def test_spans_point_at_the_attribute(self):
        src = "reqdSpace = 5G;\nrank = other.AvgRDBandwidth;\n"
        diags = check_ad_text(src)
        assert rules(diags) == ["AD107"]  # located on the missing attr's ad
        src2 = "reqdSpace = 5G;\nrequirements = other.nope > 1;\nrank = other.AvgRDBandwidth;\n"
        (d,) = check_ad_text(src2)
        assert d.rule == "AD101" and d.span.line == 2

    def test_guarded_undefined_attr_downgrades(self):
        src = (
            "requirements = isUndefined(other.customHint) || other.customHint > 1;"
            " rank = other.AvgRDBandwidth;"
        )
        (d,) = check_request_ad(parse_classad(src))
        assert d.rule == "AD101" and d.severity is Severity.WARNING

    def test_attr_used_only_inside_guard_is_silent(self):
        src = (
            "requirements = !isUndefined(other.customHint)"
            " && other.availableSpace > 1G;"
            " rank = other.AvgRDBandwidth;"
        )
        assert check_request_ad(parse_classad(src)) == []


class TestCleanAds:
    def test_exemplar_ads_zero_findings(self):
        files = sorted(
            os.path.join(ADS_DIR, f)
            for f in os.listdir(ADS_DIR)
            if f.endswith(".ad")
        )
        assert len(files) >= 3
        for path in files:
            assert check_ad_file(path) == [], path

    def test_default_read_request_is_clean(self):
        assert check_request_ad(default_read_request("client://c")) == []

    def test_demo_policy_is_clean(self):
        assert check_policy_source("other.reqdSpace <= 10G") == []

    def test_resource_ad_perspective_detected(self):
        src = 'objectClass = "Grid::Storage::ServerVolume"; mountPoint = "/x";' \
              " totalSpace = 1G; availableSpace = 1G; diskTransferRate = 1K;" \
              " drdTime = 1.0; dwrTime = 1.0;"
        assert check_ad_text(src) == []


# -------------------------------------------------------------- injected lint
class TestInjectedLintViolations:
    def test_wallclock_leak_in_sim_path(self):
        src = "import time\n\ndef stamp():\n    return time.time()\n"
        diags = lint_source(src, "repro/storage/leak.py")
        assert rules(diags) == ["SIM001"]
        assert diags[0].severity is Severity.ERROR
        # same file outside a sim path: only a warning
        (d,) = lint_source(src, "repro/launch/tool.py")
        assert d.severity is Severity.WARNING

    def test_unseeded_random_in_sim_path(self):
        src = "import random\n\ndef jitter():\n    return random.random()\n"
        diags = lint_source(src, "repro/core/jitter.py")
        assert rules(diags) == ["SIM002"]
        src_np = (
            "import numpy as np\n\ndef jitter():\n    return np.random.rand(3)\n"
        )
        assert rules(lint_source(src_np, "repro/serve/x.py")) == ["SIM002"]
        # explicitly seeded constructions stay silent
        ok = "import numpy as np\nrng = np.random.default_rng(7)\n"
        assert lint_source(ok, "repro/core/ok.py") == []

    def test_unbounded_retry_and_bare_except(self):
        src = (
            "def fetch(svc):\n"
            "    while True:\n"
            "        try:\n"
            "            svc.poll()\n"
            "        except:\n"
            "            continue\n"
        )
        diags = lint_source(src, "repro/storage/retry.py")
        assert rules(diags) == ["TRF001", "TRF002"]
        # a bounded loop (break) with a concrete except is clean
        ok = (
            "def fetch(svc):\n"
            "    for _ in range(3):\n"
            "        try:\n"
            "            return svc.poll()\n"
            "        except TimeoutError:\n"
            "            continue\n"
        )
        assert lint_source(ok, "repro/storage/retry.py") == []

    def test_unbounded_metric_label(self):
        src = (
            "def track(metrics, lfn):\n"
            "    metrics.counter('reads_total', 'reads', lfn=lfn).inc()\n"
        )
        diags = lint_source(src, "repro/core/track.py")
        assert rules(diags) == ["OBS001"]
        # a literal label value is bounded by construction
        ok = "def track(m):\n    m.counter('reads_total', 'r', op='read').inc()\n"
        assert lint_source(ok, "repro/core/track.py") == []

    def test_deprecated_tuple_read_shims(self):
        src = (
            "def old(svc, replica, client):\n"
            "    data, nbytes, bw = svc.read(replica, client)\n"
            "    for c in svc.read_chunks(replica, client):\n"
            "        pass\n"
        )
        diags = lint_source(src, "repro/serve/old.py")
        assert rules(diags) == ["DEP001", "DEP001"]
        # ordinary file-object reads are not the shim
        ok = "def load(f):\n    return f.read()\n"
        assert lint_source(ok, "repro/serve/old.py") == []

    def test_allow_marker_suppresses(self):
        src = (
            "import time\n\n"
            "def stamp():\n"
            "    return time.time()  # lint: allow-wallclock\n"
        )
        assert lint_source(src, "repro/storage/leak.py") == []

    def test_kernel_blockspec_misalignment(self):
        src = (
            "import jax.experimental.pallas as pl\n"
            "def launch(x, *, block_s=7):\n"
            "    grid = (4, 2)\n"
            "    spec = pl.BlockSpec((block_s, 100), lambda i: (i, 0))\n"
        )
        diags = check_kernel_source(src, "repro/kernels/bad/kernel.py")
        assert rules(diags) == ["KRN001", "KRN002", "KRN003"]
        ok = (
            "import jax.experimental.pallas as pl\n"
            "def launch(x, *, block_s=512):\n"
            "    grid = (4,)\n"
            "    spec = pl.BlockSpec((block_s, 256), lambda i: (i, 0))\n"
        )
        assert check_kernel_source(ok, "repro/kernels/ok/kernel.py") == []

    def test_module_constant_blockspec_alignment(self):
        """The matchrank kernel sizes its top-k carry blocks by a
        module-level constant (``K_PAD``); the checker must resolve it —
        both to keep the real kernel honest and to flag a bad edit."""
        from repro.analysis import check_kernel_file

        real = os.path.join(SRC, "kernels", "matchrank", "kernel.py")
        assert check_kernel_file(real) == []
        doctored = (
            "import jax.experimental.pallas as pl\n"
            "K_PAD = 100\n"  # not 1 and not a lane multiple
            "def merge(b, c_pad=256):\n"
            "    grid = (b,)\n"
            "    out = pl.BlockSpec((1, K_PAD), lambda bi: (bi, 0))\n"
        )
        diags = check_kernel_source(doctored, "repro/kernels/matchrank/bad.py")
        assert rules(diags) == ["KRN001"]

    def test_matchrank_rank_slot_blockspecs(self):
        """The matchrank kernel sizes its rank-form blocks by two module
        constants, ``2 * RANK_SLOTS * REQ_BLOCK`` rows; the checker must
        resolve them — the real kernel passes, a slot count that breaks
        the sublane tiling is flagged."""
        from repro.analysis import check_kernel_file

        real = os.path.join(SRC, "kernels", "matchrank", "kernel.py")
        assert check_kernel_file(real) == []
        doctored = (
            "import jax.experimental.pallas as pl\n"
            "REQ_BLOCK = 8\n"
            "RANK_SLOTS = 3\n"
            "def launch(nb, a_pad, block_s=512):\n"
            "    grid = (nb, 4)\n"
            "    ok = pl.BlockSpec((2 * RANK_SLOTS * REQ_BLOCK, 1), lambda bi, si: (bi, 0))\n"
            "    bad = pl.BlockSpec((RANK_SLOTS, block_s), lambda bi, si: (bi, si))\n"
        )
        diags = check_kernel_source(doctored, "repro/kernels/matchrank/bad.py")
        assert rules(diags) == ["KRN002"]
        assert diags[0].span.line == 7


class TestCleanTree:
    def test_repo_sources_and_ads_have_zero_findings(self):
        report = build_report([SRC], [ADS_DIR])
        assert list(report) == [], report.render()
        assert report.checked_files > 50
        assert report.checked_ads >= 3
        assert report.ok

    def test_report_is_deterministic(self):
        a = build_report([SRC], [ADS_DIR]).to_dict()
        b = build_report([SRC], [ADS_DIR]).to_dict()
        assert a == b


# ------------------------------------------------------------- broker wiring
@pytest.fixture
def grid():
    g = build_demo_grid(4, 2, seed=3)
    g.add_client("client://c0", zone="zone1")
    g.replicate("f-0", b"z" * (1 << 20), ["gsiftp://ep000", "gsiftp://ep002"])
    return g


CONSTANT_RANK_AD = (
    "clientUrl = \"client://c0\"; reqdSpace = 1G;"
    " requirements = other.availableSpace >= 0; rank = 1.0;"
)


class TestBrokerAdCheck:
    def test_warn_mode_records_into_audit(self, grid):
        b = grid.broker_for("client://c0")  # ad_check defaults to "warn"
        res = b.select("f-0", parse_classad(CONSTANT_RANK_AD))
        assert len(res) == 2
        rec = b.explain(b.last_request_id)
        assert [d["rule"] for d in rec.ad_diagnostics] == ["AD106"]
        assert rec.ad_diagnostics[0]["severity"] == "warning"
        assert b.stats["ad_findings"] == 1

    def test_clean_request_records_nothing(self, grid):
        b = grid.broker_for("client://c0")
        b.select("f-0")
        rec = b.explain(b.last_request_id)
        assert rec.ad_diagnostics == []

    def test_strict_mode_refuses_error_ads(self, grid):
        b = grid.broker_for("client://c0", ad_check="strict")
        bad = parse_classad(
            "requirements = 1 > 2; rank = other.AvgRDBandwidth;"
        )
        with pytest.raises(AdValidationError, match="AD104"):
            b.select("f-0", bad)
        rec = b.explain(b.last_request_id)
        assert rec.error.startswith("AdValidationError")
        assert [d["rule"] for d in rec.ad_diagnostics] == ["AD104"]

    def test_strict_mode_passes_clean_ads(self, grid):
        b = grid.broker_for("client://c0", ad_check="strict")
        assert len(b.select("f-0")) == 2

    def test_off_mode_skips_analysis(self, grid):
        b = grid.broker_for("client://c0", ad_check="off")
        b.select("f-0", parse_classad(CONSTANT_RANK_AD))
        rec = b.explain(b.last_request_id)
        assert rec.ad_diagnostics == []
        assert len(b._ad_diag_cache) == 0

    def test_analysis_is_memoized_per_ad_source(self, grid):
        b = grid.broker_for("client://c0")
        b.select("f-0")
        b.select("f-0")
        assert len(b._ad_diag_cache) == 1

    def test_select_many_nonstrict_isolates_bad_ad(self, grid):
        b = grid.broker_for("client://c0", ad_check="strict")
        bad = parse_classad("requirements = 1 > 2; rank = other.AvgRDBandwidth;")
        results = b.select_many(
            [("f-0", None), ("f-0", bad)], strict=False
        )
        assert len(results[0]) == 2
        assert isinstance(results[1], AdValidationError)

    def test_invalid_mode_rejected(self, grid):
        with pytest.raises(ValueError):
            grid.broker_for("client://c0", ad_check="loud")


class TestGrisPolicyCheck:
    def test_error_policy_refused_at_registration(self):
        with pytest.raises(SchemaError, match="AD102"):
            StorageGRIS(
                "volume=/x", {"requirements": "other.clientUrl > 5"},
                clock=Clock(),
            )

    def test_warning_policy_registers_with_findings(self):
        g = StorageGRIS(
            "volume=/x", {"requirements": "other.reqdFoo <= 10G"},
            clock=Clock(),
        )
        assert [d.rule for d in g.policy_diagnostics] == ["AD101"]
        assert g.policy_diagnostics[0].severity is Severity.WARNING

    def test_validate_false_keeps_findings_without_raising(self):
        g = StorageGRIS(
            "volume=/x", {"requirements": "other.clientUrl > 5"},
            clock=Clock(), validate=False,
        )
        assert [d.rule for d in g.policy_diagnostics] == ["AD102"]

    def test_set_static_reanalyzes(self):
        g = StorageGRIS("volume=/x", {}, clock=Clock())
        assert g.policy_diagnostics == []
        with pytest.raises(SchemaError):
            g.set_static("requirements", "other.clientUrl > 5")

    def test_demo_grid_policies_are_clean(self, grid):
        for ep in grid.endpoints:
            g = grid.gris_for(ep)
            if g is not None:
                assert g.policy_diagnostics == []


# ----------------------------------------------------------------- CLI / JSON
class TestRunner:
    def test_gate_fails_on_bad_ad_and_writes_report(self, tmp_path, capsys):
        bad = tmp_path / "bad.ad"
        bad.write_text(
            "requirements = other.availabelSpace > 5G;"
            " rank = other.AvgRDBandwidth;\n"
        )
        out = tmp_path / "report.json"
        rc = main(["--ads", str(bad), "--json", str(out)])
        assert rc == 1
        payload = json.loads(out.read_text())
        assert payload["version"] == 1
        assert payload["tool"] == "repro.analysis"
        assert payload["ok"] is False
        assert payload["by_rule"] == {"AD101": 1}
        assert payload["checked_ads"] == 1
        (d,) = payload["diagnostics"]
        assert d["rule"] == "AD101" and d["severity"] == "error"
        assert "availabelSpace" in d["message"]
        listing = capsys.readouterr().out
        assert "AD101" in listing

    def test_gate_passes_on_clean_inputs(self, tmp_path):
        rc = main([os.path.join(SRC, "analysis"), "--ads", ADS_DIR,
                   "--json", str(tmp_path / "r.json")])
        assert rc == 0
        payload = json.loads((tmp_path / "r.json").read_text())
        assert payload["ok"] is True and payload["diagnostics"] == []

    def test_lint_flags_injected_file_on_disk(self, tmp_path):
        pkg = tmp_path / "repro" / "storage"
        pkg.mkdir(parents=True)
        (pkg / "leak.py").write_text(
            "import time\n\ndef stamp():\n    return time.time()\n"
        )
        rc = main([str(tmp_path)])
        assert rc == 1


class TestDiagnosticModel:
    def test_severity_ordering(self):
        assert Severity.INFO < Severity.WARNING < Severity.ERROR

    def test_report_counts_and_ok(self):
        report = Report()
        assert report.ok
        report.extend(check_ad_text("requirements = 2 > 1; rank = 1;"))
        assert report.counts()["warning"] == 2  # AD105 + AD106
        assert report.ok  # warnings do not fail the gate
        report.extend(check_ad_text("requirements = 1 > 2; rank = 1.0;"))
        assert not report.ok

    def test_render_one_line_per_finding(self):
        (d,) = check_ad_text("reqdSpace = 5G;\nrank = other.AvgRDBandwidth;\n",
                             name="x.ad")
        line = d.render()
        assert line.startswith("x.ad") and "AD107" in line and "warning" in line
