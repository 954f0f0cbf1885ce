"""The harness on the CPU at a tiny size: names found, seeds, latency from
the due time, devices refused, and the check failing on broken paths."""

import json
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import harness, reference, work
from benchmarks.chip.control import bf16_scores
from benchmarks.chip.grid import build_world
from benchmarks.chip.traffic import Traffic, request_mix

ROOT = harness.ROOT
SPEC = harness.load_spec()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]

#: a deployment small enough for the Pallas interpreter, with a short TTL so
#: that a 1.5 s window crosses epochs
TINY = dict(endpoints=48, files=200, gris_ttl_s=0.5, snapshot_ttl_s=0.5,
            scheduler={"max_batch": 4, "max_delay_s": 0.005})


#: readers that send no ad (the broker's default read request, host
#: columnar tier): the analysis cell's arrivals with ``templates/default_read``
DEFAULT_READ = "default_read"


def tiny_cell(name, rate=30.0):
    if name == DEFAULT_READ:
        cell = tiny_cell("wlcg.analysis_zipf", rate)
        cell.traffic = dict(cell.traffic, requests=DEFAULT_READ)
        return cell
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, **TINY)
    if cell.traffic["loop"] == "open":
        cell.traffic = dict(cell.traffic, rate_rps=rate)
    else:
        cell.traffic = dict(cell.traffic, readers=8)
    return cell


def run_tiny(name, seed=7, seconds=1.5, **kw):
    return harness.run_cell(tiny_cell(name), seed, seconds, False,
                            t_process=time.perf_counter(), require_chip=False, **kw)


# ------------------------------------------------------------------ names
@pytest.mark.parametrize("name", WORKLOADS)
def test_cells_found_by_name(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] in {c["name"] for c in SPEC["configs"]}
    for part in request_mix(cell.traffic):
        assert (harness.HERE / "templates" / f"{part['templates']}.json").exists()
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.load_reader(m["name"]))


def test_every_metric_has_a_reader():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        base = m["name"].split(".", 1)[0]
        assert (harness.HERE / "metrics" / f"{base}.py").exists(), m["name"]
    assert harness.load_reader("lowering_ms.closed") is not None


def test_config_files_state_source_and_cuts():
    for c in SPEC["configs"]:
        with open(ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["assumed"] and cfg["guarantees"]


# ------------------------------------------------------------------ seeds
@pytest.mark.parametrize("name", ["wlcg.analysis_zipf", "fleet15k.restore_open"])
def test_same_seed_same_requests(name):
    cell = tiny_cell(name)
    big = 2**31 + 12345

    def draw(seed):
        w = build_world(cell.config, seed)
        due, reqs = Traffic(cell.traffic, w, seed).open_arrivals(2.0)
        return due, [(r.lfn, r.template, r.reqd_space) for r in reqs], w

    d1, r1, w1 = draw(big)
    d2, r2, w2 = draw(big)
    d3, r3, _ = draw(big + 1)
    assert np.array_equal(d1, d2) and r1 == r2
    assert np.array_equal(w1.state(3)[0], w2.state(3)[0])
    assert len(d3) == len(d1)  # another seed: the same amount of work
    assert r3 != r1


# --------------------------------------------------------------- latency
def test_open_loop_latency_from_due(monkeypatch):
    """A stall of the broker counts in full for every request due during
    it, though each is answered soon after it is submitted."""
    from repro.core.broker import DataBroker

    orig = DataBroker.select_many
    calls = {"n": 0}

    def stalled(self, queries, **kw):
        calls["n"] += 1
        if calls["n"] == 2:  # the first is set-up's warm-up flush
            time.sleep(0.6)
        return orig(self, queries, **kw)

    monkeypatch.setattr(DataBroker, "select_many", stalled)
    out = run_tiny("wlcg.analysis_zipf", seconds=1.5)
    assert out["correct"]
    # a tenth of the requests were due during the stall
    assert out["metrics"]["select_p99_ms"]["value"] > 300.0
    assert out["metrics"]["select_p50_ms"]["value"] < 300.0


# --------------------------------------------------------------- devices
def test_unknown_device_kind_refused():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(work.UnknownDevice):
        work.peaks("TPU v99")


def test_measured_path_fails_without_a_chip(tmp_path):
    with pytest.raises(harness.NoChip):
        harness.device_info(1)
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload", WORKLOADS[0],
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 1 and "{" not in p.stdout
    # a directory holding only BENCHMARK.json and the benchmark: no program
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip")
    p = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and "{" not in p.stdout


def test_work_count():
    tpl = {"requirements": [["availableSpace", ">", 1], ["loadFactor", "<", 2]],
           "rank": {"diskTransferRate": 1, "loadFactor": -3}}
    ops, nbytes = work.launch_work([tpl, tpl], rows=1000)
    assert ops == 2 * 1000 * (2 + 2 * 2)
    assert nbytes == 1000 * 3 * 2 * 4 + 2 * 8
    share, bound = work.roofline_share(ops, nbytes, 1e-3, {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    assert bound == "memory" and share == pytest.approx(100.0 * nbytes / 1e9 / 1e-3)


# ------------------------------------------------------------- the check
def test_reference_tie_groups():
    want = [("a", 2.0), ("b", 1.0), ("c", 1.0)]
    assert reference.compare(("ok", [("a", 2.0), ("b", 1.0), ("c", 1.0)]), want) == (True, 0.0)
    # an exact tie is ordered by host name
    assert not reference.compare(("ok", [("a", 2.0), ("c", 1.0), ("b", 1.0)]), want)[0]
    assert not reference.compare(("ok", [("b", 1.0), ("a", 2.0), ("c", 1.0)]), want)[0]
    # ranks a rounding apart may come in the order of the ranks served
    near = [("a", 1.0 + 1e-7), ("b", 1.0)]
    assert reference.compare(("ok", [("b", 1.0 + 2e-7), ("a", 1.0 + 1e-7)]), near)[0]
    assert not reference.compare(("ok", [("b", 1.0), ("a", 1.0 + 1e-7)]), near)[0]
    assert reference.compare(("NoMatchError", []), []) == (True, 0.0)
    assert not reference.compare(("ok", [("a", 1.0)]), [])[0]


def test_snapshot_epochs():
    # TTL 5: rebuilt by the first flush at 5 s or later, from that epoch's state
    assert reference.snapshot_epochs([0.1, 4.9, 5.2, 9.9, 10.1, 10.3, 15.3], 5.0) == [0, 0, 1, 1, 1, 2, 3]


def _stale(s):
    s.broker.snapshot_ttl = 1e9


def _half_left_out(s):
    orig = s.broker.select_many

    def half(queries, **kw):
        k = (len(queries) + 1) // 2
        return orig(queries[:k], **kw) + [None] * (len(queries) - k)

    s.broker.select_many = half


def _policy_ignored(s):
    """The site usage policies are not folded in: every row is admitted."""
    def admit_all(src, req, vocab, env=None):
        return lambda table, xp: SimpleNamespace(ok=True, val=True)

    s.broker.plan_cache.policy_fn = admit_all


def _ties_reversed(s):
    """Equal ranks come in reverse host-name order."""
    orig = s.broker._ranked_from_scores

    def reversed_ties(lfn, replicas, st, mask, score):
        out = orig(lfn, replicas, st, mask, score)
        out.sort(key=lambda rr: rr.pfn.endpoint, reverse=True)
        out.sort(key=lambda rr: -rr.rank)
        return out

    s.broker._ranked_from_scores = reversed_ties


def _answer_altered(s):
    orig = s.broker._ranked_from_scores

    def altered(lfn, replicas, st, mask, score):
        out = orig(lfn, replicas, st, mask, score)
        for rr in out:
            rr.rank *= 1.0 + 1e-4
        return out

    s.broker._ranked_from_scores = altered


@pytest.mark.parametrize("name", [DEFAULT_READ, "wlcg.analysis_zipf", "fleet15k.restore_closed"])
def test_sound_run_is_correct(name):
    out = run_tiny(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["notes"]["snapshot_builds"] >= 2
    # the site policy refuses part of every kernel mix (default reads need no space)
    assert (out["notes"]["policy_refused_pct"] > 0) == (name != DEFAULT_READ)


@pytest.mark.parametrize(
    "fault", [_stale, _half_left_out, _answer_altered, _policy_ignored, _ties_reversed, bf16_scores]
)
@pytest.mark.parametrize("name", ["wlcg.analysis_zipf", "fleet15k.restore_open"])
def test_broken_path_is_not_correct(name, fault):
    out = run_tiny(name, fault=fault)
    assert not out["correct"], out["checks"]
