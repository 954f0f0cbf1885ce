"""CPU rehearsal of the chip smoke: the served selection path at a tiny
size in interpret mode, the platform-derived interpret flag, the compile
cache's location, and ``chip_smoke.py`` refusing to run without a TPU."""

import importlib.util
from pathlib import Path

import jax

from repro.kernels import resolve_interpret
from repro.launch import cache
from repro.launch.smoke import run_smoke

ROOT = Path(__file__).resolve().parents[1]


def test_interpret_resolves_from_cpu_backend():
    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False


def test_smoke_core_tiny_interpret():
    report = run_smoke(endpoints=64, files=32, replicas=3, flushes=2, batch=8, seed=3)
    assert report.requests == 24  # warm-up flush + 2 timed flushes of 8
    assert report.paths == {"batched_kernel": 24}
    assert report.kernel_requests == 24
    assert report.kernel_launches == 2  # one kernel launch per timed flush
    assert report.mismatches == []
    assert report.ok
    assert 0 < report.matched <= 24
    assert report.snapshot_shape == (512, 128)
    assert not report.mosaic  # interpret mode on the CPU: no Mosaic call
    assert len(report.flush_s) == 2


def test_smoke_default_ad_phase_tiny():
    """The default-ad phase: history, site averages and breakers published,
    requests sent with no ad, the kernel against the interpreter."""
    report = run_smoke(endpoints=96, files=48, replicas=4, flushes=1, batch=8, seed=5)
    d = report.default_ads
    assert d.requests == 32 and d.paths == {"batched_kernel": 32}
    assert d.guarded_requests == 32 and d.kernel_launches == 4
    assert d.mismatches == []
    assert set(d.branch_share) == {"EwmaRDBandwidthToSource", "AvgRDBandwidth", "static"}
    assert min(d.branch_share.values()) >= 0.1 and abs(sum(d.branch_share.values()) - 1) < 1e-9
    assert d.ok and report.ok
    assert report.kernel_requests == report.requests == 16  # the first phase's own count


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv(cache.ENV_VAR, "/elsewhere/cache")
    assert cache.compile_cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv(cache.ENV_VAR)
    assert cache.compile_cache_dir() == str(ROOT / ".jax_cache")


def test_chip_smoke_refuses_cpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) != 0
    assert capsys.readouterr().out == ""  # no result line, nothing run
