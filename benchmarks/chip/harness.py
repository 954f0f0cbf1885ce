"""The harness: one cell of ``BENCHMARK.json``, set up, driven, checked and
reduced to its metrics. ``run.py`` is its command line.

Everything that belongs to a configuration, a traffic mix or a metric is
found by name: ``configs/<file>`` (named in ``BENCHMARK.json``),
``traffic/<traffic>.json``, ``templates/<requests>.json`` and
``metrics/<metric>.py``. A quantity split by the end-to-end metric it
moves, ``<metric>.<variant>`` (``lowering_ms.closed``), is read by
``metrics/<metric>.py``. Adding a cell adds those files and entries; this
file does not change.

The timed path is the program's own serving path: ``DataGrid.broker_for``
with the kernel tier on and the benchmark's ``Tracer``, behind a
``BatchScheduler`` with the configuration's settings. A single-threaded
loop submits each request at its due time and polls for latency flushes in
between. The grid's injected clock follows the wall time since the window
began, so the GRIS and snapshot TTLs lapse as they do in a deployment, and
the published free space and load are redrawn every TTL epoch.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import reference, trace as trace_mod, work
from .grid import CLIENT, World, build_world
from .traffic import Request, Traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ specs
def load_spec(path: Optional[Path] = None) -> Dict[str, Any]:
    with open(path or ROOT / "BENCHMARK.json") as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: Optional[Dict[str, Any]] = None) -> Cell:
    spec = spec or load_spec()
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(HERE / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name,
        int(w["chips"]),
        config,
        w["traffic"],
        traffic,
        [m for m in spec["end_to_end"] if _applies(m, name)],
        [m for m in spec["per_layer"] if _applies(m, name)],
    )


def load_reader(metric: str) -> Callable[["RunData"], Optional[float]]:
    """``metrics/<metric>.py``'s ``read``; a variant ``<metric>.<variant>``
    without a file of its own is read by ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.exists():
        metric = metric.split(".", 1)[0]
        path = HERE / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------- device
def device_info(chips: int, require_chip: bool = True) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(
            f"the cell needs {chips} TPU chip(s); JAX found {len(devs)} {devs[0].platform} device(s)"
        )
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts traces and backend compiles (cache loads included) while on."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration", "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, duration: float, **kw) -> None:
        if self.on and event in self.EVENTS:
            self.count += 1


# ------------------------------------------------------------------- rig
@dataclass
class Rig:
    """What set-up builds for one run, and the window drives."""

    cell: Cell
    seed: int
    world: World
    traffic: Traffic
    broker: Any
    sched: Any
    tracer: Any
    kernel: bool  # the traffic lowers to the kernel tier
    phases: Dict[str, float]  # set-up phase → seconds


def setup(cell: Cell, seed: int, *, annotate: bool = False) -> Rig:
    """Grid, broker, scheduler and the warm-up every shape of the window
    needs: the snapshot, one kernel compile per batch size the scheduler
    can emit, and one flush through the whole path."""
    from repro.obs import Tracer
    from repro.serve.scheduler import BatchScheduler

    cfg = cell.config
    phases: Dict[str, float] = {}
    t = time.perf_counter()

    def lap(name: str) -> None:
        nonlocal t
        now = time.perf_counter()
        phases[name] = now - t
        t = now

    world = build_world(cfg, seed)
    traffic = Traffic(cell.traffic, world, seed)
    lap("grid")
    tracer = Tracer(jax_annotations=annotate, max_spans=1 << 22)
    broker = world.grid.broker_for(
        CLIENT, batch_use_kernel=True, tracer=tracer, snapshot_ttl=float(cfg["snapshot_ttl_s"])
    )
    sch = cfg["scheduler"]
    sched = BatchScheduler(
        broker, max_batch=int(sch["max_batch"]), max_delay=float(sch["max_delay_s"])
    )
    snap = broker.warm_snapshot(world.grid.alive_endpoints())
    lap("snapshot")
    kernel = traffic.kernel
    if kernel:
        _warm_kernel(broker, snap, traffic, int(sch["max_batch"]))
    lap("kernel_compiles")
    warm = traffic.warmup(int(sch["max_batch"]))
    tickets = [sched.submit(r.lfn, r.ad) for r in warm]
    sched.flush()
    if not all(tk.done for tk in tickets):
        raise RuntimeError("the warm-up flush left tickets unfilled")
    lap("warm_flush")
    tracer.clear()
    return Rig(cell, seed, world, traffic, broker, sched, tracer, kernel, phases)


def _warm_kernel(broker, snap, traffic: Traffic, max_batch: int) -> None:
    """Compile (or load from the cache) the kernel launch for B = 1..max_batch."""
    from repro.kernels.matchrank.ops import lower_request, matchrank_batched

    attrs, valid, n_rows = snap.device_columns()
    plans = [
        lower_request(traffic.request(0, t).ad, snap.vocab_key(), env=broker.env)
        for t in range(len(traffic.templates))
        if not traffic.as_none(t)
    ]
    for b in range(1, max_batch + 1):
        batch = [plans[i % len(plans)] for i in range(b)]
        matchrank_batched(
            attrs, valid, batch, admit=np.zeros((b, n_rows), np.float32),
            n_rows=n_rows, use_kernel=True,
        )


# ----------------------------------------------------------------- window
@dataclass
class Records:
    """What the window saw, times in seconds from the window's start."""

    due: List[float] = field(default_factory=list)
    fill: List[float] = field(default_factory=list)
    flush_of: List[int] = field(default_factory=list)
    reqs: List[Request] = field(default_factory=list)
    tickets: List[Any] = field(default_factory=list)
    flush_clock: List[float] = field(default_factory=list)  # clock of each flush
    flush_reqs: List[List[int]] = field(default_factory=list)
    lateness: List[float] = field(default_factory=list)  # submit - due (open loop)
    backlog_at_end: int = 0


class LoadLoop:
    """The single-threaded loop around ``submit`` and ``poll``."""

    def __init__(self, s: Rig, t_base: float):
        self.s = s
        self.t_base = t_base
        self.rec = Records()
        self.pending: List[int] = []
        self.oldest: Optional[float] = None
        self.max_delay = s.sched.max_delay
        self.clock = s.world.grid.clock
        self.w0 = 0.0

    def now(self) -> float:
        return time.perf_counter() - self.w0

    def _prepare(self, now: float) -> None:
        c = self.t_base + now
        self.s.world.apply(self.s.world.epoch_of(c))
        self.clock.set(c)

    def _after(self, batches_before: int) -> List[int]:
        if self.s.sched.stats["batches"] == batches_before:
            return []
        t = self.now()
        k = len(self.rec.flush_clock)
        self.rec.flush_clock.append(self.clock.now())
        done = self.pending
        self.rec.flush_reqs.append(done)
        for i in done:
            self.rec.fill[i] = t
            self.rec.flush_of[i] = k
        self.pending, self.oldest = [], None
        return done

    def submit(self, req: Request, due: float) -> List[int]:
        now = self.now()
        self._prepare(now)
        i = len(self.rec.due)
        self.rec.due.append(due)
        self.rec.fill.append(math.nan)
        self.rec.flush_of.append(-1)
        self.rec.reqs.append(req)
        self.rec.lateness.append(now - due)
        self.pending.append(i)
        if self.oldest is None:
            self.oldest = now
        b0 = self.s.sched.stats["batches"]
        self.rec.tickets.append(self.s.sched.submit(req.lfn, req.ad))
        return self._after(b0)

    def poll(self) -> List[int]:
        self._prepare(self.now())
        b0 = self.s.sched.stats["batches"]
        self.s.sched.poll()
        return self._after(b0)

    def idle_until(self, t: float) -> None:
        """Poll a due latency flush, or sleep a little towards ``t``."""
        now = self.now()
        if self.pending and now - self.oldest >= self.max_delay:
            self.poll()
            return
        nxt = t
        if self.pending:
            nxt = min(nxt, self.oldest + self.max_delay)
        if nxt > now:
            time.sleep(min(nxt - now, 0.001))

    def open_loop(self, due: np.ndarray, reqs: List[Request], seconds: float) -> Records:
        n = len(due)
        i = 0
        at_end = False
        self.w0 = time.perf_counter()
        while i < n or self.pending:
            now = self.now()
            if not at_end and now >= seconds:
                # requests due in the window and not yet answered
                at_end = True
                self.rec.backlog_at_end = n - i + len(self.pending)
            if i < n and due[i] <= now:
                self.submit(reqs[i], float(due[i]))
                i += 1
                continue
            self.idle_until(float(due[i]) if i < n else math.inf)
        return self.rec

    def closed_loop(self, readers: int, seconds: float) -> Records:
        """Each reader sends its next request when its last is answered; a
        request is due when its reader became ready (zero think time), so
        the wait for this single-threaded loop to get to it counts. Readers
        ready before the window's end are all served; later ones stop."""
        ready = deque((r, 0.0) for r in range(readers))
        reader_of: List[int] = []
        self.w0 = time.perf_counter()
        while True:
            if ready:
                r, t_ready = ready.popleft()
                if t_ready < seconds:
                    reader_of.append(r)
                    done = self.submit(self.s.traffic.next_closed(self.t_base + self.now()), t_ready)
                    ready.extend((reader_of[j], self.rec.fill[j]) for j in done)
                continue
            if not self.pending:
                break
            now = self.now()
            if now - self.oldest >= self.max_delay:
                ready.extend((reader_of[j], self.rec.fill[j]) for j in self.poll())
            else:
                time.sleep(min(self.oldest + self.max_delay - now, 0.001))
        return self.rec


# ------------------------------------------------------------- run data
@dataclass
class RunData:
    """Everything a metric reader may read (times in s from the window's start)."""

    cell: Cell
    seconds: float
    setup_s: float
    due: np.ndarray  # requests due in the window
    fill: np.ndarray
    flush_start: np.ndarray  # start of the scheduler.flush that answered each
    spans: List[Tuple[str, float, float, int, Optional[int], Dict[str, Any]]]
    flushes: int
    sched: Dict[str, float]  # scheduler.stats over the window
    plan_cache: Dict[str, float]  # plan-cache stats over the window
    broker: Dict[str, float]  # broker counters over the window
    trace: Optional[trace_mod.Summary] = None
    kernel_work: Tuple[float, float] = (0.0, 0.0)
    peaks: Optional[Dict[str, Any]] = None


def _delta(after: Dict[str, Any], before: Dict[str, Any]) -> Dict[str, float]:
    return {k: float(after[k]) - float(before.get(k, 0)) for k in after}


def _outcome(ticket) -> Tuple[str, List[Tuple[str, float]]]:
    from repro.core.broker import BrokerError

    if not ticket.done:
        return "unanswered", []
    try:
        res = ticket.result()
    except BrokerError as e:
        return type(e).__name__, []
    if res is None:  # a ticket filled with no outcome was never answered
        return "unanswered", []
    return "ok", [(rr.pfn.endpoint, float(rr.rank)) for rr in res]


def check(s: Rig, rec: Records, n_due: int,
          limits: Dict[str, float]) -> Tuple[Dict[str, Dict[str, float]], int, int]:
    """Compare every answer due in the window with the plain reference.

    → (checks {name: {value, limit}}, failed requests, requests whose
    answer a site policy changed)."""
    cfg = s.cell.config
    epochs = reference.snapshot_epochs(
        rec.flush_clock, float(cfg["snapshot_ttl_s"]), epoch_len=float(cfg["gris_ttl_s"])
    )
    unanswered = wrong = failed = refused = 0
    gap = 0.0
    for i in range(n_due):
        got = _outcome(rec.tickets[i])
        if got[0] == "unanswered":
            unanswered += 1
            failed += 1
            continue
        if got[0] not in ("ok", "NoMatchError"):
            failed += 1
        r = rec.reqs[i]
        want, n_refused = reference.answer(
            s.world, s.traffic.templates[r.template], r.reqd_space, r.file, epochs[rec.flush_of[i]]
        )
        refused += n_refused > 0
        same, g = reference.compare(got, want)
        wrong += not same
        gap = max(gap, g)
    checks = {
        "unanswered": {"value": unanswered, "limit": limits["unanswered"]},
        "wrong_rankings": {"value": wrong, "limit": limits["wrong_rankings"]},
        "rank_gap": {"value": gap, "limit": limits["rank_gap"]},
    }
    return checks, failed, refused


#: limits of the numbers compared (PERF.md gives the readings they come from)
LIMITS = {"unanswered": 0, "wrong_rankings": 0, "rank_gap": 1e-5}


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_process: float,
    require_chip: bool = True,
    fault: Optional[Callable[[Rig], None]] = None,
    keep_trace: Optional[str] = None,
    counter: Optional[CompileCounter] = None,
) -> Dict[str, Any]:
    """One run: set-up, window, drain, metrics and the check → result line."""
    import jax

    device = device_info(cell.chips, require_chip)
    t_jax = time.perf_counter() - t_process
    enable_compile_cache()
    counter = counter or CompileCounter()
    counter.count = 0
    s = setup(cell, seed, annotate=trace)
    if fault is not None:
        fault(s)
    drv = LoadLoop(s, t_base=0.0)
    due = reqs = None
    if s.traffic.loop == "open":
        t = time.perf_counter()
        due, reqs = s.traffic.open_arrivals(seconds)
        s.phases["arrivals"] = time.perf_counter() - t
    stats0 = (dict(s.sched.stats), dict(s.broker.plan_cache.stats), dict(s.broker.stats))
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the host path is Python: trace only the spans
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_process
    counter.on = True
    # the traced window, on the profiler's own clock
    with jax.profiler.TraceAnnotation("bench.window"):
        if s.traffic.loop == "open":
            rec = drv.open_loop(due, reqs, seconds)
        else:
            rec = drv.closed_loop(int(s.cell.traffic["readers"]), seconds)
    counter.on = False
    compiles = counter.count
    summary = None
    if trace:
        jax.profiler.stop_trace()
        ev = trace_mod.load_events(trace_mod.find_xplane(log_dir), ("scheduler.", "broker.", "bench."))
        shutil.rmtree(log_dir, ignore_errors=True)
        if keep_trace:
            trace_mod.save_events(ev, keep_trace)
        win = [e for e in ev.host if e[0] == "bench.window"]
        summary = trace_mod.summarize(ev, (win[0][1], win[0][1] + win[0][2]))
    mem = _memory_peak()
    stats1 = (dict(s.sched.stats), dict(s.broker.plan_cache.stats), dict(s.broker.stats))

    due_arr = np.asarray(rec.due)
    n_due = int((due_arr < seconds).sum())
    spans = _window_spans(s.tracer, drv.w0)
    flush_spans = [sp for sp in spans if sp[0] == "scheduler.flush"]
    if len(flush_spans) != len(rec.flush_clock):
        raise RuntimeError(f"{len(flush_spans)} flush spans for {len(rec.flush_clock)} flushes")
    fstart = np.asarray([sp[1] for sp in flush_spans])
    flush_of = np.asarray(rec.flush_of[:n_due])
    run = RunData(
        cell=cell,
        seconds=seconds,
        setup_s=setup_s,
        due=due_arr[:n_due],
        fill=np.asarray(rec.fill[:n_due]),
        flush_start=fstart[flush_of] if n_due else np.zeros(0),
        spans=spans,
        flushes=len(rec.flush_clock),
        sched=_delta(stats1[0], stats0[0]),
        plan_cache=_delta(stats1[1], stats0[1]),
        broker=_delta(stats1[2], stats0[2]),
        trace=summary,
    )
    if trace:
        run.peaks = work.peaks(device["kind"]) if require_chip else None
        if s.kernel:
            run.kernel_work = _kernel_work(s, rec)
    metrics: Dict[str, Any] = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks, failed, refused = check(s, rec, n_due, LIMITS)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device["memory_peak_bytes"] = mem
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    out: Dict[str, Any] = {
        "correct": correct,
        "attempted": n_due,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.top_ops(), "idle_gaps": summary.top_idle()}
    out["notes"] = {
        "compiles_in_window": compiles,
        "flushes": run.flushes,
        "generator_late_p99_ms": float(np.percentile(np.asarray(rec.lateness), 99) * 1e3) if rec.lateness else 0.0,
        "backlog_at_end": rec.backlog_at_end,
        "snapshot_builds": run.broker.get("snapshot_builds", 0.0),
        "policy_refused_pct": 100.0 * refused / n_due if n_due else 0.0,
        "setup_phases_s": dict(start_and_jax=t_jax, **s.phases),
    }
    out["checks"] = checks
    return out


def _window_spans(tracer, w0: float):
    """The tracer's spans from the window's start on, as tuples with times
    in seconds from the window's start."""
    if tracer.dropped:
        raise RuntimeError(f"the tracer dropped {tracer.dropped} spans")
    return [
        (sp.name, sp.t0 - w0, sp.t1 - w0, sp.span_id, sp.parent_id, sp.args)
        for sp in tracer.spans()
        if sp.t0 >= w0
    ]


def _kernel_work(s: Rig, rec: Records) -> Tuple[float, float]:
    """Operations and bytes of every kernel launch of the window and drain."""
    rows = len(s.world.urls)
    ops = nbytes = 0.0
    for members in rec.flush_reqs:
        sent = [rec.reqs[i].template for i in members]
        tpls = [s.traffic.templates[t] for t in sent if not s.traffic.as_none(t)]
        if tpls:
            o, b = work.launch_work(tpls, rows)
            ops += o
            nbytes += b
    return ops, nbytes


def _memory_peak() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))
