"""A grid deployment built from a configuration file and a seed.

:func:`build_world` makes the program's ``DataGrid``: storage endpoints with
seeded disk rates, the configuration's usage policy on every ``every``-th
endpoint, and a replica catalog of seeded file sizes and replica sites. It
keeps its own record of all of it (``World``), from which the plain
reference (``reference.py``) answers without reading anything the program
made.

The published dynamic state (free space and load) is redrawn from the seed
for every TTL epoch ``e = floor(clock / gris_ttl)``: :meth:`World.state`
gives it, :meth:`World.apply` writes it into the endpoints, whose GRIS
providers then publish it when their TTL lapses.

The construction follows ``repro.launch.smoke.build_smoke_grid``; it is copied
here so that a change to the program cannot move the benchmark's grid.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import numpy as np

CLIENT = "client://bench"

#: rng stream tags, so that each kind of draw has its own stream per seed
_GRID, _FILES, _EPOCH = 1, 2, 3


def policy_source(policy: Dict[str, Any]) -> str:
    """The usage policy as the ClassAd ``requirements`` a site publishes."""
    return f"other.{policy['attr']} {policy['op']} {int(policy['value'])}"


@dataclass
class World:
    """The deployment as the benchmark made it."""

    config: Dict[str, Any]
    seed: int
    grid: Any  # repro.storage.endpoint.DataGrid
    urls: List[str]
    disk_rate: np.ndarray  # [n] bytes/s
    has_policy: np.ndarray  # [n] bool
    lfns: List[str]
    file_bytes: np.ndarray  # [files] int64
    replicas: List[np.ndarray]  # per file: endpoint indices
    applied_epoch: int = -1
    _states: Dict[int, Tuple[np.ndarray, np.ndarray]] = field(default_factory=dict)

    @property
    def ttl(self) -> float:
        return float(self.config["gris_ttl_s"])

    def epoch_of(self, clock: float) -> int:
        return int(np.floor(clock / self.ttl))

    def state(self, epoch: int) -> Tuple[np.ndarray, np.ndarray]:
        """→ (free bytes [n] int64, load factor [n] int64) of a TTL epoch."""
        hit = self._states.get(epoch)
        if hit is None:
            cfg = self.config
            rng = np.random.default_rng([self.seed, _EPOCH, epoch])
            lo, hi = cfg["free_space_units"]
            n = len(self.urls)
            free = rng.integers(lo, hi + 1, n).astype(np.int64) * int(
                cfg["free_space_unit_bytes"]
            )
            llo, lhi = cfg["load_factor"]
            load = rng.integers(llo, lhi, n).astype(np.int64)
            hit = self._states[epoch] = (free, load)
        return hit

    def apply(self, epoch: int) -> None:
        """Write an epoch's free space and load into the endpoints."""
        if epoch == self.applied_epoch:
            return
        free, load = self.state(epoch)
        eps = self.grid.endpoints
        for url, f, ld in zip(self.urls, free.tolist(), load.tolist()):
            ep = eps[url]
            ep.capacity = f  # published as availableSpace (nothing is stored)
            ep.active_transfers = ld  # published as loadFactor
        self.applied_epoch = epoch


def _distinct_rows(rng: np.random.Generator, n: int, rows: int, k: int) -> np.ndarray:
    """[rows, k] endpoint indices, distinct within each row."""
    out = rng.integers(0, n, (rows, k))
    for _ in range(64):
        srt = np.sort(out, axis=1)
        dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
        if not dup.any():
            return out
        out[dup] = rng.integers(0, n, (int(dup.sum()), k))
    raise RuntimeError("could not draw distinct replica sites")


def build_world(config: Dict[str, Any], seed: int) -> World:
    """The grid of ``config``, drawn from ``seed``, at TTL epoch 0."""
    from repro.storage.endpoint import DataGrid
    from repro.core.catalog import PhysicalFile

    cfg = config
    n = int(cfg["endpoints"])
    rng = np.random.default_rng([seed, _GRID])
    rates = np.asarray(cfg["disk_rates_bytes_per_s"], dtype=np.float64)
    disk_rate = rates[rng.integers(0, len(rates), n)]
    every = int(cfg["usage_policy"]["every"])
    has_policy = (np.arange(n) % every) == 0 if every else np.zeros(n, bool)
    policy = policy_source(cfg["usage_policy"])

    grid = DataGrid(seed=seed)
    grid.clock.set(0.0)
    zones, sites = int(cfg["zones"]), int(cfg["sites"])
    urls = [f"gsiftp://ep{i:05d}" for i in range(n)]
    for i, url in enumerate(urls):
        grid.add_endpoint(
            url,
            zone=f"zone{i % zones:03d}",
            region=f"site{i % sites:03d}",
            disk_rate=float(disk_rate[i]),
            policy=policy if has_policy[i] else None,
            gris_ttl=float(cfg["gris_ttl_s"]),
        )
    grid.add_client(CLIENT, zone="zone000")

    frng = np.random.default_rng([seed, _FILES])
    files = int(cfg["files"])
    lo, hi = cfg["file_bytes"]
    file_bytes = frng.integers(int(lo), int(hi) + 1, files).astype(np.int64)
    rlo, rhi = cfg["replicas_per_file"]
    counts = frng.integers(int(rlo), int(rhi) + 1, files)
    sites_of = _distinct_rows(frng, n, files, int(rhi))
    lfns = [f"file-{f:06d}" for f in range(files)]
    replicas: List[np.ndarray] = []
    catalog = grid.catalog
    for f, lfn in enumerate(lfns):
        eps = sites_of[f, : counts[f]]
        replicas.append(eps)
        size = int(file_bytes[f])
        for e in eps.tolist():
            catalog.register_replica(lfn, PhysicalFile(urls[e], f"/data/{lfn}", size))

    world = World(cfg, seed, grid, urls, disk_rate, has_policy, lfns, file_bytes, replicas)
    world.apply(0)
    return world
