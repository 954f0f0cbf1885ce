"""The one traffic generator: reads a traffic mix's parameters and draws
its requests from the seed.

A mix (``traffic/<name>.json``) names its loop (``open`` with a rate, or
``closed`` with a number of readers), its file popularity (``uniform``, or
``zipf`` with exponent ``s`` and a hot set that rotates every ``rotate_s``
seconds) and its requests: one template file (``templates/<name>.json``),
or a list of them with weights, each template of a file equally likely. A template
is data: conjunctive thresholds and a linear rank (or the default read
ad's quotient), rendered here as the ClassAd a client sends. The plain
reference evaluates the same template without the program's ClassAd code.
A request's ``reqdSpace`` is the file's size where its template file says
``"reqd_space": "file_bytes"`` (times the template's ``stage_files``, a
client that stages that many files of the size at once), else the number
given.

Open loop: the arrival count is fixed at ``round(rate * seconds)`` and the
due times are that many sorted uniform draws over the window (a Poisson
process conditioned on its count), so every seed offers the same amount
of work in another order.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent

#: rng stream tags (grid.py uses 1 to 3)
_ARRIVALS, _WARMUP, _ROTATION, _CLOSED = 11, 12, 13, 14


def load_templates(name: str) -> Dict[str, Any]:
    with open(HERE / "templates" / f"{name}.json") as f:
        return json.load(f)


def render_rank(rank: Dict[str, Any]) -> str:
    if "quotient" in rank:
        num, den = rank["quotient"]
        return f"other.{num} / (1 + other.{den})"
    parts: List[str] = []
    for attr, w in rank.items():
        w = float(w)
        mag = "" if abs(w) == 1 else f"{abs(w):.17g} * "
        sign = "-" if w < 0 else "+"
        parts.append(f"{sign} {mag}other.{attr}")
    text = " ".join(parts)
    return text[2:] if text.startswith("+ ") else text


def render_ad(template: Dict[str, Any], reqd_space: int) -> str:
    """The ClassAd source of one request."""
    reqs = " && ".join(
        f"other.{attr} {op} {int(v) if float(v).is_integer() else float(v)!r}"
        for attr, op, v in template["requirements"]
    ) or "true"
    return (
        f"reqdSpace = {int(reqd_space)}; rank = {render_rank(template['rank'])};"
        f" requirements = {reqs};"
    )


def request_mix(params: Dict[str, Any]) -> List[Dict[str, Any]]:
    """A mix's requests as [{templates, weight}] (one file: weight 1)."""
    mix = params["requests"]
    return [{"templates": mix, "weight": 1.0}] if isinstance(mix, str) else mix


@dataclass
class Request:
    """One request as the window submits it."""

    file: int
    template: int
    lfn: str
    ad: Any  # ClassAd, or None for the broker's default read request
    reqd_space: int


class Traffic:
    """The requests of one traffic mix over one world, from the seed."""

    def __init__(self, params: Dict[str, Any], world, seed: int):
        self.params = params
        self.world = world
        self.seed = seed
        mix = request_mix(params)
        self.docs = [load_templates(m["templates"]) for m in mix]
        #: every template of the mix, each with the index of its document
        self.templates: List[Dict[str, Any]] = []
        weights: List[float] = []
        for d, (m, doc) in enumerate(zip(mix, self.docs)):
            for tpl in doc["templates"]:
                self.templates.append(dict(tpl, doc=d))
                weights.append(float(m["weight"]) / len(doc["templates"]))
        self.cum_weights = np.cumsum(weights) / sum(weights)
        pop = params["popularity"]
        self.files = len(world.lfns)
        self.zipf_cdf: Optional[np.ndarray] = None
        if pop["kind"] == "zipf":
            w = 1.0 / np.arange(1, self.files + 1, dtype=np.float64) ** float(pop["s"])
            self.zipf_cdf = np.cumsum(w) / w.sum()
            self.rotate_s = float(pop["rotate_s"])
        elif pop["kind"] != "uniform":
            raise ValueError(f"unknown popularity {pop['kind']!r}")
        self._perms: Dict[int, np.ndarray] = {}
        self._ads: Dict[Tuple[int, int], Any] = {}
        self._closed_rng = np.random.default_rng([seed, _CLOSED])
        self._closed_buf: List[Tuple[float, int]] = []

    def as_none(self, template: int) -> bool:
        """The template is the broker's default read ad: the client sends None."""
        return bool(self.docs[self.templates[template]["doc"]].get("submit_as_none", False))

    @property
    def kernel(self) -> bool:
        """Some template of the mix is sent as an ad (and may lower to the kernel)."""
        return not all(self.as_none(t) for t in range(len(self.templates)))

    # ----------------------------------------------------------- drawing
    @property
    def loop(self) -> str:
        return self.params["loop"]

    def _draw(self, rng: np.random.Generator, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """n (popularity draw in [0, 1), template index) pairs."""
        u, v = rng.random(n), rng.random(n)
        return u, np.minimum(np.searchsorted(self.cum_weights, v, side="right"), len(self.templates) - 1)

    def _file_of(self, u: float, clock: float) -> int:
        if self.zipf_cdf is None:
            return min(int(u * self.files), self.files - 1)
        rank = min(int(np.searchsorted(self.zipf_cdf, u, side="right")), self.files - 1)
        period = int(clock // self.rotate_s)
        perm = self._perms.get(period)
        if perm is None:
            perm = np.random.default_rng([self.seed, _ROTATION, period]).permutation(self.files)
            self._perms[period] = perm
        return int(perm[rank])

    def request(self, file: int, template: int) -> Request:
        key = (file, template)
        ad = self._ads.get(key)
        tpl = self.templates[template]
        reqd = self.reqd_space(file, template)
        if ad is None and not self.as_none(template):
            from repro.core.classads import parse_classad

            ad = self._ads[key] = parse_classad(render_ad(tpl, reqd))
        return Request(file, template, self.world.lfns[file], ad, reqd)

    def reqd_space(self, file: int, template: int) -> int:
        tpl = self.templates[template]
        rs = self.docs[tpl["doc"]]["reqd_space"]
        if rs != "file_bytes":
            return int(rs)
        return int(tpl.get("stage_files", 1)) * int(self.world.file_bytes[file])

    def open_arrivals(self, seconds: float) -> Tuple[np.ndarray, List[Request]]:
        """→ (due times [N] in s from the window's start, requests)."""
        rate = float(self.params["rate_rps"])
        n = int(round(rate * seconds))
        rng = np.random.default_rng([self.seed, _ARRIVALS])
        due = np.sort(rng.random(n) * seconds)
        u, tpl = self._draw(rng, n)
        reqs = [
            self.request(self._file_of(float(u[i]), float(due[i])), int(tpl[i]))
            for i in range(n)
        ]
        return due, reqs

    def next_closed(self, clock: float) -> Request:
        """The closed loop's next request (a fixed stream, drawn in blocks)."""
        if not self._closed_buf:
            u, tpl = self._draw(self._closed_rng, 4096)
            self._closed_buf = list(zip(u.tolist(), tpl.tolist()))[::-1]
        u, t = self._closed_buf.pop()
        return self.request(self._file_of(u, clock), int(t))

    def warmup(self, n: int) -> List[Request]:
        """n requests for set-up's warm-up flush (their own stream)."""
        rng = np.random.default_rng([self.seed, _WARMUP])
        u, tpl = self._draw(rng, n)
        return [self.request(self._file_of(float(u[i]), 0.0), int(tpl[i])) for i in range(n)]
