"""The one traffic generator: reads a mix file (``mixes/<traffic>.json``)
and draws requests from the seed.

A mix names its loop and a list of query templates.  A template is a
``kind`` with parameters, and every request draws its own values:

  select         rows of ``dataset`` with ``field`` in a drawn range;
                 ``index: false`` adds the ``skip-index`` hint (a scan)
  aggregate      ``aggs`` over the rows of a drawn scan range
  group_by       ``aggs`` grouped by ``keys`` over a drawn scan range
  join_group_by  a drawn scan range of ``dataset`` joined on
                 ``on: [left, right]`` with a drawn scan range of
                 ``right.dataset``, then grouped as above
  fuzzy_ed       rows of ``dataset`` whose ``field`` is within edit
                 distance ``ed`` of ``prefix`` + a uniform user id

A range is ``{"field", "mode", "width"}`` over the field's domain
(``DOMAINS``): ``window`` is ``width`` units from a uniform start,
``tail`` a uniform start to the domain's end, ``head`` the domain's
start to a uniform end.  Units are seconds for datetimes and values for
integers; bounds are inclusive, as the program's ranges are.

Each template's ``weight`` is an integer share of the requests.  A
request is a plain dict (template name, kind and drawn bounds): the
reference evaluates it without the program, and ``plan()`` turns it
into the program's logical plan.
"""

from __future__ import annotations

import datetime as dt
import json
import pathlib
from typing import Any, Dict, List, Optional

import numpy as np

from . import gen

MIXES = pathlib.Path(__file__).resolve().parent / "mixes"

# per-request random streams: one per client, apart from the data's
CLIENT_STREAM = 1000
WARMUP_STREAM = 2000


def load_mix(name: str) -> Dict[str, Any]:
    return json.loads((MIXES / f"{name}.json").read_text())


def domains(n_users: int) -> Dict[str, tuple]:
    """field -> (kind, origin, extent): values lie in
    [origin, origin + extent)."""
    return {"timestamp": ("datetime", gen.MSG_T0, gen.MSG_SPAN_S),
            "user-since": ("datetime", gen.USER_T0, gen.USER_SPAN_S),
            "author-id": ("int", 0, n_users),
            "id": ("int", 0, n_users)}


def _draw_range(rng: np.random.Generator, spec: Dict[str, Any],
                dom: Dict[str, tuple]) -> List[Any]:
    """[field, lo, hi] with lo/hi as offsets into the field's domain."""
    _kind, _o, extent = dom[spec["field"]]
    mode = spec["mode"]
    if mode == "window":
        w = int(spec["width"])
        lo = int(rng.integers(0, extent - w))
        return [spec["field"], lo, lo + w]
    if mode == "tail":
        return [spec["field"], int(rng.integers(0, extent)), extent - 1]
    if mode == "head":
        return [spec["field"], 0, int(rng.integers(0, extent))]
    raise ValueError(f"unknown range mode {mode!r}")


def value(dom: Dict[str, tuple], field: str, off: int) -> Any:
    """A drawn offset as the program's value (datetime or int)."""
    kind, origin, _extent = dom[field]
    if kind == "datetime":
        return origin + dt.timedelta(seconds=int(off))
    return origin + int(off)


class Drawer:
    """Requests of one mix, drawn from ``(seed, stream)``: ``next()``
    gives the next request of that stream.  Templates come in blocks
    that hold each template its (integer) weight over the weights'
    common divisor times, in an order shuffled per block: every seed
    offers the same composition of work, in another order.  A share
    ``check_share`` of the requests, and the first of each template, are
    marked for the check against the reference."""

    def __init__(self, mix: Dict[str, Any], n_users: int, seed: int,
                 stream: int, templates: Optional[List[dict]] = None):
        self.templates = templates if templates is not None \
            else mix["templates"]
        w = [int(t.get("weight", 1)) for t in self.templates]
        g = int(np.gcd.reduce(w))
        self.block = np.repeat(np.arange(len(w)), [x // g for x in w])
        self.queue: List[int] = []
        self.seen: set = set()
        self.check_share = float(mix.get("check_share", 1.0))
        self.dom = domains(n_users)
        self.n_users = n_users
        self.rng = np.random.default_rng([int(seed), int(stream)])

    def next(self) -> Dict[str, Any]:
        rng = self.rng
        if not self.queue:
            self.queue = rng.permutation(self.block).tolist()
        t = self.templates[self.queue.pop()]
        first = t["name"] not in self.seen
        self.seen.add(t["name"])
        req: Dict[str, Any] = {"template": t["name"], "kind": t["kind"],
                               "check": bool(rng.random() < self.check_share)
                               or first}
        if t["kind"] == "fuzzy_ed":
            req["query"] = t["prefix"] + str(int(rng.integers(0, self.n_users)))
        else:
            req["range"] = _draw_range(rng, t["range"], self.dom)
        if t["kind"] == "join_group_by":
            req["right_range"] = _draw_range(rng, t["right"]["range"],
                                             self.dom)
        return req

    def template(self, name: str) -> Dict[str, Any]:
        return next(t for t in self.templates if t["name"] == name)


def plan(req: Dict[str, Any], t: Dict[str, Any], dom: Dict[str, tuple]):
    """The program's logical plan for one request of template ``t``."""
    from repro.core import algebra as A

    def sel(ds: str, rng: List[Any], index: bool):
        fld = rng[0]
        lo, hi = value(dom, fld, rng[1]), value(dom, fld, rng[2])
        return A.select(A.scan(ds), pred=lambda r: lo <= r[fld] <= hi,
                        fields=[fld], ranges={fld: (lo, hi)},
                        ranges_exact=not index,
                        hints=[] if index else ["skip-index"])

    kind = t["kind"]
    aggs = {k: tuple(v) for k, v in t.get("aggs", {}).items()}
    if kind == "select":
        return sel(t["dataset"], req["range"], t.get("index", True))
    if kind == "aggregate":
        return A.aggregate(sel(t["dataset"], req["range"], False), aggs)
    if kind == "group_by":
        return A.group_by(sel(t["dataset"], req["range"], False),
                          list(t["keys"]), aggs)
    if kind == "join_group_by":
        left = sel(t["dataset"], req["range"], False)
        right = sel(t["right"]["dataset"], req["right_range"], False)
        return A.group_by(A.join(left, right, [t["on"][0]], [t["on"][1]]),
                          list(t["keys"]), aggs)
    if kind == "fuzzy_ed":
        from repro.fuzzy import fuzzy_predicate
        spec = (t["field"], "ed", req["query"], int(t["ed"]))
        return A.select(A.scan(t["dataset"]), pred=fuzzy_predicate(spec),
                        fields=[t["field"]], fuzzy=spec)
    raise ValueError(f"unknown template kind {kind!r}")
