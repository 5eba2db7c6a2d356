"""The plain reference: what each request must answer, from the
generated columns alone (numpy and the standard library; nothing of the
program).

Answers compare as canonical row lists (``canon_rows``): every row a
sorted tuple of (field, value) pairs, datetimes and dates in ISO form,
bags (``tags``, ``friend-ids``) sorted, absent and null fields dropped,
rows sorted.  Selects return the stored records unchanged, so the
reference rebuilds the matching records with ``gen``; aggregates and
groups follow the paper's semantics (count of rows, then sum / min /
max over the rows where the column is present).
"""

from __future__ import annotations

import datetime as dt
from typing import Any, Dict, List, Optional

import numpy as np

from . import gen

BAGS = ("tags", "friend-ids")


def canon(v: Any, key: Optional[str] = None) -> Any:
    if isinstance(v, dict):
        return tuple(sorted((k, canon(x, k)) for k, x in v.items()
                            if x is not None))
    if isinstance(v, (list, tuple)):
        items = [canon(x) for x in v]
        return tuple(sorted(items)) if key in BAGS else tuple(items)
    if isinstance(v, (dt.datetime, dt.date)):
        return v.isoformat()
    if isinstance(v, (bool, np.bool_)):
        return bool(v)
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, (float, np.floating)):
        return float(v)
    return v


def canon_rows(rows: List[dict]) -> List[tuple]:
    return sorted(canon(r) for r in rows)


def edit_distances(names: np.ndarray, lens: np.ndarray, q: str) -> np.ndarray:
    """Levenshtein distance of ``q`` to every row of a [n, L] uint8 char
    matrix (``lens`` true lengths), one DP row per query character.  The
    DP is held column-major ([L + 1, n], int16), so each column step is
    one contiguous vector."""
    n, width = names.shape
    chars = np.ascontiguousarray(names.T)
    prev = np.repeat(np.arange(width + 1, dtype=np.int16)[:, None], n,
                     axis=1)
    for i, c in enumerate(q.encode(), start=1):
        best = np.minimum(prev[:-1] + (chars != c), prev[1:] + 1)
        cur = np.empty_like(prev)
        cur[0] = i
        for j in range(1, width + 1):
            np.minimum(best[j - 1], cur[j - 1] + 1, out=cur[j])
        prev = cur
    return prev[lens, np.arange(n)]


class Reference:
    """Answers over one data set: the users and every message the
    program was given (preload, and the feed's records where a snapshot
    or the final state holds them)."""

    def __init__(self, users: gen.Columns, msgs: gen.Columns):
        self.users = users
        self.msgs = msgs
        self.cols = {
            "MugshotMessages": {
                "timestamp": msgs["ts_s"], "author-id": msgs["author-id"],
                "message-id": msgs["message-id"]},
            "MugshotUsers": {
                "user-since": users["since_s"], "id": users["id"]},
        }
        self._names = None

    # -- building blocks ----------------------------------------------------
    def _mask(self, ds: str, rng: List[Any],
              among: Optional[np.ndarray] = None) -> np.ndarray:
        x = self.cols[ds][rng[0]]
        m = (x >= rng[1]) & (x <= rng[2])
        return m if among is None else m & among

    def _msg_rows(self, m: np.ndarray) -> List[dict]:
        return gen.message_records(gen.take(self.msgs, np.flatnonzero(m)))

    def _value(self, ds: str, col: str, idx: np.ndarray) -> np.ndarray:
        if col == "timestamp":
            return self.msgs["ts_s"][idx]
        return self.cols[ds][col][idx]

    def _as_value(self, col: str, v: int) -> Any:
        if col == "timestamp":
            return gen.MSG_T0 + dt.timedelta(seconds=int(v))
        return int(v)

    def _agg(self, ds: str, aggs: Dict[str, List[str]],
             idx: np.ndarray) -> Dict[str, Any]:
        row: Dict[str, Any] = {}
        for name, (fn, col) in aggs.items():
            if fn == "count":
                row[name] = int(idx.size)
                continue
            vals = self._value(ds, col, idx)
            if fn == "sum":
                row[name] = int(vals.sum(dtype=np.int64)) if vals.size else 0
            elif vals.size == 0:
                row[name] = None
            elif fn == "min":
                row[name] = self._as_value(col, vals.min())
            elif fn == "max":
                row[name] = self._as_value(col, vals.max())
            else:
                raise ValueError(fn)
        return row

    def _groups(self, ds: str, keys: List[str], aggs: Dict[str, List[str]],
                idx: np.ndarray) -> List[dict]:
        """One row per key value: counts and extremes by ``reduceat``
        over the rows sorted by key."""
        (key,) = keys
        kv = self.cols[ds][key][idx]
        order = np.argsort(kv, kind="stable")
        idx, kv = idx[order], kv[order]
        if idx.size == 0:
            return []
        starts = np.concatenate([[0], np.flatnonzero(np.diff(kv)) + 1])
        counts = np.diff(np.concatenate([starts, [idx.size]]))
        cols: Dict[str, List[Any]] = {key: kv[starts].tolist()}
        for name, (fn, col) in aggs.items():
            if fn == "count":
                cols[name] = counts.tolist()
                continue
            vals = self._value(ds, col, idx)
            red = {"sum": np.add, "min": np.minimum, "max": np.maximum}[fn]
            out = red.reduceat(vals, starts).tolist()
            cols[name] = out if fn == "sum" else \
                [self._as_value(col, v) for v in out]
        names = list(cols)
        return [dict(zip(names, vs)) for vs in zip(*cols.values())]

    def _user_names(self):
        if self._names is None:
            names = [f"User Number {i}" for i in self.users["id"].tolist()]
            width = max(map(len, names))
            mat = np.zeros((len(names), width), dtype=np.uint8)
            for r, s in enumerate(names):
                mat[r, :len(s)] = np.frombuffer(s.encode(), dtype=np.uint8)
            self._names = (mat, np.asarray([len(s) for s in names]))
        return self._names

    # -- one request ----------------------------------------------------------
    def answer(self, req: Dict[str, Any], t: Dict[str, Any],
               among: Optional[np.ndarray] = None) -> List[tuple]:
        """Canonical answer of ``req`` (template ``t``).  ``among``
        restricts messages to those a snapshot holds (a bool mask over
        ``self.msgs``)."""
        kind = t["kind"]
        ds = t["dataset"]
        if kind == "fuzzy_ed":
            mat, lens = self._user_names()
            d = edit_distances(mat, lens, req["query"])
            idx = np.flatnonzero(d <= int(t["ed"]))
            return canon_rows([self._user(i) for i in idx.tolist()])
        m = self._mask(ds, req["range"],
                       among if ds == "MugshotMessages" else None)
        if kind == "select":
            return canon_rows(self._msg_rows(m))
        aggs = t["aggs"]
        if kind == "aggregate":
            return canon_rows([self._agg(ds, aggs, np.flatnonzero(m))])
        if kind == "group_by":
            return canon_rows(self._groups(ds, t["keys"], aggs,
                                           np.flatnonzero(m)))
        if kind == "join_group_by":
            right = t["right"]
            rm = self._mask(right["dataset"], req["right_range"])
            ok = np.zeros(self.users["id"].size, dtype=bool)
            ok[self.cols[right["dataset"]][t["on"][1]][rm]] = True
            lk = self.cols[ds][t["on"][0]]
            idx = np.flatnonzero(m & ok[lk])
            return canon_rows(self._groups(ds, t["keys"], aggs, idx))
        raise ValueError(f"unknown template kind {kind!r}")

    def _user(self, i: int) -> dict:
        u = self.users
        one = {k: u[k][i:i + 1] for k in ("id", "since_s", "state",
                                           "org", "has_end")}
        a, b = int(u["friend_off"][i]), int(u["friend_off"][i + 1])
        one["friend_off"] = np.asarray([0, b - a])
        one["friends"] = u["friends"][a:b]
        return gen.user_records(one)[0]
