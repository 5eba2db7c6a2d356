"""The benchmark's own Mugshot generators (paper §5, TinySocial data
definitions 1-2), vectorised with numpy.

Fields, widths and distributions follow the program's
``configs/tinysocial.gen_users`` / ``gen_messages``; the draws come from
``numpy.random.default_rng([seed, stream])``, so the same seed gives the
same records, and nothing here imports the program (the reference in
``reference.py`` works on these columns too).

Users: ``id`` i, ``alias`` "user<i>", ``name`` "User Number <i>",
``user-since`` uniform over the 6 years from 2008-01-01 (whole seconds),
``address`` with a uniform state of six, ``friend-ids`` 0-4 uniform
user ids, one ``employment`` record with a uniform organisation of five
and an ``end-date`` with probability 1/2.

Messages: ``message-id`` consecutive from ``first_id``, ``author-id``
uniform over the users, ``timestamp`` uniform over the 120 days from
2014-01-01 (whole seconds), ``sender-location`` uniform over
[33, 34) x [-118, -117), 1-4 distinct ``tags`` from the 18-word
vocabulary, and a ``message`` of 4-19 uniform vocabulary words.
"""

from __future__ import annotations

import datetime as dt
from typing import Dict, List

import numpy as np

TAG_VOCAB = ["tpu", "jax", "lsm", "asterix", "bigdata", "nosql", "flwor",
             "hyracks", "algebricks", "feeds", "fuzzy", "spatial", "tonight",
             "coffee", "verona", "mesh", "pallas", "roofline"]
STATES = ["CA", "WA", "OR", "NV", "AZ", "TX"]
ORGS = ["Kongreen", "Codetechno", "Zamcorp", "Streettax", "Villa-tech"]

USER_T0 = dt.datetime(2008, 1, 1)
USER_SPAN_S = 6 * 365 * 86400
MSG_T0 = dt.datetime(2014, 1, 1)
MSG_SPAN_S = 120 * 86400
EPOCH = dt.datetime(1970, 1, 1)

# random streams: one per table, so growing one table leaves the other
USERS_STREAM, MESSAGES_STREAM, FEED_STREAM = 1, 2, 3

Columns = Dict[str, np.ndarray]


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def users(n: int, seed: int) -> Columns:
    rng = _rng(seed, USERS_STREAM)
    nf = rng.integers(0, 5, n)
    return {
        "id": np.arange(n, dtype=np.int64),
        "since_s": rng.integers(0, USER_SPAN_S, n),
        "state": rng.integers(0, len(STATES), n),
        "n_friends": nf,
        "friend_off": np.concatenate([[0], np.cumsum(nf)]),
        "friends": rng.integers(0, n, int(nf.sum())),
        "org": rng.integers(0, len(ORGS), n),
        "has_end": rng.random(n) < 0.5,
    }


def _distinct_tags(rng: np.random.Generator, n: int) -> np.ndarray:
    """[n, 4] vocabulary ids, distinct within a row: the first four
    steps of a Fisher-Yates shuffle of each row's vocabulary."""
    v = len(TAG_VOCAB)
    perm = np.tile(np.arange(v, dtype=np.int8), (n, 1))
    rows = np.arange(n)
    for j in range(4):
        k = j + rng.integers(0, v - j, n)
        a, b = perm[rows, j].copy(), perm[rows, k]
        perm[rows, j] = b
        perm[rows, k] = a
    return perm[:, :4]


def messages(n: int, n_users: int, seed: int, first_id: int = 0,
             stream: int = MESSAGES_STREAM) -> Columns:
    rng = _rng(seed, stream)
    nw = rng.integers(4, 20, n)
    words = rng.integers(0, len(TAG_VOCAB), int(nw.sum()))
    return {
        "message-id": np.arange(first_id, first_id + n, dtype=np.int64),
        "author-id": rng.integers(0, n_users, n),
        "ts_s": rng.integers(0, MSG_SPAN_S, n),
        "lat": rng.uniform(33.0, 34.0, n),
        "lon": rng.uniform(-118.0, -117.0, n),
        "n_tags": rng.integers(1, 5, n),
        "tags": _distinct_tags(rng, n),
        "text": np.asarray(_texts(nw, words), dtype=object),
    }


def _texts(nw: np.ndarray, words: np.ndarray) -> List[str]:
    """Each record's words joined by spaces, built as one string and
    split at record ends (a Python join per record would dominate)."""
    tok = np.asarray([w + " " for w in TAG_VOCAB], dtype=object)[words]
    ends = np.cumsum(nw) - 1
    tok[ends] = np.asarray([w + "\n" for w in TAG_VOCAB],
                           dtype=object)[words[ends]]
    return "".join(tok.tolist()).split("\n")[:-1]


def take(cols: Columns, idx: np.ndarray) -> Columns:
    """Rows ``idx`` of a messages column set (ragged fields stay
    aligned: tags are [n, 4] and texts one per row)."""
    return {k: v[idx] for k, v in cols.items()}


def concat(a: Columns, b: Columns) -> Columns:
    return {k: np.concatenate([a[k], b[k]]) for k in a}


# -- records as the program takes them ----------------------------------------

def _dts(t0: dt.datetime, secs: np.ndarray) -> List[dt.datetime]:
    return [t0 + dt.timedelta(seconds=s) for s in secs.tolist()]


def user_records(u: Columns) -> List[dict]:
    since = _dts(USER_T0, u["since_s"])
    off = u["friend_off"].tolist()
    fr = u["friends"].tolist()
    out = []
    for i, (s, st, org, he) in enumerate(zip(
            since, u["state"].tolist(), u["org"].tolist(),
            u["has_end"].tolist())):
        uid = int(u["id"][i])
        emp = {"organization-name": ORGS[org],
               "start-date": (s + dt.timedelta(days=30)).date()}
        if he:
            emp["end-date"] = (s + dt.timedelta(days=400)).date()
        out.append({
            "id": uid, "alias": f"user{uid}", "name": f"User Number {uid}",
            "user-since": s,
            "address": {"street": f"{uid} Main St", "city": "Irvine",
                        "state": STATES[st], "zip": f"9{uid % 10000:04d}",
                        "country": "USA"},
            "friend-ids": fr[off[i]:off[i + 1]],
            "employment": [emp],
        })
    return out


def message_records(m: Columns) -> List[dict]:
    ts = _dts(MSG_T0, m["ts_s"])
    tags = m["tags"].tolist()
    return [{"message-id": mid, "author-id": aid, "timestamp": t,
             "sender-location": (la, lo), "tags": [TAG_VOCAB[j]
                                                   for j in tg[:k]],
             "message": txt}
            for mid, aid, t, la, lo, tg, k, txt in zip(
                m["message-id"].tolist(), m["author-id"].tolist(), ts,
                m["lat"].tolist(), m["lon"].tolist(), tags,
                m["n_tags"].tolist(), m["text"].tolist())]


def message_record(m: Columns, i: int) -> dict:
    """Row ``i`` of a messages column set (the feed's per-record path)."""
    k = int(m["n_tags"][i])
    return {"message-id": int(m["message-id"][i]),
            "author-id": int(m["author-id"][i]),
            "timestamp": MSG_T0 + dt.timedelta(seconds=int(m["ts_s"][i])),
            "sender-location": (float(m["lat"][i]), float(m["lon"][i])),
            "tags": [TAG_VOCAB[j] for j in m["tags"][i, :k].tolist()],
            "message": m["text"][i]}


def epoch_us(t0: dt.datetime, secs: np.ndarray) -> np.ndarray:
    """Offsets in seconds from ``t0`` as epoch microseconds (int64)."""
    base = int((t0 - EPOCH).total_seconds()) * 1_000_000
    return base + secs.astype(np.int64) * 1_000_000
