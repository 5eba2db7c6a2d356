"""The generators match the program's Mugshot generators in fields and
ranges and repeat from the seed; at 2^14 messages every template's
device-path answer equals the reference, for both configurations."""

import datetime as dt

import numpy as np
import pytest

from bench import gen, reference, run, traffic
from conftest import small

SEED = 2 ** 31 + 12345


def _types(v):
    if isinstance(v, dict):
        return {k: _types(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return type(v).__name__
    return type(v).__name__


def test_same_seed_same_records():
    a = gen.message_records(gen.messages(512, 64, SEED))
    b = gen.message_records(gen.messages(512, 64, SEED))
    c = gen.message_records(gen.messages(512, 64, SEED + 1))
    assert a == b and a != c
    assert gen.user_records(gen.users(64, SEED)) == \
        gen.user_records(gen.users(64, SEED))


def test_fields_and_ranges_match_tinysocial():
    from repro.configs import tinysocial as ts
    n, nu = 4096, 256
    mine = gen.message_records(gen.messages(n, nu, SEED))
    theirs = ts.gen_messages(64, nu, 1)
    assert {_types(r).__repr__() for r in mine} == \
        {_types(r).__repr__() for r in theirs}
    t0, t1 = dt.datetime(2014, 1, 1), dt.datetime(2014, 1, 1) + \
        dt.timedelta(days=120)
    for r in mine:
        assert 0 <= r["author-id"] < nu and t0 <= r["timestamp"] < t1
        assert 1 <= len(r["tags"]) <= 4 and len(set(r["tags"])) == \
            len(r["tags"]) and set(r["tags"]) <= set(ts.TAG_VOCAB)
        assert 4 <= len(r["message"].split()) <= 19
        assert 33 <= r["sender-location"][0] < 34
        assert -118 <= r["sender-location"][1] < -117
    assert [r["message-id"] for r in mine] == list(range(n))
    users = gen.user_records(gen.users(nu, SEED))
    ref_users = ts.gen_users(8, 0)
    assert {tuple(sorted(u)) for u in users} == \
        {tuple(sorted(u)) for u in ref_users}
    u0, u1 = dt.datetime(2008, 1, 1), dt.datetime(2014, 1, 1)
    for u in users:
        assert u0 <= u["user-since"] < u1 and len(u["friend-ids"]) <= 4
        assert all(0 <= f < nu for f in u["friend-ids"])
        assert u["name"] == f"User Number {u['id']}"
        assert set(u["address"]) == set(ref_users[0]["address"])
        assert set(u["employment"][0]) <= {"organization-name",
                                           "start-date", "end-date"}
    ends = np.mean(["end-date" in u["employment"][0] for u in users])
    assert 0.35 < ends < 0.65


def test_edit_distance_matches_definition():
    from repro.core.functions import edit_distance
    names = ["User Number 1", "User Number 123", "Usr Number 12", "x"]
    width = max(map(len, names))
    mat = np.zeros((len(names), width), dtype=np.uint8)
    for i, s in enumerate(names):
        mat[i, :len(s)] = np.frombuffer(s.encode(), dtype=np.uint8)
    lens = np.asarray([len(s) for s in names])
    for q in ("User Number 12", "x", ""):
        got = reference.edit_distances(mat, lens, q).tolist()
        assert got == [edit_distance(s, q) for s in names]


@pytest.mark.parametrize("config", ["mugshot-schema-4m",
                                    "mugshot-keyonly-2m"])
def test_every_template_equals_reference(spec, config):
    from repro.storage.query import run_query
    cfg = run.load_config(config)
    cfg.update(small(cfg))
    dep = run.Deployment(cfg, SEED)
    dep.build()
    ref = reference.Reference(dep.users, dep.msgs)
    dom = traffic.domains(dep.n_users)
    templates = []
    for mix in ("indexed-reads", "feed-ingest"):
        m = traffic.load_mix(mix)
        templates += m["templates"] if m["loop"] == "closed" else \
            m["background"]["templates"] + [m["recovery_probe"]]
    for j, t in enumerate(templates):
        d = traffic.Drawer({"templates": [t]}, dep.n_users, SEED, 50 + j,
                           templates=[t])
        for _ in range(3):
            req = d.next()
            rows, ex = run_query(traffic.plan(req, t, dom), dep.datasets,
                                 vectorize=True, snapshot=True)
            assert not ex.stats.fallback_reasons, (t["name"],
                                                   ex.stats.fallback_reasons)
            assert ex.stats.kernel_dispatches > 0, t["name"]
            assert reference.canon_rows(rows) == ref.answer(req, t), \
                (config, t["name"], req)
