"""A run with its timed path broken underneath must come out with
``correct`` false: once for each fault a cell can have.  The look for a
chip is skipped (``run.run_cell``); everything else is a whole run at a
size a CPU test can hold.  One chip per cell, so there is no exchange
between chips to leave out."""

import datetime as dt

import pytest

from bench import run
from conftest import small

SEED = 3 ** 20


def _altered(rows):
    """The first row with one value moved: an int by one, a datetime by
    a second."""
    if not rows:
        return rows
    r = dict(rows[0])
    for k, v in r.items():
        if isinstance(v, dt.datetime):
            r[k] = v + dt.timedelta(seconds=1)
            break
        if isinstance(v, int) and not isinstance(v, bool):
            r[k] = v + 1
            break
    return [r] + list(rows[1:])


def _halved(rows):
    return list(rows[:len(rows) // 2])


def _cell(fault):
    """A Cell whose window runs with ``fault(monkeypatch)`` applied."""

    class Faulty(run.Cell):
        def window(self):
            with pytest.MonkeyPatch.context() as mp:
                fault(mp)
                super().window()

    return Faulty


def _answers(transform):
    def fault(mp):
        from repro.storage import query
        orig = query.run_query

        def broken(*a, **k):
            rows, ex = orig(*a, **k)
            return transform(rows), ex
        mp.setattr(query, "run_query", broken)
    return fault


def _inserts(keep):
    def fault(mp):
        from repro.storage.dataset import PartitionedDataset
        orig = PartitionedDataset.insert_batch

        def broken(self, records):
            return orig(self, keep(list(records)))
        mp.setattr(PartitionedDataset, "insert_batch", broken)
    return fault


def _run(spec, name, cell_cls=run.Cell):
    cfg = run.load_config(next(w for w in spec["workloads"]
                               if w["name"] == name)["config"])
    seconds = 6.0 if name == "keyonly-feed-ingest" else 2.0
    return run.run_cell(spec, name, SEED, seconds, False,
                        overrides=small(cfg), cell_cls=cell_cls)


class NoReplay(run.Cell):
    """Recovery through the program's own path with the WAL tail not
    replayed: what was in the memtables at the crash is gone."""

    def _recover(self, ds):
        from repro.core import lsm
        orig = lsm.recover

        def no_replay(components, wal, **kw):
            return orig(components, wal, **{**kw,
                                            "replay_from_lsn": len(wal)})
        with pytest.MonkeyPatch.context() as mp:
            from repro.storage import dataset
            mp.setattr(dataset, "recover", no_replay)
            ds.crash_and_recover()


@pytest.mark.parametrize("name", ["schema-indexed-reads",
                                  "keyonly-feed-ingest"])
def test_sound_run_is_correct(spec, name):
    line = _run(spec, name)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name,fault,check", [
    ("schema-indexed-reads", _answers(_altered), "wrong_answers"),
    ("schema-indexed-reads", _answers(_halved), "wrong_answers"),
    ("keyonly-feed-ingest", _answers(_altered), "wrong_answers"),
    ("keyonly-feed-ingest", _inserts(lambda recs: []), "lost_acked"),
    ("keyonly-feed-ingest", _inserts(lambda recs: recs[:len(recs) // 2]),
     "lost_acked"),
])
def test_fault_is_not_correct(spec, name, fault, check):
    line = _run(spec, name, _cell(fault))
    assert not line["correct"]
    assert line["checks"][check]["value"] > line["checks"][check]["limit"]


def test_recovery_without_replay_is_not_correct(spec):
    line = _run(spec, "keyonly-feed-ingest", NoReplay)
    assert not line["correct"]
    assert line["checks"]["lost_acked_after_recovery"]["value"] > 0
