"""Storage (recovery): share of ``recovery_s`` spent replaying the WAL
into the memtables (``lsm.wal_replay_seconds`` sum since the window's
close, over ``recovery_s``), in %.  It relies on readers running after
``finish()``, that is after the crash and recovery: the registry is read
here, at reader time."""

KEY = "lsm.wal_replay_seconds"


def read(c):
    from repro import obs
    now = obs.snapshot().get(KEY)
    rec = c.e2e.get("recovery_s")
    if now is None or not rec:
        return None
    return (now["sum"] - (c.obs1.get(KEY) or {}).get("sum", 0.0)) \
        / rec * 100.0
