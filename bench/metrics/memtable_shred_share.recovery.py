"""Storage (recovery): share of ``recovery_s`` spent shredding memtables
into column batches (``storage.memtable_shred_seconds`` sum since the
window's close, over ``recovery_s``), in %.  After the crash each
partition's memtable holds its whole replayed WAL, and the recovery
probe's columnar scan shreds it.  It relies on readers running after
``finish()``, that is after the crash and recovery: the registry is read
here, at reader time.  Between the close and the reader nothing else
scans columnar: the read-backs use key arrays and point lookups."""

KEY = "storage.memtable_shred_seconds"


def read(c):
    from repro import obs
    now = obs.snapshot().get(KEY)
    rec = c.e2e.get("recovery_s")
    if now is None or not rec:
        return None
    return (now["sum"] - (c.obs1.get(KEY) or {}).get("sum", 0.0)) \
        / rec * 100.0
