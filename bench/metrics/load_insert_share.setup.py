"""Storage: share of set-up spent in ``insert_batch`` (the
``storage.insert_validate_seconds`` and ``storage.insert_apply_seconds``
sums at the window's start, over ``setup_s``), in %.  The load is
serial; the ingest cell's warm-up burst runs two lanes at once."""

KEYS = ("storage.insert_validate_seconds", "storage.insert_apply_seconds")


def read(c):
    if any(k not in c.obs0 for k in KEYS) or not c.setup_s:
        return None
    return sum(c.obs0[k]["sum"] for k in KEYS) / c.setup_s * 100.0
