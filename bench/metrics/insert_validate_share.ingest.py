"""Storage: share of the ingest lanes' time spent validating, observing
open fields and routing feed batches (``storage.insert_validate_seconds``
sum over the window, over lanes x window), in %."""

KEY = "storage.insert_validate_seconds"


def read(c):
    if KEY not in c.obs1:
        return None
    busy = c.obs1[KEY]["sum"] - (c.obs0.get(KEY) or {}).get("sum", 0.0)
    return busy / (int(c.mix["lanes"]) * (c.pc1 - c.pc0)) * 100.0
