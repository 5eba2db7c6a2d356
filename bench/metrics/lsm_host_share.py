"""Storage: share of the ingest lanes' time spent in LSM flushes and
merges (``lsm.flush_seconds`` + ``lsm.merge_seconds`` sums over the
window, over lanes x window), in %.  Postings builds run inside both
and are not added again."""


def read(c):
    def s(key):
        return (c.obs1.get(key) or {}).get("sum", 0.0) - \
            (c.obs0.get(key) or {}).get("sum", 0.0)
    busy = s("lsm.flush_seconds") + s("lsm.merge_seconds")
    return busy / (int(c.mix["lanes"]) * (c.pc1 - c.pc0)) * 100.0
