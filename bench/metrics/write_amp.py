"""Storage: rows written per row ingested in the window,
(``lsm.rows_flushed`` + ``lsm.rows_merged``) / ``lsm.rows_ingested``."""


def read(c):
    def d(key):
        return c.obs1.get(key, 0) - c.obs0.get(key, 0)
    ingested = d("lsm.rows_ingested")
    if ingested <= 0:
        return None
    return (d("lsm.rows_flushed") + d("lsm.rows_merged")) / ingested
