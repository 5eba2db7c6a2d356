"""Feed: share of the ingest lanes' time their pumps spent blocked on a
full sink queue, the backpressure of the storage stage
(``serve.sink.put_wait_seconds`` sum over the window, over lanes x
window), in %."""

KEY = "serve.sink.put_wait_seconds"


def read(c):
    if KEY not in c.obs1:
        return None
    busy = c.obs1[KEY]["sum"] - (c.obs0.get(KEY) or {}).get("sum", 0.0)
    return busy / (int(c.mix["lanes"]) * (c.pc1 - c.pc0)) * 100.0
