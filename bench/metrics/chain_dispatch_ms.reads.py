"""Kernels (host wrapper): wall time a query's host thread spends at the
fused chain's dispatch, per query completed in the traced window, in ms:
the ``chain.device`` spans, from the chain core's call through its
``device_get``.  That time holds the host's jit dispatch (the spans'
``cpu_s``), the device's own work (``device_ms.reads``), queueing behind
the other clients' programs on the device, and waits for the GIL; this
metric does not split them."""


def read(c):
    spans = [s for s in c.spans if s.name == "chain.device"]
    done = [r for r in c.requests if c.pc0 <= r.t_done <= c.pc1]
    if not spans or not done:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(done) * 1e3
