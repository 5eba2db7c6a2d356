"""Device: idle share of the traced window of the ingest cell, in %."""


def read(c):
    tr = c.trace_red
    if not tr or not tr.get("chips") or tr["window_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
