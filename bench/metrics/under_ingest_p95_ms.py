"""Query path under ingest: 95th percentile of the background queries'
latency in the window, each timed from its pin to its last row, in ms.
One client polls in a closed loop, so every query it issued was served;
with few queries in a window the percentile lies near their maximum."""

import statistics


def read(c):
    lat = sorted(r.latency for r in c.requests if r.bg and not r.failed)
    if len(lat) < 2:
        return lat[0] * 1e3 if lat else None
    return statistics.quantiles(lat, n=20, method="inclusive")[18] * 1e3
