"""Mixed ingest+query serving benchmark (paper §2.4/§4.4): N feed pump
threads and M snapshot-isolated query workers drive one
``PartitionedDataset`` through the admission-controlled
``repro.serve.ServeHarness``, with a mid-run checkpoint +
crash-and-recover to exercise at-least-once feed replay.

Hard assertions (smoke and full): zero torn reads, zero lost
acknowledged records (both live floor checks and the final scan), no
query-worker exceptions, nonzero sustained ingest, and — now that every
request carries a deadline — a *zero deadline-miss ledger* at smoke
load (``serve.slo.missed == 0`` and ``serve.slo.rejected_deadline ==
0`` under the generous smoke deadline): the numbers are only reported
if the concurrent run was correct *and* met its SLO.

Reported per row: sustained ingest rate (acked records/s), p50/p99
query latency from the ``serve.query.latency_s`` obs histogram, SLO
attainment, queue-wait p50/p99, and the phase that dominates tail
latency.  The ``serve_mixed_2x2_exported`` smoke row repeats the steady
-state row with the ``obs.serve_http()`` Prometheus exporter + rate
sampler live, scrapes ``/metrics`` after the run, and reports the
ingest-rate parity vs the exporter-off row (the exporter must be
near-free; the hard bound is intentionally loose because two
thread-scheduled runs already differ run-to-run).

Usage: PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]
"""

from __future__ import annotations

import urllib.request

from repro import obs
from repro.core import adm
from repro.core.lsm import TieredMergePolicy
from repro.serve import ServeHarness
from repro.storage.dataset import PartitionedDataset

# exporter-on vs exporter-off sustained-ingest ratio floor: measured
# parity is ~1.0 (±10%); the assert is looser only because two
# concurrent runs differ by thread-scheduling noise alone
EXPORT_PARITY_FLOOR = 0.5


def _dataset(flush_threshold: int) -> PartitionedDataset:
    rt = adm.RecordType("ServedType",
                        (adm.Field("pk", adm.INT64),
                         adm.Field("val", adm.INT64),
                         adm.Field("text", adm.STRING)),
                        open=True)
    return PartitionedDataset("served", rt, "pk", num_partitions=4,
                              flush_threshold=flush_threshold,
                              merge_policy=TieredMergePolicy(k=3))


def _drive(name: str, *, n_ingest: int, n_query: int, per_lane: int,
           duration_s: float, deadline_s: float, crash: bool = False,
           smoke: bool = False, exporter: bool = False) -> dict:
    ds = _dataset(flush_threshold=256)
    h = ServeHarness(ds, n_ingest=n_ingest, n_query=n_query,
                     pump_batch=64, records_per_lane=per_lane,
                     deadline_s=deadline_s)
    total = n_ingest * per_lane
    server = None
    metrics_text = ""
    if exporter:
        server = obs.serve_http(port=0, sample_interval_s=0.25,
                                trace_source=h.tracker.profile_spans)
    try:
        rep = h.run(duration_s=duration_s,
                    checkpoint_after=total // 4 if crash else None,
                    crash_after=total // 2 if crash else None)
        if server is not None:
            metrics_text = urllib.request.urlopen(
                server.url + "/metrics", timeout=10).read().decode()
    finally:
        if server is not None:
            server.stop()
    d = rep.as_dict()
    assert d["torn_reads"] == 0, f"{name}: torn reads {d['torn_reads']}"
    assert d["lost_acks"] == 0, f"{name}: lost-ack reads {d['lost_acks']}"
    assert d["lost_acked_final"] == 0, \
        f"{name}: acked records missing from final scan"
    if d["query_errors"]:
        # a worker that caught a query failure kept serving; the run
        # still fails
        raise RuntimeError(f"{name}: {len(rep.query_errors)} query errors, "
                           f"first: {d['query_errors'][0]}")
    assert d["ingest_acked"] >= n_ingest * per_lane, \
        f"{name}: only {d['ingest_acked']} acked"
    assert d["ingest_rate"] > 0, f"{name}: zero sustained ingest"
    assert d["queries"] > 0 and d["query_p99_ms"] is not None, \
        f"{name}: no query latency measured"
    assert d["queue_wait_p99_ms"] is not None, \
        f"{name}: no queue wait measured"
    assert d["slo"]["attained"] > 0, f"{name}: no request met its SLO"
    if smoke:
        # zero deadline-miss ledger at smoke load: the generous smoke
        # deadline must never be blown, by completion or by admission
        assert d["slo"]["missed"] == 0, \
            f"{name}: {d['slo']['missed']} deadline misses at smoke load"
        assert d["slo"]["rejected_deadline"] == 0, \
            f"{name}: deadline-rejected requests at smoke load"
    if exporter:
        # the scrape must be real Prometheus text covering the serve tier
        assert "# TYPE serve_ingest_acked counter" in metrics_text, \
            f"{name}: /metrics missing serve counters"
        assert "serve_queue_wait_s" in metrics_text, \
            f"{name}: /metrics missing queue-wait summary"
    return {"bench": name,
            "us_per_call": 1e6 / d["ingest_rate"],
            "ingest_rate": round(d["ingest_rate"], 1),
            "ingest_acked": d["ingest_acked"],
            "queries": d["queries"],
            "admission_rejected": d["admission_rejected"],
            "query_p50_ms": round(d["query_p50_ms"], 3),
            "query_p99_ms": round(d["query_p99_ms"], 3),
            "queue_wait_p50_ms": round(d["queue_wait_p50_ms"], 3),
            "queue_wait_p99_ms": round(d["queue_wait_p99_ms"], 3),
            "slo_attained": d["slo"]["attained"],
            "slo_missed": d["slo"]["missed"],
            "slo_rejected_deadline": d["slo"]["rejected_deadline"],
            "slo_attainment": d["slo"]["attainment"],
            "deadline_miss_rate": d["deadline_miss_rate"],
            "slowest_phase_p99": d["slowest_phase_p99"],
            "torn_reads": d["torn_reads"],
            "lost_acked": d["lost_acked_final"] + d["lost_acks"],
            "recoveries": d["recoveries"],
            "derived": f"{d['ingest_rate']:.0f} rec/s, "
                       f"p99 {d['query_p99_ms']:.1f}ms, "
                       f"{d['queries']} queries, "
                       f"slo {d['slo']['attained']}/{d['slo']['attained'] + d['slo']['missed']}"}


def run(smoke: bool = False) -> list:
    per_lane = 1500 if smoke else 8000
    budget = 20.0 if smoke else 90.0
    deadline = 5.0 if smoke else 15.0
    rows = [
        # steady state: 2 ingest lanes + 2 query workers
        _drive("serve_mixed_2x2", n_ingest=2, n_query=2,
               per_lane=per_lane, duration_s=budget, deadline_s=deadline,
               smoke=smoke),
        # fault injection: checkpoint, crash, WAL recovery + feed replay
        _drive("serve_crash_replay", n_ingest=2, n_query=2,
               per_lane=per_lane, duration_s=budget, deadline_s=deadline,
               smoke=smoke, crash=True),
    ]
    if smoke:
        # steady state again, exporter + rate sampler live: the serving
        # numbers must stay at parity with the exporter stopped
        exported = _drive("serve_mixed_2x2_exported", n_ingest=2, n_query=2,
                          per_lane=per_lane, duration_s=budget,
                          deadline_s=deadline, smoke=True, exporter=True)
        parity = exported["ingest_rate"] / rows[0]["ingest_rate"]
        exported["export_parity"] = round(parity, 3)
        exported["derived"] += f", parity {parity:.2f}x"
        assert parity >= EXPORT_PARITY_FLOOR, \
            f"exporter cost: ingest parity {parity:.2f}x < " \
            f"{EXPORT_PARITY_FLOOR}x of exporter-off row"
        rows.append(exported)
    else:
        rows.append(_drive("serve_mixed_4x4", n_ingest=4, n_query=4,
                           per_lane=per_lane, duration_s=budget,
                           deadline_s=deadline))
    return rows


if __name__ == "__main__":
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true")
    args = p.parse_args()
    for r in run(smoke=args.smoke):
        print(r)
