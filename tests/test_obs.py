"""Observability subsystem: span tracer semantics, disabled fast path,
kernel dispatch/transfer accounting against a hand-computed oracle,
Chrome-trace export, ExecStats per-field assertions on fixed plans, and
fallback-reason reporting."""

import json

import numpy as np
import pytest

from repro import obs
from repro.core import adm
from repro.core import algebra as A
from repro.kernels import columnar_ops as K
from repro.storage.dataset import PartitionedDataset
from repro.storage.query import explain_analyze, run_query


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with the tracer disabled and empty (the
    tracer is process-global; leaking an enabled tracer would slow and
    pollute the rest of the suite)."""
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def _rec_type():
    return adm.RecordType("ObsT", (
        adm.Field("id", adm.INT64),
        adm.Field("g", adm.INT64),
        adm.Field("a", adm.INT64),
    ), open=True)


def _dataset(n=120, parts=3):
    ds = PartitionedDataset("D", _rec_type(), "id", num_partitions=parts,
                            flush_threshold=32)
    ds.create_index("a")
    for i in range(n):
        ds.insert({"id": i, "g": i % 4, "a": i % 50})
    return ds


# ---------------------------------------------------------------------------
# tracer semantics
# ---------------------------------------------------------------------------

def test_spans_nest_and_close_under_exceptions():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("outer", layer="test"):
            with obs.span("inner"):
                assert obs.current().name == "inner"
                raise ValueError("boom")
    evs = obs.events()
    assert [e.name for e in evs] == ["inner", "outer"]  # close order
    inner, outer = evs
    assert inner.depth == 1 and outer.depth == 0
    assert inner.attrs["error"] == "ValueError"
    assert outer.attrs["error"] == "ValueError"
    assert outer.attrs["layer"] == "test"
    for e in evs:
        assert e.t1 >= e.t0 > 0.0
    assert obs.current() is None          # stack fully unwound


def test_leaked_child_spans_cannot_wedge_the_stack():
    obs.enable()
    with obs.span("parent"):
        obs.span("leaked").__enter__()    # never exited
    assert obs.current() is None          # parent exit popped the leak


def test_disabled_tracer_allocates_nothing():
    assert not obs.enabled()
    s1, s2 = obs.span("a"), obs.span("b", k=1)
    assert s1 is s2                       # shared no-op singleton
    with s1 as s:
        s.set("k", 2)
        s.add("n", 1)
    assert obs.events() == []
    assert obs.current() is None


def test_chrome_trace_round_trips(tmp_path):
    obs.enable()
    with obs.span("exec.SCAN", rows_out=7, mode="columnar",
                  unexported=[1, 2]):
        with obs.span("lsm.flush"):
            pass
    path = tmp_path / "trace.json"
    assert obs.dump_trace(str(path)) == 2
    trace = json.load(open(path))
    assert trace["displayTimeUnit"] == "ms"
    evs = {e["name"]: e for e in trace["traceEvents"]}
    assert set(evs) == {"exec.SCAN", "lsm.flush"}
    scan = evs["exec.SCAN"]
    assert scan["ph"] == "X"
    args = dict(scan["args"])
    ids = {k: args.pop(k) for k in ("sid", "parent", "trace_id", "cpu_s")}
    assert args == {"rows_out": 7, "mode": "columnar"}  # scalars only
    assert ids["parent"] is None and ids["trace_id"] is None
    assert evs["lsm.flush"]["args"]["parent"] == ids["sid"]
    for e in trace["traceEvents"]:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
    # the child interval lies inside the parent's
    flush = evs["lsm.flush"]
    assert scan["ts"] <= flush["ts"]
    assert flush["ts"] + flush["dur"] <= scan["ts"] + scan["dur"] + 1e-3


# ---------------------------------------------------------------------------
# kernel dispatch / transfer-byte accounting
# ---------------------------------------------------------------------------

def test_dispatch_and_transfer_bytes_match_hand_oracle():
    """range_mask, one int64 predicate, n=100, jnp path: the wrapper pads
    to 128, ships data (128*8B) + validity (128*1B) and fetches the
    padded bool mask (128*1B) — exactly one dispatch."""
    data = np.arange(100, dtype=np.int64)
    valid = np.ones(100, dtype=bool)
    d0, h0, r0 = obs.kernel_totals()
    out = K.range_mask([(data, valid, 10, 20)], 100, force_pallas=False)
    d1, h1, r1 = obs.kernel_totals()
    assert out.sum() == 11
    assert (d1 - d0, h1 - h0, r1 - r0) == (1, 128 * 8 + 128, 128)
    # per-kernel counters advance in lockstep with the totals
    snap = obs.snapshot()
    assert snap["kernel.range_mask.dispatches"] >= 1
    assert snap["kernel.range_mask.h2d_bytes"] >= 1152

    # host-path kernels (sorted_intersect_mask under the size threshold)
    # move no device bytes and count no dispatch
    keys = np.arange(50, dtype=np.int64)
    cands = np.array([3, 7, 11], dtype=np.int64)
    d0, h0, r0 = obs.kernel_totals()
    mask = K.sorted_intersect_mask(keys, cands, force_pallas=False)
    d1, h1, r1 = obs.kernel_totals()
    assert mask.sum() == 3
    assert (d1 - d0, h1 - h0, r1 - r0) == (0, 0, 0)


def test_dispatch_attributes_onto_open_span():
    obs.enable()
    data = np.arange(100, dtype=np.int64)
    valid = np.ones(100, dtype=bool)
    with obs.span("exec.SELECT"):
        K.range_mask([(data, valid, 0, 5)], 100, force_pallas=False)
    (ev,) = obs.events()
    assert ev.attrs["kernel_dispatches"] == 1
    assert ev.attrs["h2d_bytes"] == 1152
    assert ev.attrs["d2h_bytes"] == 128


# ---------------------------------------------------------------------------
# ExecStats per-field on fixed plans
# ---------------------------------------------------------------------------

def _agg_plan():
    return A.aggregate(
        A.select(A.scan("D"), pred=lambda r: 10 <= r["a"] <= 29,
                 fields=["a"], ranges={"a": (10, 29)}, ranges_exact=True),
        {"c": ("count", "*"), "s": ("sum", "a")})


def test_exec_stats_fields_on_fixed_plan():
    parts = 3
    ds = _dataset(n=120, parts=parts)
    rows, ex = run_query(_agg_plan(), {"D": ds}, vectorize=True)
    # 120 ids, a = id % 50 -> a in [10, 29] matches 2 full cycles + the
    # partial third cycle (ids 100..119 -> a 0..19, of which 10..19): 50
    assert rows[0]["c"] == 2 * 20 + 10
    # the local/global split moves exactly one partial-aggregate row per
    # non-root partition
    assert ex.stats.rows_moved == {"ReplicateToOne": parts - 1}
    # one global result row; every local partial is counted per-op
    assert ex.stats.op_rows["GLOBAL_AGG"] == 1
    assert ex.stats.fallback_reasons == {}
    assert ex.stats.rows_fallback == 0
    # warm second run: padded batches hit the jit cache, zero retraces;
    # with the device buffer pool + fused plan cache the repeated chain
    # runs over already-resident buffers — nothing ships host -> device
    _, ex2 = run_query(_agg_plan(), {"D": ds}, vectorize=True)
    assert ex2.stats.kernel_retraces == 0
    assert ex2.stats.kernel_dispatches >= 1
    assert ex2.stats.h2d_bytes == 0
    assert ex2.stats.plan_cache_hits >= 1
    assert ex2.stats.plan_cache_misses == 0


def test_fallback_reasons_name_the_op_and_cause():
    ds = _dataset(n=60, parts=2)
    # opaque predicate: no ranges -> the columnar engine must decline
    # with a reason, not silently row-execute
    plan = A.select(A.scan("D"), pred=lambda r: r["a"] % 7 == 3,
                    fields=["a"])
    _, ex = run_query(plan, {"D": ds}, vectorize=True)
    assert ex.stats.rows_fallback > 0
    assert any("SELECT" in k and "opaque predicate" in k
               for k in ex.stats.fallback_reasons), ex.stats.fallback_reasons


# ---------------------------------------------------------------------------
# explain_analyze on the Figure-6 chain
# ---------------------------------------------------------------------------

def _flatten(node):
    yield node
    for c in node["children"]:
        yield from _flatten(c)


def test_explain_analyze_reports_the_figure6_chain():
    ds = _dataset(n=120, parts=3)
    report = explain_analyze(_agg_plan(), {"D": ds})
    root = report["plan"]
    assert root["op"] == "GLOBAL_AGG" and root["mode"] == "columnar"
    assert root["wall_s"] > 0 and root["self_wall_s"] > 0
    assert root["rows_out"] == 1
    nodes = {n["op"]: n for n in _flatten(root)}
    for kind in ("SECONDARY_INDEX_SEARCH", "SORT_PK",
                 "PRIMARY_INDEX_LOOKUP", "LOCAL_AGG"):
        assert kind in nodes, sorted(nodes)
        assert nodes[kind]["mode"] == "fused"
    assert nodes["SECONDARY_INDEX_SEARCH"]["rows_out"] == 50
    totals = report["totals"]
    assert totals["rows"] == 1
    assert totals["kernel_dispatches"] >= 1
    assert totals["h2d_bytes"] > 0
    assert totals["wall_s"] > 0
    assert report["stats"].fallback_reasons == {}


def test_explain_analyze_measures_row_fallback_ops():
    ds = _dataset(n=60, parts=2)
    plan = A.select(A.scan("D"), pred=lambda r: r["a"] % 7 == 3,
                    fields=["a"])
    report = explain_analyze(plan, {"D": ds})
    nodes = {n["op"]: n for n in _flatten(report["plan"])}
    sel = nodes["STREAM_SELECT"]
    assert sel["mode"] == "fallback"
    assert "opaque predicate" in sel["fallback_reason"]
    assert sel["wall_s"] >= 0 and sel["rows_out"] == len(report["rows"])


# ---------------------------------------------------------------------------
# metric registry + layer metric names
# ---------------------------------------------------------------------------

def test_registry_type_clash_raises():
    obs.counter("obs_test.clash").inc()
    with pytest.raises(TypeError):
        obs.gauge("obs_test.clash")


def test_histogram_quantiles():
    h = obs.histogram("obs_test.hist")
    for v in range(1, 101):
        h.observe(v)
    snap = obs.snapshot()["obs_test.hist"]
    assert snap["count"] == 100 and snap["min"] == 1.0
    assert snap["max"] == 100.0
    assert 45 <= snap["p50"] <= 55
    assert 90 <= snap["p95"] <= 100


def test_feed_and_sink_metric_names():
    from repro.data.feeds import DatasetSink, Feed, SocketAdaptor
    ds = _dataset(n=0, parts=2)
    sock = SocketAdaptor()
    sock.push([{"id": 1000 + i, "g": 0, "a": i} for i in range(70)])
    sink = DatasetSink(ds, batch_size=32)
    feed = Feed("obs_feed", adaptor=sock, store=sink)
    while feed.pump(25):
        pass
    snap = obs.snapshot()
    assert snap["feed.obs_feed.records"] == 70
    assert snap["feed.joint.obs_feed.published"] == 70
    assert snap["feed.obs_feed.batch_records"]["count"] >= 3
    # 70 records in batches of 32 -> 2 delivered, 6 in backlog (sink lag)
    assert snap["feed.sink.D.records"] == 64
    assert snap["feed.sink.D.backlog"] == 6
    assert sink.flush() == 6
    assert obs.snapshot()["feed.sink.D.backlog"] == 0
    assert feed.joint.rate() >= 0.0


def test_lsm_flush_and_write_amplification_metrics():
    ds = _dataset(n=120, parts=2)   # threshold 32 -> several flushes
    for part in ds.partitions:
        part.primary.flush()
    lsm = ds.partitions[0].primary
    assert lsm.stats["flushed_rows"] >= lsm.stats["inserts"] > 0
    assert lsm.stats["flushed_bytes"] > 0
    wa = lsm.write_amplification()
    assert wa >= 1.0                # every ingested row flushed at least once
    snap = obs.snapshot()
    assert snap["lsm.flushes"] >= 1
    assert snap["lsm.flush_seconds"]["count"] >= 1
