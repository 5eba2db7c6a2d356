"""The trace reduction: interval arithmetic on hand-made events, and the
whole reduction on a small trace recorded on one TPU v5 lite
(``data/small.xplane.pb.gz``: a quarter-second traced window of the
scan-analytics mix at 2^14 messages)."""

import gzip
import os
import shutil

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb.gz")


def test_union_clips_and_merges():
    busy, merged = trace.union_ns([(0, 10), (5, 20), (30, 40), (-5, 2)],
                                  0, 35)
    assert busy == 25 and merged == [(0, 20), (30, 35)]
    assert trace.gaps(merged, 0, 35) == [(20, 30)]
    assert trace.gaps([], 0, 7) == [(0, 7)]


def test_gaps_go_to_the_innermost_host_interval():
    idle = [(10, 20), (50, 60), (90, 100)]
    host = [("outer", 0, 100), ("inner", 40, 30)]
    got = trace.name_gaps(idle, host)
    assert got == {"outer": 20, "inner": 10}
    assert trace.name_gaps([(0, 5)], []) == {"no host span": 5}


def test_recorded_tpu_trace(tmp_path):
    path = tmp_path / "small.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    prof = trace.load(str(path))
    devs, host = trace.planes(prof)
    assert len(devs) == 1 and devs[0]["ops"] and devs[0]["modules"]
    red = trace.reduce(prof)
    assert red["chips"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    ops = [(s, s + d) for _n, s, d in devs[0]["ops"]]
    lo = min(a for a, _b in ops)
    hi = max(b for _a, b in ops)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    # busy is a union: never more than the sum of the op durations
    assert red["busy_s"] <= sum(b - a for a, b in ops) / 1e9 + 1e-12
    total_idle = sum(red["idle_gaps"].values())
    assert total_idle == pytest.approx(red["window_s"] - red["busy_s"],
                                       rel=1e-9, abs=1e-12)
    # every program's device time lies inside the window
    assert 0 < sum(red["device_s"].values()) <= red["window_s"]
