"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

A device plane is one whose name starts with ``/device:TPU:`` (one per
chip).  On it, the ``XLA Ops`` line holds one event per operation that
ran; ``XLA Modules`` holds one event per program (its name is the
jitted function's, e.g. ``jit__mask_core(...)``).  Busy time is the
union of the op intervals inside the window; per-program device time is
the sum of the module events' durations.  Both are averaged over the
chips used.  Host intervals (the benchmark's ``TraceAnnotation``s and
the program's spans moved onto the trace clock) name the idle gaps: a
gap goes to the shortest host interval that holds its midpoint.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the trace's own host events that name idle gaps: the benchmark's
# ``TraceAnnotation``s
HOST_NAMES = re.compile(r"^bench\.")

Interval = Tuple[float, float]          # start_ns, end_ns


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def planes(prof) -> Tuple[List[dict], List[Tuple[str, float, float]]]:
    """(device planes as {"ops", "modules"} event lists, host events)."""
    devs, host = [], []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: _events(line) for line in plane.lines}
            devs.append({"name": plane.name,
                         "ops": lines.get(OPS_LINE, []),
                         "modules": lines.get(MODULES_LINE, [])})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return devs, host


def union_ns(iv: Iterable[Interval], lo: float, hi: float
             ) -> Tuple[float, List[Interval]]:
    """Length of the union of intervals clipped to [lo, hi], and the
    merged busy intervals in order."""
    xs = sorted((max(a, lo), min(b, hi)) for a, b in iv
                if b > lo and a < hi)
    merged: List[List[float]] = []
    for a, b in xs:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return (sum(b - a for a, b in merged),
            [(a, b) for a, b in merged])


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def name_gaps(idle: Sequence[Interval],
              host: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle nanoseconds by the host interval holding each gap's
    midpoint (the shortest, so the innermost of nested spans wins)."""
    if not idle:
        return {}
    mids = np.asarray([(a + b) / 2 for a, b in idle])
    order = np.argsort(mids)
    mids = mids[order]
    lens = np.asarray([b - a for a, b in idle])[order]
    label = np.full(mids.size, -1)
    names = [h[0] for h in host]
    by_len = sorted(range(len(host)), key=lambda i: -host[i][2])
    for i in by_len:                  # longest first, shorter overwrite
        _n, s, d = host[i]
        a, b = np.searchsorted(mids, [s, s + d], side="left")
        label[a:b] = i
    out: Dict[str, float] = defaultdict(float)
    for lab, ln in zip(label.tolist(), lens.tolist()):
        out["no host span" if lab < 0 else names[lab]] += ln
    return dict(out)


def reduce(prof, lo_ns: Optional[float] = None, hi_ns: Optional[float] = None,
           host_extra: Sequence[Tuple[str, float, float]] = ()
           ) -> Dict[str, object]:
    """Device numbers of one trace between ``lo_ns`` and ``hi_ns`` (the
    whole trace where None): ``busy_s``, ``window_s``, ``device_s`` (per
    module name), ``idle_gaps`` (idle seconds by host interval name).
    ``host_extra`` adds host intervals already on the trace clock to
    the trace's own that match ``HOST_NAMES``."""
    devs, host = planes(prof)
    if not devs:
        return {"chips": 0}
    all_ops = [e for d in devs for e in d["ops"]]
    if lo_ns is None:
        lo_ns = min(s for _n, s, _d in all_ops) if all_ops else 0.0
    if hi_ns is None:
        hi_ns = max(s + d for _n, s, d in all_ops) if all_ops else 0.0
    busy_total, modules, idle_by = 0.0, defaultdict(float), defaultdict(float)
    hosts = [h for h in host if HOST_NAMES.search(h[0])] + list(host_extra)
    for d in devs:
        busy, merged = union_ns(((s, s + du) for _n, s, du in d["ops"]),
                                lo_ns, hi_ns)
        busy_total += busy
        for name, s, du in d["modules"]:
            if s + du > lo_ns and s < hi_ns:
                modules[name] += min(s + du, hi_ns) - max(s, lo_ns)
        for name, ns in name_gaps(gaps(merged, lo_ns, hi_ns), hosts).items():
            idle_by[name] += ns
    n = len(devs)
    return {"chips": n, "window_s": (hi_ns - lo_ns) / 1e9,
            "busy_s": busy_total / n / 1e9,
            "device_s": {k: v / n / 1e9 for k, v in modules.items()},
            "idle_gaps": {k: v / n / 1e9 for k, v in idle_by.items()}}


def top(d: Dict[str, float], n: int = 10) -> List[List[object]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
