"""Kernels: the stacked T-occurrence program's share of its HBM roofline
in the traced window, in %: the bytes it must move
(``tocc_roofline.min_bytes`` of the ``mesh.tocc_hit_positions`` and
``mesh.tocc_count_cells`` deltas) over its device time summed over the
chips times each chip's HBM bandwidth.  The program is bound by memory
(a gather and a scatter-add, no arithmetic to speak of), so the bytes
bound is its roofline.  None where the program did not run (no mesh, or
a program without it)."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_metric_tocc_roofline",
    pathlib.Path(__file__).with_name("tocc_roofline.py"))
roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(roofline)


def read(c):
    tr = c.trace_red
    if not tr or not tr.get("chips"):
        return None
    busy = sum(v for k, v in tr.get("device_s", {}).items()
               if k.startswith(roofline.PROGRAM)) * tr["chips"]

    def delta(key):
        return c.obs1.get(key, 0) - c.obs0.get(key, 0)
    hits = delta("mesh.tocc_hit_positions")
    if busy <= 0 or not hits:
        return None
    peak = roofline.PEAK_HBM_BYTES_S[c.device_kind]
    need = roofline.min_bytes(hits, delta("mesh.tocc_count_cells"))
    return need / (busy * peak) * 100.0
