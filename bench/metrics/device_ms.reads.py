"""Kernels: device busy time per query completed in the traced window,
in ms (profiler trace)."""


def read(c):
    tr = c.trace_red
    done = [r for r in c.requests if c.pc0 <= r.t_done <= c.pc1]
    if not tr or not tr.get("chips") or not done:
        return None
    return tr["busy_s"] / len(done) * 1e3
