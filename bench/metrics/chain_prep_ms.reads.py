"""Kernels (host wrapper): the fused chain's operand preparation per
query completed in the traced window, in ms: the ``chain.operands``
spans (each partition's gather: tier probes, scan batch, range
predicates, pow2 padding) plus the ``chain.h2d`` spans (the device-pool
fetch)."""

NAMES = ("chain.operands", "chain.h2d")


def read(c):
    spans = [s for s in c.spans if s.name in NAMES]
    done = [r for r in c.requests if c.pc0 <= r.t_done <= c.pc1]
    if not spans or not done:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(done) * 1e3
