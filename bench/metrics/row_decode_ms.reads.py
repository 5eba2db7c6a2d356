"""Executor: result decode per query completed in the traced window, in
ms: the ``columnar.to_rows`` spans (column batches to row dicts at the
columnar boundary) plus the ``chain.assemble`` spans (the fused chain's
host-side filter of its gathered batch)."""

NAMES = ("columnar.to_rows", "chain.assemble")


def read(c):
    spans = [s for s in c.spans if s.name in NAMES]
    done = [r for r in c.requests if c.pc0 <= r.t_done <= c.pc1]
    if not spans or not done:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(done) * 1e3
