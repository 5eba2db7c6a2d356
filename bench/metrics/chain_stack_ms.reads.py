"""Kernels (host wrapper): the stacking of every partition's fused-chain
operands before the one dispatch over the partition mesh, per query
completed in the traced window, in ms: the ``chain.stack`` spans
(``StackCache`` lookups, and the builds of the stacks it misses, plus
the per-partition bound vectors).  None where no chain ran on a mesh,
or where the program has no such span."""


def read(c):
    spans = [s for s in c.spans if s.name == "chain.stack"]
    done = [r for r in c.requests if c.pc0 <= r.t_done <= c.pc1]
    if not spans or not done:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(done) * 1e3
