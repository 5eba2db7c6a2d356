"""Executor layer: mean wall time per query of the root ``exec.*`` span
(the program's span tracer), in ms."""


def read(c):
    roots = [s for s in c.spans if s.name.startswith("exec.") and s.depth == 0]
    if not roots:
        return None
    return sum(s.t1 - s.t0 for s in roots) / len(roots) * 1e3
