"""Executor: thread CPU time of the queries' root spans (depth 0:
``query.optimize`` and the root ``exec.*``) per query completed in the
traced window, in ms.  Beside ``exec_ms.reads`` (wall time of the root
``exec.*`` span) the gap is time the client threads spent waiting: on
the GIL, the device, or a lock.  None where the spans carry no
``cpu_s``."""


def read(c):
    roots = [s for s in c.spans if s.depth == 0]
    done = [r for r in c.requests if c.pc0 <= r.t_done <= c.pc1]
    if not roots or not done \
            or any(getattr(s, "cpu_s", None) is None for s in roots):
        return None
    return sum(s.cpu_s for s in roots) / len(done) * 1e3
