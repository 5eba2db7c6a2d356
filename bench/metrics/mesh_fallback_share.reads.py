"""Runtime (mesh): share of the window's SPMD-eligible calls that fell
back to the per-partition loop, in %: the ``mesh.spmd_fallbacks`` delta
over the ``mesh.spmd_dispatches`` plus ``mesh.spmd_fallbacks`` deltas.
None where no such call ran (no mesh)."""


def read(c):
    def delta(key):
        return c.obs1.get(key, 0) - c.obs0.get(key, 0)
    fell = delta("mesh.spmd_fallbacks")
    n = delta("mesh.spmd_dispatches") + fell
    if not n:
        return None
    return fell / n * 100.0
