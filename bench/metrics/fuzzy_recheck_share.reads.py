"""Executor: share of the fuzzy index chains run in the window that
still re-checked ``pred`` row by row after the batched verify
(``fuzzy.selects_rechecked`` delta over ``fuzzy.selects`` delta), in %.
None where no fuzzy chain ran, or where the program has no such
counters."""


def read(c):
    key = "fuzzy.selects"
    n = c.obs1.get(key, 0) - c.obs0.get(key, 0)
    if not n:
        return None
    key = "fuzzy.selects_rechecked"
    return (c.obs1.get(key, 0) - c.obs0.get(key, 0)) / n * 100.0
