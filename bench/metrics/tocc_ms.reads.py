"""Kernels: the ngram T-occurrence count per fuzzy query in the traced
window, in ms: the ``fuzzy.tocc`` spans summed per query (by the spans'
``trace_id``) and averaged over the queries that have one.  On the
per-partition loop a query has one span a partition; under a partition
mesh one, the stacked dispatch with its stacking.  None where no fuzzy
query ran, or where the program has no such span."""


def read(c):
    per_query = {}
    for s in c.spans:
        if s.name == "fuzzy.tocc":
            per_query[s.trace_id] = per_query.get(s.trace_id, 0.0) \
                + (s.t1 - s.t0)
    if not per_query:
        return None
    return sum(per_query.values()) / len(per_query) * 1e3
