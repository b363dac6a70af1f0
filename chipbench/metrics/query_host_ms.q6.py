"""query_host_ms.q6: the host's own time per query, the mean over the
traced ``query.agg_where`` spans of their duration less their
``query.fetch`` child (the wait for the masked histogram and its copy to
the host): predicate compile and cache lookup, dispatch, and the
dictionary-weighted sum over the counts. Nothing when the program writes
no query spans."""
from chipbench import spans

CELL = "lineitem-q6"


def read(obs):
    t = spans.for_run(obs, __file__, CELL, "query.agg_where")
    if t is None:
        return None
    aggs, fetches = t.named("query.agg_where"), t.named("query.fetch")
    return 1e-6 * sum(spans.self_ns(a, fetches) for a in aggs) / len(aggs)
