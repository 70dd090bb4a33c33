"""The 50th percentile of query latency, from each query's due time to its
acknowledgement, over every query due in the window (host clock)."""
from chipbench.readers import percentile_ms


def read(run):
    return percentile_ms(run, "query", 50)
