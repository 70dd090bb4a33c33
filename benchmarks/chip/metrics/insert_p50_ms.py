"""The 50th percentile of insert latency, from each insert's due time to its
acknowledgement, over every insert due in the window (host clock)."""
from chipbench.readers import percentile_ms


def read(run):
    return percentile_ms(run, "insert", 50)
