"""Mean scheduler queue wait of the query tasks completed in the window
(`core/scheduler.py` counters)."""
from chipbench.readers import mean_wait_ms


def read(run):
    return mean_wait_ms(run, "query")
