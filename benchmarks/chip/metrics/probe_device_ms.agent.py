"""Device milliseconds per probed query: the mean duration of the probed
query's module (`query_probed`) in the trace."""
from chipbench.readers import module_ms


def read(run):
    return module_ms(run, "jit_query_probed")
