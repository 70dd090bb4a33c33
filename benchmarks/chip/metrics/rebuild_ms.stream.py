"""Device milliseconds per index rebuild: the mean duration of the rebuild
module (`rebuild`: k-means re-cluster and re-pack) in the trace."""
from chipbench.readers import module_ms


def read(run):
    return module_ms(run, "jit_rebuild")
