"""Device milliseconds per insert call: the mean duration of the insert
module (`_insert`, copying or, in a rebuild's replay, donating)."""
from chipbench.readers import module_ms


def read(run):
    return module_ms(run, "jit__insert")
