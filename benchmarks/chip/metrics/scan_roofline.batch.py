"""The full-scan kernel's share of its roofline.

Per full-scan call the scan needs to read every live row once and write one
score per query and row: live rows x dim x 4 B, plus the queries and the
scores.  The least time is the larger of those bytes over the chip's HBM
bandwidth and the call's 2 x B x rows x dim operations over its bf16 peak
(bandwidth bounds it at these sizes); the share is the least time of all
calls in the window over the kernel's device time there.  The pad-and-cast
copy before the kernel is not counted, so the count does not depend on
what implements the scan.
"""
KERNEL = "scan_scores"          # the Pallas kernel's op name prefix in the trace
MODULE = "jit_query_full_scan"


def read(run):
    t = run.trace
    if t is None:
        return None
    calls, _ = t.module(MODULE)
    kernel_s = t.op_seconds(MODULE, KERNEL)
    if not calls or kernel_s <= 0:
        return None
    e = run.cell.config["engine"]
    b = int(run.cell.traffic["streams"][0]["rows"])
    rows, dim = run.live_rows, int(e["dim"])
    nbytes = 4 * (rows * dim + b * dim + b * rows)
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                2.0 * b * rows * dim / run.peaks["bf16_flops_per_s"])
    return 100.0 * calls * least / kernel_s
