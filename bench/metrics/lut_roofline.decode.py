"""The table kernels' share of their roofline in the decode step.

The bound is the larger of two times: the bytes the kernels need over the
HBM peak, and twice the multiply-adds of the dense projections they
replace, for every slot, over the bf16 peak.  The bytes are the converted
projections' leaves as stored (counted at set-up) plus each slot's codes
in and outputs out.  The same work is counted whatever implements it.
The divisor is the device time of the table-kernel ops per decode step.
"""

from bench.metrics_common import kernel_ns_per_decode


def read(run):
    kern = kernel_ns_per_decode(run)
    if not kern:
        return None
    rows = run.slots
    bytes_ = run.info["stored_bytes"] + rows * run.info["row_bytes"]
    flops = 2.0 * rows * run.info["linear_work"]["linears"]
    bound_s = max(bytes_ / run.peaks["hbm_bw"], flops / run.peaks["bf16_flops"])
    return 100.0 * bound_s / (kern / 1e9)
