"""Layer `train_step`: operations the forward and backward passes
require (benchmarks/flops.py, nothing recomputed) over the median
device time of the step's module on the `XLA Modules` line, over the
peak.  The step alone: host and loader are not in it."""

from benchmarks import flops, xplane


def read(run):
    if run["capture"] is None:
        return None
    step_s = xplane.main_module_median_s(run["capture"])
    if not step_s:
        return None
    return flops.mfu_percent(run["facts"]["flops_per_step"], step_s,
                             run["peaks"]["bf16_flops_per_s"],
                             run["chips"])
