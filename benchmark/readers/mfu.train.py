"""Model FLOPs of the steps the device ran in the traced window, over the
window's seconds x chips x the chip's bf16 peak. FLOPs come from the family's
``flops_per_step`` (shapes, never XLA's cost analysis); the steps are the
runs of the step program on the device plane, per chip."""


def read(run, trace, cell, peak):
    if not trace["program_runs"]:
        return None
    flops = cell.family.flops_per_step(cell.config, cell.traffic) / cell.chips * trace["program_runs"]
    return 100.0 * flops / (run["traced_s"] * peak["bf16_flops_per_s"])
