"""Least time a step could take on this chip - the larger of model FLOPs
over peak FLOP/s and least bytes over peak HBM bytes/s - over the mean
device time of the step program's whole runs in the traced window."""


def read(run, trace, cell, peak):
    if not trace["program_mean_s"]:
        return None
    flops = cell.family.flops_per_step(cell.config, cell.traffic) / cell.chips
    least_bytes = cell.family.least_bytes_per_step(cell.config, cell.traffic)
    by_flops, by_bytes = flops / peak["bf16_flops_per_s"], least_bytes / peak["hbm_bytes_per_s"]
    print(f"train_step_roofline: bound by {'flops' if by_flops >= by_bytes else 'bytes'} "
          f"({by_flops * 1e3:.2f} ms by FLOPs, {by_bytes * 1e3:.2f} ms by bytes), "
          f"step program {trace['program_mean_s'] * 1e3:.2f} ms on the device", flush=True)
    return 100.0 * max(by_flops, by_bytes) / trace["program_mean_s"]
