"""The resident fused attention kernel pair's share of its roofline: over
the kernel runs in the traced window, the least time the chip could take for
each (the larger of its FLOPs over peak FLOP/s and its least bytes over peak
HBM bytes/s), over the device seconds those runs took. FLOPs and bytes of a
run come from the family's ``attention_kernel_flops`` / ``_bytes`` (shapes,
never XLA's cost analysis), runs and seconds from ``kind_seconds`` by the
kernels' names. Nothing to read where no such kernel ran, or where the
family has no such functions."""


def read(run, trace, cell, peak):
    family = cell.family
    if not hasattr(family, "attention_kernel_flops"):
        return None
    flops, least_bytes = (fn(cell.config, cell.traffic) for fn in
                          (family.attention_kernel_flops, family.attention_kernel_bytes))
    least_s = took_s = 0.0
    for kernel in flops:
        seconds, runs = trace["kind_seconds"].get(kernel, (0.0, 0.0))
        by_flops = flops[kernel] / cell.chips / peak["bf16_flops_per_s"]
        by_bytes = least_bytes[kernel] / cell.chips / peak["hbm_bytes_per_s"]
        if runs:
            print(f"fused_attention_roofline: {kernel} bound by {'flops' if by_flops >= by_bytes else 'bytes'} "
                  f"({by_flops * 1e3:.3f} ms by FLOPs, {by_bytes * 1e3:.3f} ms by bytes), "
                  f"{seconds / runs * 1e3:.3f} ms a run over {runs:.1f} runs", flush=True)
        least_s += runs * max(by_flops, by_bytes)
        took_s += seconds
    return 100.0 * least_s / took_s if took_s else None
