"""The block-diffusion flash kernels' share of their roofline: over the runs
of ``bd_flash_attention_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` in the traced
window, the least time the chip could take for each (the larger of its
useful FLOPs over peak FLOP/s and its least bytes over peak HBM bytes/s),
over the device seconds those runs took. FLOPs and bytes of a run come from
the family's ``bd_flash_kernel_flops`` / ``bd_flash_kernel_bytes`` (the mask's
allowed entries, not the tiles visited; K and V once a group of query heads;
never XLA's cost analysis), runs and seconds from ``kind_seconds`` by the
kernels' names. Nothing to read where no such kernel ran, or where the
family has no such functions."""


def read(run, trace, cell, peak):
    family = cell.family
    if not hasattr(family, "bd_flash_kernel_flops"):
        return None
    flops, least_bytes = (fn(cell.config, cell.traffic) for fn in
                          (family.bd_flash_kernel_flops, family.bd_flash_kernel_bytes))
    least_s = took_s = 0.0
    for kernel in flops:
        seconds, runs = trace["kind_seconds"].get(kernel, (0.0, 0.0))
        if not runs:
            continue
        by_flops = flops[kernel] / cell.chips / peak["bf16_flops_per_s"]
        by_bytes = least_bytes[kernel] / cell.chips / peak["hbm_bytes_per_s"]
        print(f"bd_flash_attention_roofline: {kernel} bound by {'flops' if by_flops >= by_bytes else 'bytes'} "
              f"({by_flops * 1e3:.3f} ms by FLOPs, {by_bytes * 1e3:.3f} ms by bytes), "
              f"{seconds / runs * 1e3:.3f} ms a run over {runs:.1f} runs", flush=True)
        least_s += runs * max(by_flops, by_bytes)
        took_s += seconds
    return 100.0 * least_s / took_s if took_s else None
