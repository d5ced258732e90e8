"""Share of the HBM roofline the scans through NvmCsd
reached: the least time the chip could take to read every scanned extent
once (bytes over peak HBM bandwidth) over the kernel time in the device
trace, in percent."""

def read(ctx):
    dw = ctx.device
    nbytes = sum(r.n_blocks for r in ctx.records
                 if r.ok and r.job.kind == "offload") * ctx.block_bytes
    if dw is None or not nbytes or dw.kernel_s <= 0:
        return None
    return nbytes / ctx.peaks["hbm_bytes_per_s"] / dw.kernel_s * 100
