"""Zone bytes scanned per second by the window's offloads through
OffloadScheduler (the array): every byte of every answered offload, counted from
the benchmark's own extents, over the time from the window's opening to
the last answer."""

def read(ctx):
    done = [r for r in ctx.records if r.ok and r.job.kind == "offload"]
    if not done or ctx.t_last <= ctx.t_open:
        return None
    nbytes = sum(r.n_blocks for r in done) * ctx.block_bytes
    return nbytes / (ctx.t_last - ctx.t_open) / 2**30
