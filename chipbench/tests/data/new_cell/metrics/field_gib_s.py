"""Record bytes scanned per second by the window's answered offloads."""


def read(ctx):
    done = [r for r in ctx.records if r.ok and r.job.kind == "offload"]
    if not done or ctx.t_last <= ctx.t_open:
        return None
    return (sum(r.n_blocks for r in done) * ctx.block_bytes / 2**30
            / (ctx.t_last - ctx.t_open))
