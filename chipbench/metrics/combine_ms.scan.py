"""Milliseconds per offload in the scheduler's combine stage
(``sched.stage.combine_seconds`` sum over the offloads completed)."""


def read(ctx):
    n = ctx.reg.get("offload.commands", 0)
    s = ctx.reg.get("sched.stage.combine_seconds.sum")
    if not n or s is None:
        return None
    return s / n * 1e3
