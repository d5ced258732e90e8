"""Milliseconds per offload of staging copies into the scheduler's group
buffers: ``stage.copy`` spans (one a member run, on the gather pool) summed
over the offloads completed. Pool seconds: copies on several threads add."""


def read(ctx):
    n = ctx.reg.get("offload.commands", 0)
    d = [e["dur"] for e in ctx.spans if e["name"] == "stage.copy"]
    if not n or not d:
        return None
    return sum(d) / n * 1e3
