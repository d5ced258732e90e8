"""Milliseconds per offload in the scheduler's host-to-HBM puts: the
``stage.put`` events (one a staged group, from the start of its put until
its device buffer is ready) summed over the offloads completed."""


def read(ctx):
    n = ctx.reg.get("offload.commands", 0)
    d = [e["dur"] for e in ctx.spans if e["name"] == "stage.put"]
    if not n or not d:
        return None
    return sum(d) / n * 1e3
