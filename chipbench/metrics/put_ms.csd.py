"""Mean host-to-HBM put of one offload through NvmCsd, in milliseconds:
``tier.put`` spans, one an offload, from the start of the put until the
extent's device buffer is ready."""


def read(ctx):
    d = [e["dur"] for e in ctx.spans if e["name"] == "tier.put"]
    if not d:
        return None
    return sum(d) / len(d) * 1e3
