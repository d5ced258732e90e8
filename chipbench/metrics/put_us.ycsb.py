"""Mean host-to-HBM put of one per-chunk execution, in microseconds:
``tier.put`` spans, one a chunk, until the chunk's device buffer is ready."""


def read(ctx):
    d = [e["dur"] for e in ctx.spans if e["name"] == "tier.put"]
    if not d:
        return None
    return sum(d) / len(d) * 1e6
