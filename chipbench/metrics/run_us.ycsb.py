"""Mean device round trip of one per-chunk execution, in microseconds:
``tier.run`` spans, one a chunk: dispatch, the device run and the fetch of
the answer."""


def read(ctx):
    d = [e["dur"] for e in ctx.spans if e["name"] == "tier.run"]
    if not d:
        return None
    return sum(d) / len(d) * 1e6
