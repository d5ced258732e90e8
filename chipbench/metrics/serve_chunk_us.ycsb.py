"""Mean time of one per-chunk execution (``stage.serve_chunk`` spans), in
microseconds."""


def read(ctx):
    d = [e["dur"] for e in ctx.spans if e["name"] == "stage.serve_chunk"]
    if not d:
        return None
    return sum(d) / len(d) * 1e6
