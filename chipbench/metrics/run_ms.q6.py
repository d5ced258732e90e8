"""Mean device round trip of one record-program offload through NvmCsd, in
milliseconds: ``tier.run`` spans that carry ``columns`` (a record program's
tag), one an offload: dispatch, the device scan and the fetch of the
answer."""


def read(ctx):
    d = [e["dur"] for e in ctx.spans
         if e["name"] == "tier.run" and "columns" in (e.get("tags") or {})]
    if not d:
        return None
    return sum(d) / len(d) * 1e3
