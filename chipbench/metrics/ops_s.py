"""Operations answered per second, offloads and appends alike, over the time
from the window's opening to the last answer."""


def read(ctx):
    done = [r for r in ctx.records if r.ok]
    if not done or ctx.t_last <= ctx.t_open:
        return None
    return len(done) / (ctx.t_last - ctx.t_open)
