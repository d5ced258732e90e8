"""Mean verifier time per offload submit, in microseconds
(``sched.verify_seconds`` sum over count)."""


def read(ctx):
    n = ctx.reg.get("sched.verify_seconds.count", 0)
    if not n:
        return None
    return ctx.reg["sched.verify_seconds.sum"] / n * 1e6
