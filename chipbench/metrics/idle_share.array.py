"""Share of the traced window in which no operation ran on the device, in
percent (one minus busy over window)."""


def read(ctx):
    dw = ctx.device
    if dw is None or dw.window_s <= 0:
        return None
    return dw.idle_share * 100
