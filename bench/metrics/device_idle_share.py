"""Share of the traced span in which no operation ran on the device, in
%: 1 - (union of op intervals) / span, averaged over the chips used."""


def read(ctx):
    red = ctx.reduction
    if red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
