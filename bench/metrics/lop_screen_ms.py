"""Mean device time of the LOP screen phase in one decode step, in ms:
the ops under the program's ``lop_screen`` scope (the screen launch and
its candidate selection) inside the decode-step programs that lie
wholly in the traced span, over the number of those programs."""

from bench import spans


def read(ctx):
    return spans.lop_screen_ms(spans.trace(ctx), ctx.table["programs"])
