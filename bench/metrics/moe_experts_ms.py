"""Mean device time of the routed and shared experts in one decode
step, in ms: the ops under the program's ``moe_experts`` scope (the
expert FFN launches and the combine) inside the decode-step programs
that lie wholly in the traced span, over the number of those
programs."""

from bench import scopes, spans


def read(ctx):
    got = scopes.scope_ms(spans.trace(ctx), ctx.table["programs"],
                          "moe_experts")
    return None if got is None else got[0]
