"""Share of its roofline the expert work of a decode step reaches, in
%: the least time of the model module's ``moe_layer`` count (each row
through its top-k experts and the shared ones; the codes of the
distinct experts the rows touch; rows = the mean number of lanes
decoded per step in the traced span) in every MoE layer, over the
device time marked ``moe_experts`` per decode step."""

from bench import cost, readers, scopes, spans


def read(ctx):
    got = scopes.scope_ms(spans.trace(ctx), ctx.table["programs"],
                          "moe_experts")
    steps = len(ctx.reduction.program_spans.get("decode_step", ()))
    lives = readers.decoded_live_lengths(ctx)
    if got is None or not (steps and lives):
        return None
    arch = ctx.conf["arch"]
    layers = arch["n_layers"] - arch["first_k_dense"]
    least = layers * cost.least_s(
        *ctx.model.moe_layer(arch, len(lives) / steps), readers.peaks(ctx))
    return 100.0 * least / (got[0] / 1e3)
