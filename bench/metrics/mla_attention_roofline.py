"""Share of its roofline the latent attention of the decode steps
reaches, in %: the least time of the model module's ``mla_lane`` count
for every decode lane-step in the traced span (at its live length) and
of its ``mla_step`` count (W_UK and W_UV codes) for every decode step,
in every layer, over the device time of the work marked
``mla_attention`` (the absorption, the decode-attention launches, W_UV)
in those decode-step programs."""

from bench import cost, readers, scopes, spans


def read(ctx):
    got = scopes.scope_ms(spans.trace(ctx), ctx.table["programs"],
                          "mla_attention")
    lives = readers.decoded_live_lengths(ctx)
    if got is None or not lives:
        return None
    ms, runs = got
    arch, kk, pk = ctx.conf["arch"], readers.k_keep(ctx), readers.peaks(ctx)
    use_lop = ctx.conf["server"]["use_lop"]
    steps = len(ctx.reduction.program_spans.get("decode_step", ()))
    least = sum(cost.least_s(*ctx.model.mla_lane(arch, live, kk, use_lop),
                             pk) for live in lives)
    least += steps * cost.least_s(*ctx.model.mla_step(arch), pk)
    return 100.0 * arch["n_layers"] * least / (ms * runs / 1e3)
