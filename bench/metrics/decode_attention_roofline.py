"""Share of its roofline the decode attention reaches (both launches:
the LOP screen and the exact phase), in %: the least time of every
decode lane-step in the traced span (the model module's
``decode_attention_calls``, at each step's live length) over the
kernels' summed device time inside decode-step programs."""

from bench import cost, readers


def read(ctx):
    secs, calls = readers.kernel_seconds(ctx, "decode_attention",
                                         "decode_step")
    lives = readers.decoded_live_lengths(ctx)
    if not calls or not lives or secs <= 0:
        return None
    arch, kk, pk = ctx.conf["arch"], readers.k_keep(ctx), readers.peaks(ctx)
    use_lop = ctx.conf["server"]["use_lop"]
    least = 0.0
    for live in lives:
        for ops, nbytes, n in ctx.model.decode_attention_calls(
                arch, live, kk, use_lop):
            least += cost.least_s(ops, nbytes, pk) * n
    return 100.0 * least / secs
