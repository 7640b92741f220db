"""Share of their roofline the ternary projections reach in decode
steps (for a dense model fused QKV and O through the qlinear kernel,
the fused FFN through the ffn kernel), in %: per call the least time of
its shapes (the model module's ``decode_projection_calls``; rows = the
mean number of lanes decoded per step in the traced span), over the
kernels' summed device time inside decode-step programs."""

from bench import cost, readers


def read(ctx):
    steps = len(ctx.reduction.program_spans.get("decode_step", ()))
    lives = readers.decoded_live_lengths(ctx)
    if not (steps and lives):
        return None
    per = ctx.model.decode_projection_calls(ctx.conf["arch"],
                                            len(lives) / steps)
    pk = readers.peaks(ctx)
    total = secs = 0.0
    for kernel, shapes in per.items():
        k_s, k_calls = readers.kernel_seconds(ctx, kernel, "decode_step")
        if not k_calls:
            return None
        total += k_calls / len(shapes) * sum(cost.least_s(*s, pk)
                                             for s in shapes)
        secs += k_s
    return 100.0 * total / secs
