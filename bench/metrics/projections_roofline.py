"""Share of their roofline the ternary projections reach in decode
steps (fused QKV and O through the qlinear kernel, the fused FFN), in
%: per call the least time of its shapes (``bench/cost.py``; rows = the
mean number of lanes decoded per step in the traced span), over the
kernels' summed device time inside decode-step programs."""

from bench import cost, readers


def read(ctx):
    q_s, q_calls = readers.kernel_seconds(ctx, "qlinear", "decode_step")
    f_s, f_calls = readers.kernel_seconds(ctx, "ffn", "decode_step")
    steps = len(ctx.reduction.program_spans.get("decode_step", ()))
    lives = readers.decoded_live_lengths(ctx)
    if not (q_calls and f_calls and steps and lives):
        return None
    rows = len(lives) / steps
    pk = readers.peaks(ctx)
    per = cost.projections_per_layer(ctx.conf["arch"], rows)
    least = lambda kind: cost.least_s(*per[kind], pk)
    total = (q_calls / 2 * (least("qkv") + least("o"))
             + f_calls * least("ffn"))
    return 100.0 * total / (q_s + f_s)
