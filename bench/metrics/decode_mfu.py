"""The decode steps' share of the chip's int8 peak, in %: the operations
every token decoded in the traced span needed (the model module's
``decode_token_ops``: for a dense model 2 per ternary weight and per
LM-head weight, plus attention at its live length) over the span's
length times the peak."""

from bench import readers


def read(ctx):
    lives = readers.decoded_live_lengths(ctx)
    if not lives:
        return None
    arch, kk = ctx.conf["arch"], readers.k_keep(ctx)
    use_lop = ctx.conf["server"]["use_lop"]
    ops = sum(ctx.model.decode_token_ops(arch, live, kk, use_lop)
              for live in lives)
    pk = readers.peaks(ctx)
    return 100.0 * ops / (ctx.reduction.window_s * pk["int8_ops_per_s"])
