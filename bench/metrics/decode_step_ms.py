"""Mean device span of one run of the decode-step program (every active
lane one token), from the trace's program events."""

from bench import readers


def read(ctx):
    return readers.program_mean_ms(ctx, "decode_step")
