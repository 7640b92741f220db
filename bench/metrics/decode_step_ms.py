"""Mean device span of one run of the decode-step program (every active
lane one token), over its whole runs in the traced span: none that the
profiler's start or stop cut (``trace.whole_runs``)."""

from bench import readers


def read(ctx):
    return readers.program_mean_ms(ctx, "decode_step")
