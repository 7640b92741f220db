"""Mean device span of one run of the prefill-chunk program
(``jit_prefill_chunk``: one chunk of one prompt into its lane), over
the runs that lie wholly in the traced span."""

from bench import spans


def read(ctx):
    return spans.prefill_chunk_ms(spans.trace(ctx))
