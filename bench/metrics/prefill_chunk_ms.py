"""Mean device span of one run of the prefill-chunk program
(``jit_prefill_chunk``: one chunk of one prompt into its lane), over
its whole runs in the traced span: none that the profiler's start or
stop cut (``trace.whole_runs``)."""

from bench import spans


def read(ctx):
    return spans.prefill_chunk_ms(spans.trace(ctx), ctx.table["programs"])
