"""Mean device idle inside one serve cycle that runs a decode step, in
ms: over the program's ``repro.cycle`` spans that lie wholly in the
traced span and hold a ``repro.decode.dispatch``, the time in the span
during which no op runs on the device. How that idle splits by the
innermost ``repro.*`` span covering it goes to standard error."""

from bench import spans


def read(ctx):
    return spans.cycle_idle_ms(spans.trace(ctx))
