"""Host loop and input: device idle time per step outside the train step's
executions, the time the harness loop (drawing and placing the batch,
dispatching, waiting on the previous step) leaves the chip without work.
Idle time inside a step is the program's own and is not counted here."""
from bench import trace


def read(ctx):
    red, worst = ctx.reduced, None
    for dev in red.ops:
        n = len(red.steps[dev])
        if n:
            outside = trace._minus(trace.idle(red, dev), red.steps[dev])
            worst = max(worst or 0.0, trace._covered(outside) / n / 1e6)
    return worst
