"""Device busy milliseconds of the traced campaign per round: the union of
the operations' intervals, averaged over the chips, over the mix's
rounds."""


def read(ctx):
    trace = ctx["trace"]
    busy = trace.busy_s() if trace is not None else 0.0
    return 1e3 * busy / ctx["mix"]["rounds"] if busy > 0 else None
