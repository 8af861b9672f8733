"""Share of the traced campaign's span in which the device ran no
operation, averaged over the chips."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.ops or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
