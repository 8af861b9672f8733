"""Share of the traced campaign's device-idle time (the first device's, as
``idle_gaps``) in which the innermost program span
(``repro.launch.spans``) was a campaign's root span, or none: idle time
that no step of the program accounts for.  None for a program without
those spans."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    try:
        from repro.launch import spans
    except ImportError:
        return None
    import spanreduce
    return spanreduce.idle_unattributed_share(trace, spans.NAMES,
                                              spans.ROOTS)
