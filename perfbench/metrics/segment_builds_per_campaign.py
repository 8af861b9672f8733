"""Segment scans the campaign driver built (traced, lowered and compiled
or loaded) per campaign: the program's counter ``segment_builds`` over its
counter ``campaigns`` (``repro.launch.spans``), over every campaign of the
run, set-up's and the traced one included; a cell's campaigns share one
schedule shape.  None for a program without those counters."""


def read(ctx):
    try:
        from repro.launch import spans
    except ImportError:
        return None
    runs = spans.counts["campaigns"]
    return spans.counts["segment_builds"] / runs if runs else None
