"""Share of its roofline that the Step-4 Gram Pallas kernel reaches in the
traced campaign: the least time the chip needs for the Gram products of
every eval (``flops.gram_work``) over the kernel's device time in the
trace.  The peak is the chip's bf16 one; the kernel runs its dots at
HIGHEST precision, in several bf16 passes."""

# the Mosaic custom call takes the name of the jitted wrapper that holds
# it (``gram``), e.g. ``gram.1`` in the compiled HLO
KERNEL = "gram"


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds, calls = trace.op_seconds(lambda name: KERNEL in name)
    if calls == 0 or seconds <= 0:
        return None
    mix = ctx["mix"]
    evals = -(-mix["rounds"] // (mix["eval_every"] or mix["rounds"]))
    fl = ctx["flops"]
    ops, nbytes = fl.gram_work(ctx["kind"], ctx["config"],
                               evals * mix["seeds_per_campaign"])
    least, _ = fl.roofline_seconds(ops, nbytes, ctx["peaks"])
    return 100.0 * least / seconds
