"""Device milliseconds of the traced campaign's collectives per round:
the summed durations of the cross-chip operations (the round's one fused
psum, ``engine.psum_bundle``, and the Step-4 eval's Gram psums), averaged
over the chips, over the mix's rounds.  None where the trace holds no
collective (a campaign on one chip)."""

# a collective's op in the chip's trace takes the name of the JAX primitive
# that made it (``psum.3``: a TPU v5e's trace of the mesh cell shows 54 per
# chip, 30 rounds and 3 evals of 8 Grams) or, where XLA made it, its HLO
# opcode (``all-reduce.7``, ``all-reduce-start.1`` / ``-done.1``)
COLLECTIVES = ("psum", "pmax", "pmin", "all_gather", "ppermute", "all_to_all",
               "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds, calls = trace.op_seconds(is_collective)
    if calls == 0 or seconds <= 0:
        return None
    return 1e3 * seconds / ctx["mix"]["rounds"]
