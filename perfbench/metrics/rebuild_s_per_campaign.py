"""Host seconds per campaign of the window spent tracing, lowering and
compiling or loading executables: the union of the spans of JAX's
``jaxpr_trace_duration``, ``jaxpr_to_mlir_module_duration`` and
``backend_compile_duration`` events (a load from the persistent cache runs
inside the last)."""


def read(ctx):
    rebuild = ctx["host"]["rebuild"]
    return sum(rebuild) / len(rebuild) if rebuild else None
