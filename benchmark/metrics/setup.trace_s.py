"""setup.trace_s: seconds of the set-up spent in tracing and lowering to
MLIR (JAX's jaxpr_trace_duration and jaxpr_to_mlir_module_duration), as
the program's `serve.registry.CompileCounters` add them up from the
registry's making to the window's start."""


def read(record):
    return (record["setup"].get("compile") or {}).get("trace_s") or None
