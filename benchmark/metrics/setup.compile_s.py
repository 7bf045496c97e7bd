"""setup.compile_s: seconds of the set-up spent in the backend compile,
which holds the persistent cache's read (JAX's
backend_compile_duration), as the program's
`serve.registry.CompileCounters` add them up from the registry's making
to the window's start."""


def read(record):
    return (record["setup"].get("compile") or {}).get("compile_s") or None
