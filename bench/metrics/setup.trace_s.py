"""Seconds of set-up spent tracing programs and lowering them to MLIR: the
program's ``compile.trace_s`` counter (``repro.runtime.compile_cache``,
host clock), read after a window in which nothing compiles.  Nothing when
the program keeps no compile counters."""


def read(run):
    from repro.runtime import compile_cache

    return getattr(compile_cache, "totals", dict)().get("compile.trace_s")
