"""Seconds of set-up spent compiling programs or loading them from the
compile cache: the program's ``compile.backend_s`` and
``compile.cache_load_s`` counters (``repro.runtime.compile_cache``, host
clock), read after a window in which nothing compiles.  Nothing when the
program keeps no compile counters."""


def read(run):
    from repro.runtime import compile_cache

    totals = getattr(compile_cache, "totals", dict)()
    if "compile.backend_s" not in totals and "compile.cache_load_s" not in totals:
        return None
    return totals.get("compile.backend_s", 0.0) + totals.get("compile.cache_load_s", 0.0)
