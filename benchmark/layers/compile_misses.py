"""Start-up: compile requests the persistent cache could not answer, from
server start to the window's end."""


def read(ctx):
    cache = ctx["runtime"].get("compile_cache")
    return None if cache is None else cache["misses"]
