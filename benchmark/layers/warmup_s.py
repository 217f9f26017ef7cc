"""Start-up: the server's ladder warm-up, as /monitoring's runtime block has it."""


def read(ctx):
    return ctx["runtime"].get("warmup_s")
