"""Start-up: the server's parameter init (or checkpoint load), blocked until
the parameters are on the device, as the runtime block's `startup` has it."""
from _timeline import startup_s


def read(ctx):
    return startup_s(ctx, "params_init_s")
