"""The machine (NO entry in BENCHMARK.json: the chip machine is gVisor, whose
kernel keeps no run-queue wait, so this reads nothing there; it reads on a
Linux host with `schedstat`): time the request path's threads (pollers, handlers,
collector, dispatch, completer) were RUNNABLE and had no core, summed, over
the window, in percent of one core (`sched.<role>`: the kernel's run-queue
wait a thread). Not the interpreter lock: a thread that waits for the lock
sleeps and is not runnable. Large on a host whose cores are shared out."""
from _cpu import REQUEST_PATH_ROLES, role_pct_of_core


def read(ctx):
    if not any(name.startswith("sched.") for name in ctx["phases"]):
        return None  # a kernel that keeps no wait: absent, not zero
    return role_pct_of_core(ctx, "sched.", REQUEST_PATH_ROLES)
