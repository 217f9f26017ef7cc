"""Kernels (the routed layer): the busiest held expert's load over the mean
load of a held expert, over the window. The step counts, a routed layer a
step and inside the expert loops, the most tokens one held expert's blocks
took (`moe.busiest_expert_tokens`) and all that the held experts' blocks took
(`moe.assignments_here`); `held` is the servable's `startup.expert_plan`. 1.0
is an even spread; the grouped product's padded blocks and its longest loop
follow the busiest. None where the program counts no such thing."""
from _lib import phase_count


def read(ctx):
    plans = [p for p in ((ctx["runtime"].get("startup") or {}).get("expert_plan") or {}).values() if p]
    here = phase_count(ctx, "moe.assignments_here")
    if not here or len(plans) != 1:
        return None
    return phase_count(ctx, "moe.busiest_expert_tokens") * plans[0]["held"] / here
