"""Kernels (the routed layer): of the held experts of the routed layers, the
share that took at least one token, a step: 100 x `moe.experts_hit` over
`held` x the routed layers x the window's batches. The step counts, a routed
layer a step and where the pairs' tiles are walked, the held experts whose
rows took a token (`moe.experts_hit`, recorded by the completer as a phase by
count); `held` is the servable's `startup.expert_plan`, the routed layers
those of its `startup.layer_plan` (every layer of `qwen3_next`, whose plan
names mixers alone; the layers named `.../moe` where a plan names both), and
`batch.dispatch` counts the batches. 100 where every held expert of every
routed layer has work in every step; a last layer that routes a few tokens a
row, or a share that holds more experts than a step's tokens reach, reads
lower: its experts' weights are then held for nothing that step. None where
the program counts no such thing (a family without a routed layer; a commit
before ISSUE 58)."""
from _lib import phase_count


def read(ctx):
    startup = ctx["runtime"].get("startup") or {}
    plans = [p for p in (startup.get("expert_plan") or {}).values() if p]
    layers = [p for p in (startup.get("layer_plan") or {}).values() if p]
    hit, batches = phase_count(ctx, "moe.experts_hit"), phase_count(ctx, "batch.dispatch")
    if not hit or not batches or len(plans) != 1 or len(layers) != 1:
        return None
    named = {kind: n for kind, n in layers[0].items() if kind.endswith("/moe")}
    routed = sum(named.values()) if named else sum(layers[0].values())
    return 100.0 * hit / (plans[0]["held"] * routed * batches)
