"""Jitted step: device busy time of the capture per embedding row looked up
for the rows answered during it (rows x the servable's `lookups_per_row`, from
the runtime block's `startup`), so that the step's cost is comparable across
configurations of 26, 43 and 214 ids a row. None where the program reports no
`lookups_per_row`."""


def read(ctx):
    rows = ctx["trace"].get("rows")
    per_servable = (ctx["runtime"].get("startup") or {}).get("lookups_per_row") or {}
    lookups = [v for v in per_servable.values() if v]
    if not rows or len(lookups) != 1:
        return None
    return ctx["trace"]["busy_s"] * 1e9 / (rows * lookups[0])
