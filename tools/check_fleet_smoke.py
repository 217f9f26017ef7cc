#!/usr/bin/env python
"""CI gate for the fleet kill/restart chaos smoke (ISSUE 17).

Usage: python tools/check_fleet_smoke.py SOAK_LINE_JSON

Reads the JSON line a SOAK_FLEET=1 soak printed (tools/ci_tier1.sh tees
it to a file) and asserts the acceptance criteria end to end:

- the chaos script ran against a real multi-process fleet (>= 3 serving
  replica subprocesses behind the fleet.router subprocess) with edge
  traffic dialing ONLY the router;
- SIGKILLing one replica mid-traffic cost ZERO edge-visible errors (the
  router's scoreboard + failover absorbed it) and every per-1s goodput
  window of the kill/restart phase (kill -> canary publish) stayed at
  >= half the steady-state median — the rollout phase that follows is
  excluded from the goodput gate (three replicas warmup-compiling the
  canary at once starve a CPU host) and gated on zero errors + bounded
  propagation instead;
- the restarted replica REJOINED through gossip (its serving record
  re-admitted it to the router's rotation: state `serving` in the
  router's /fleetz view and healthy_backends back at full strength),
  within a bounded wall time;
- the canary published into the shared base dir went live on every
  replica, and ONE replica's operator rollback propagated FLEET-WIDE:
  the router's rollout coordinator blacklisted the version and every
  replica's lifecycle rolled it back within about one gossip interval
  of the router's state change;
- scores through the router stayed BIT-IDENTICAL to a direct backend
  call, both before the chaos and after the rollback settled;
- the observability surfaces answered: dts_tpu_fleet_* series on the
  router's gossip-port /metrics AND in a replica's REST exposition.

Exits 0 on success; prints every failure and exits 1.
"""

import json
import sys

REJOIN_BOUND_S = 45.0
# Propagation is measured between two polled observations (router
# blacklist seen -> last replica rolled back); delivery itself rides each
# replica's next push-pull exchange, i.e. at most one gossip interval,
# with the poll cadence on both ends as slack.
PROPAGATION_SLACK_S = 1.0
MIN_GOODPUT_RATIO = 0.5


def main() -> None:
    if len(sys.argv) != 2:
        print("usage: check_fleet_smoke.py SOAK_LINE_JSON", file=sys.stderr)
        sys.exit(2)
    path = sys.argv[1]
    line = None
    try:
        with open(path) as f:
            for raw in reversed(f.read().strip().splitlines()):
                try:
                    parsed = json.loads(raw)
                except json.JSONDecodeError:
                    continue
                if isinstance(parsed, dict) and "fleet" in parsed:
                    line = parsed
                    break
    except OSError as e:
        print(
            f"check_fleet_smoke: FAIL: cannot read {path}: {e}",
            file=sys.stderr,
        )
        sys.exit(1)
    if line is None or not isinstance(line.get("fleet"), dict):
        print(
            f"check_fleet_smoke: FAIL: no JSON line with a `fleet` block "
            f"in {path}", file=sys.stderr,
        )
        sys.exit(1)

    fl = line["fleet"]
    kill = fl.get("kill") or {}
    rollout = fl.get("rollout") or {}
    failures = []

    if fl.get("replicas", 0) < 3:
        failures.append(
            f"fleet ran with {fl.get('replicas')} replicas (need >= 3 "
            "for a kill to leave a quorum)"
        )
    if fl.get("requests", 0) < 50:
        failures.append(
            f"only {fl.get('requests')} edge requests — the soak never "
            "generated meaningful traffic"
        )
    # THE headline criterion: a replica died and came back mid-traffic
    # and no edge client ever saw it.
    if fl.get("errors", 0) != 0:
        failures.append(
            f"{fl.get('errors')} edge-visible error(s) — kinds: "
            f"{fl.get('error_kinds')}"
        )
    ratio = fl.get("min_chaos_window_ratio")
    if ratio is None or ratio < MIN_GOODPUT_RATIO:
        failures.append(
            f"goodput collapsed during chaos: min per-1s window ratio "
            f"{ratio} < {MIN_GOODPUT_RATIO} of the steady median "
            f"({fl.get('steady_window_median')}/s; chaos windows: "
            f"{fl.get('chaos_windows')})"
        )
    if not fl.get("bit_identical_pre"):
        failures.append(
            "pre-chaos probe: scores through the router were NOT "
            "bit-identical to a direct backend call"
        )
    if not fl.get("bit_identical_post"):
        failures.append(
            "post-rollback probe: scores through the router were NOT "
            "bit-identical to a direct backend call"
        )
    rejoin_s = kill.get("rejoin_s")
    if rejoin_s is None or rejoin_s > REJOIN_BOUND_S:
        failures.append(
            f"restarted replica {kill.get('victim')} did not rejoin via "
            f"gossip within {REJOIN_BOUND_S}s (took: {rejoin_s}s)"
        )
    if kill.get("healthy_backends") != fl.get("replicas"):
        failures.append(
            f"rotation never returned to full strength after the "
            f"restart (healthy_backends={kill.get('healthy_backends')} "
            f"of {fl.get('replicas')})"
        )
    if not rollout.get("rollback_accepted"):
        failures.append(
            "the operator rollback POST was never accepted — no canary "
            "was live to roll back"
        )
    interval = fl.get("gossip_interval_s") or 0.5
    prop = rollout.get("propagation_s")
    bound = interval + PROPAGATION_SLACK_S
    if prop is None or prop > bound:
        failures.append(
            f"fleet-wide rollback took {prop}s from the router's "
            f"blacklist to the last replica (bound: one gossip interval "
            f"{interval}s + {PROPAGATION_SLACK_S}s slack = {bound}s)"
        )
    per_replica = rollout.get("per_replica_rolled_back") or []
    if len(per_replica) != fl.get("replicas") or any(
        v != rollout.get("canary_version") for v in per_replica
    ):
        failures.append(
            f"not every replica rolled the canary back "
            f"(rolled_back_version per replica: {per_replica})"
        )
    counters = fl.get("router_counters") or {}
    if counters.get("requests", 0) < 50:
        failures.append(
            f"router forwarded only {counters.get('requests')} requests "
            "— edge traffic did not route through it"
        )
    if fl.get("prom_router_series", 0) < 10:
        failures.append(
            f"only {fl.get('prom_router_series')} dts_tpu_fleet_* series "
            "on the router's /metrics (expected >= 10)"
        )
    if fl.get("prom_replica_series", 0) < 5:
        failures.append(
            f"only {fl.get('prom_replica_series')} dts_tpu_fleet_* "
            "series in the replica's REST exposition (expected >= 5)"
        )

    if failures:
        print("check_fleet_smoke: FAIL", file=sys.stderr)
        for f_ in failures:
            print(f"  - {f_}", file=sys.stderr)
        sys.exit(1)
    print(
        "check_fleet_smoke: OK "
        f"(requests={fl.get('requests')} errors=0 "
        f"min_window_ratio={ratio} rejoin={rejoin_s}s "
        f"rollback_propagation={prop}s "
        f"fleet_series={fl.get('prom_router_series')}+"
        f"{fl.get('prom_replica_series')})"
    )


if __name__ == "__main__":
    main()
