#!/usr/bin/env python
"""CI gate for the continuous-freshness lifecycle smoke (ISSUE 8).

Usage: python tools/check_lifecycle_smoke.py SOAK_LINE_JSON

Reads the JSON line a SOAK_LIFECYCLE=1 soak printed (tools/ci_tier1.sh
tees it to a file) and asserts the acceptance criteria end to end:

- a GOOD canary was published through the real fine-tune publisher and
  AUTO-PROMOTED (promotes >= 1; the live /lifecyclez stable version is
  the published good version and the state settled back to idle);
- a POISONED canary was published and AUTO-ROLLED-BACK (rollbacks >= 1,
  rollback reason recorded with its pair-PSI evidence at/above the
  configured threshold);
- the watcher RETIRED + BLACKLISTED the bad version, and the blacklist
  held across subsequent reconcile passes while the bad directory still
  sat ready on disk (blacklist_survived_reconcile, bad version absent
  from the final loaded set, present in the live blacklist);
- real PAIRED traffic flowed: the canary router sent requests to both
  the canary (probe lane + ramped default share) and the stable version;
- ZERO failed requests attributable to either swap: the whole soak's
  gRPC error count is zero;
- the live surfaces answered: /lifecyclez enabled, the
  /monitoring?section=lifecycle filter served exactly one block, and
  dts_tpu_lifecycle_* Prometheus series were present.

Exits 0 on success; prints every failure and exits 1.
"""

import json
import sys


def main() -> None:
    if len(sys.argv) != 2:
        print("usage: check_lifecycle_smoke.py SOAK_LINE_JSON", file=sys.stderr)
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
                if isinstance(parsed, dict) and "lifecycle" in parsed:
                    line = parsed
                    break
    except OSError as e:
        print(
            f"check_lifecycle_smoke: FAIL: cannot read {path}: {e}",
            file=sys.stderr,
        )
        sys.exit(1)
    if line is None or not isinstance(line.get("lifecycle"), dict):
        print(
            f"check_lifecycle_smoke: FAIL: no JSON line with a `lifecycle` "
            f"block in {path}", file=sys.stderr,
        )
        sys.exit(1)

    lc = line["lifecycle"]
    counters = lc.get("counters") or {}
    failures = []
    if lc.get("error"):
        failures.append(f"probe error: {lc['error']}")
    good = (lc.get("published_good") or {}).get("version")
    bad = (lc.get("published_poisoned") or {}).get("version")
    if good is None:
        failures.append("good canary was never published")
    if bad is None:
        failures.append("poisoned canary was never published")
    if counters.get("promotes", 0) < 1:
        failures.append(
            f"good canary was not auto-promoted (promotes="
            f"{counters.get('promotes')}, waited "
            f"{lc.get('promote_wait_s')}s)"
        )
    elif lc.get("stable_version") != good:
        failures.append(
            f"promoted stable version {lc.get('stable_version')} != the "
            f"published good canary {good}"
        )
    if counters.get("rollbacks", 0) < 1:
        failures.append(
            f"poisoned canary was not auto-rolled-back (rollbacks="
            f"{counters.get('rollbacks')}, waited "
            f"{lc.get('rollback_wait_s')}s)"
        )
    else:
        rb = lc.get("last_rollback") or {}
        if rb.get("version") != bad:
            failures.append(
                f"rollback hit version {rb.get('version')}, expected the "
                f"poisoned canary {bad}"
            )
        if not rb.get("reason"):
            failures.append("rollback carries no recorded reason/evidence")
    if bad is not None:
        if bad in (lc.get("post_rollback_versions") or []):
            failures.append(
                f"poisoned version {bad} still loaded after rollback "
                f"(loaded={lc.get('post_rollback_versions')})"
            )
        if bad not in (lc.get("blacklisted") or []):
            failures.append(
                f"poisoned version {bad} missing from the live blacklist "
                f"({lc.get('blacklisted')})"
            )
    if not lc.get("blacklist_survived_reconcile"):
        failures.append(
            "blacklist did not survive the watcher's reconcile passes — "
            "the rolled-back version was reloaded from disk"
        )
    if counters.get("routed_canary", 0) <= 0:
        failures.append("no traffic was ever routed to a canary")
    if counters.get("routed_stable", 0) <= 0:
        failures.append(
            "no default-lane traffic stayed on stable during canary "
            "(the paired comparison had nothing to compare)"
        )
    grpc_err = line.get("grpc_err", -1)
    if grpc_err != 0:
        failures.append(
            f"swaps must not fail traffic: grpc_err={grpc_err} "
            f"(kinds={line.get('error_kinds')})"
        )
    if not lc.get("lifecyclez_enabled"):
        failures.append("live /lifecyclez did not answer enabled=true")
    if not lc.get("section_filter_ok"):
        failures.append(
            "GET /monitoring?section=lifecycle did not answer exactly the "
            "lifecycle block"
        )
    if lc.get("prom_lifecycle_series", 0) <= 0:
        failures.append("no dts_tpu_lifecycle_* Prometheus series served")

    if failures:
        for f_ in failures:
            print(f"check_lifecycle_smoke: FAIL: {f_}", file=sys.stderr)
        sys.exit(1)
    print(
        "check_lifecycle_smoke: OK: "
        f"promoted v{good} in {lc.get('promote_wait_s')}s, rolled back "
        f"v{bad} in {lc.get('rollback_wait_s')}s "
        f"(psi={((lc.get('last_rollback') or {}).get('pair') or {}).get('psi')}), "
        f"routed canary={counters.get('routed_canary')} "
        f"stable={counters.get('routed_stable')} "
        f"probe={counters.get('routed_probe')}, "
        f"blacklist held, {line.get('grpc_ok')} requests 0 errors, "
        f"prom_series={lc.get('prom_lifecycle_series')}"
    )


if __name__ == "__main__":
    main()
