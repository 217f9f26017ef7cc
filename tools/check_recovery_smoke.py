#!/usr/bin/env python
"""CI gate for the device-failure recovery smoke (ISSUE 11).

Usage: python tools/check_recovery_smoke.py SOAK_LINE_JSON

Reads the JSON line a SOAK_RECOVERY=1 soak printed (tools/ci_tier1.sh
tees it to a file) and asserts the acceptance criteria end to end:

- a deterministic WEDGE injected at pipeline depth 4 QUARANTINED the
  replica (watchdog_wedge_trips >= 1, quarantines >= 1) and the cycle
  completed back to `serving`;
- REINIT + REPLAY answered every captured in-flight/queued request:
  replayed_items >= 1, replay_budget_exhausted == 0, and the soak's
  whole gRPC error count is ZERO (clients rode their retry horizon
  through the quarantine window — non-poison requests never fail);
- MTTR (fault injection -> first post-recovery success) is recorded and
  bounded;
- the deliberately POISONED request was isolated by BISECTION: it alone
  failed with the distinct PoisonedInputError status while both clean
  companions coalesced into its batch replayed to success
  (poisoned_requests >= 1, bisections >= 1);
- the live surfaces answered: /recoveryz enabled, the
  /monitoring?section=recovery filter served exactly one block, and
  dts_tpu_recovery_* Prometheus series were present.

Exits 0 on success; prints every failure and exits 1.
"""

import json
import sys

MTTR_BOUND_S = 60.0


def main() -> None:
    if len(sys.argv) != 2:
        print("usage: check_recovery_smoke.py SOAK_LINE_JSON", file=sys.stderr)
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
                if isinstance(parsed, dict) and "recovery" in parsed:
                    line = parsed
                    break
    except OSError as e:
        print(
            f"check_recovery_smoke: FAIL: cannot read {path}: {e}",
            file=sys.stderr,
        )
        sys.exit(1)
    if line is None or not isinstance(line.get("recovery"), dict):
        print(
            f"check_recovery_smoke: FAIL: no JSON line with a `recovery` "
            f"block in {path}", file=sys.stderr,
        )
        sys.exit(1)

    rec = line["recovery"]
    counters = rec.get("counters") or {}
    failures = []
    if rec.get("error"):
        failures.append(f"probe error: {rec['error']}")
    if not rec.get("wedge_injected"):
        failures.append("the wedge was never injected")
    if counters.get("watchdog_wedge_trips", 0) < 1:
        failures.append(
            "the watchdog never escalated the wedge clock into a "
            f"quarantine (trips={counters.get('watchdog_wedge_trips')})"
        )
    if counters.get("quarantines", 0) < 1:
        failures.append(f"no quarantine ran ({counters.get('quarantines')})")
    if counters.get("cycles_completed", 0) < 1:
        failures.append("no recovery cycle ever completed")
    if counters.get("replayed_items", 0) < 1:
        failures.append(
            "nothing was replayed — the captured pipeline was lost"
        )
    if counters.get("replay_budget_exhausted", 0) != 0:
        failures.append(
            "replay budget exhausted for "
            f"{counters.get('replay_budget_exhausted')} item(s) — "
            "captured work FAILED instead of replaying"
        )
    mttr = rec.get("mttr_s")
    if mttr is None or mttr <= 0 or mttr > MTTR_BOUND_S:
        failures.append(f"MTTR missing or out of bounds: {mttr}s")
    if rec.get("final_state") != "serving":
        failures.append(
            f"replica did not settle back to serving "
            f"(state={rec.get('final_state')})"
        )
    # Zero failed non-poison requests: the poison is submitted DIRECTLY
    # to the batcher, so every client-visible gRPC error is a non-poison
    # failure by construction.
    if line.get("grpc_err", 0) != 0:
        failures.append(
            f"{line.get('grpc_err')} client-visible request failure(s) — "
            f"kinds: {line.get('error_kinds')}"
        )
    poison = rec.get("poison") or {}
    if not poison.get("poisoned"):
        failures.append(
            "the poisoned request did not fail with PoisonedInputError "
            f"(got: {poison.get('poison_error', '<nothing recorded>')})"
        )
    if poison.get("companions_ok", 0) != 2:
        failures.append(
            f"only {poison.get('companions_ok')}/2 clean companions "
            f"scored (errors: {poison.get('companion_errors')})"
        )
    if counters.get("poisoned_requests", 0) < 1:
        failures.append("controller recorded no poisoned request")
    if counters.get("bisections", 0) < 1:
        failures.append(
            "no bisection ran — the poison was never isolated out of a "
            "multi-request batch"
        )
    if not rec.get("recoveryz_enabled"):
        failures.append("/recoveryz did not answer enabled=true")
    if not rec.get("section_filter_ok"):
        failures.append("/monitoring?section=recovery filter failed")
    if rec.get("prom_recovery_series", 0) < 10:
        failures.append(
            f"only {rec.get('prom_recovery_series')} dts_tpu_recovery_* "
            "Prometheus series present (expected >= 10)"
        )

    if failures:
        print("check_recovery_smoke: FAIL", file=sys.stderr)
        for f_ in failures:
            print(f"  - {f_}", file=sys.stderr)
        sys.exit(1)
    print(
        "check_recovery_smoke: OK "
        f"(mttr={mttr}s quarantines={counters.get('quarantines')} "
        f"replayed={counters.get('replayed_items')} "
        f"bisections={counters.get('bisections')} "
        f"poisoned={counters.get('poisoned_requests')} "
        f"grpc_err=0)"
    )


if __name__ == "__main__":
    main()
