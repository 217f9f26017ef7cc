#!/usr/bin/env python
"""CI gate for the data-integrity smoke (ISSUE 20).

Usage: python tools/check_integrity_smoke.py SOAK_LINE_JSON

Reads the JSON line a SOAK_INTEGRITY=1 soak printed (tools/ci_tier1.sh
tees it to a file) and asserts the acceptance criteria end to end:

- the VERIFYING CLIENT received zero corrupted scores: every injected
  response-side wire flip was caught by the score-CRC verify before
  merge (corrupt_responses >= 1 proves the detector fired), no NaN row
  was ever merged into a ranking (nan_scores_merged == 0), and every
  client-visible error in the classification is an integrity
  rejection/retry — never silently-wrong data;
- each DETECTION LAYER fired on its own fault site: the server rejected
  request-side wire corruption (wire.inputs_rejected >= 1) while clean
  requests kept verifying (inputs_verified >= 1, responses_stamped
  >= 1); the readback screen caught injected NaN rows (screen.trips
  >= 1); shadow verification caught injected bitflips bit-identically
  (shadow.batches >= 1, mismatches >= 1);
- detections ESCALATED into the recovery plane (escalations >= 1,
  quarantines >= 1, cycles completed) and detection->next-success MTTR
  is recorded and bounded;
- CLEAN traffic is bit-identical with the plane armed (forced shadow
  audit included), both before chaos and after it cleared — the plane
  never changes answers;
- the live surfaces answered: /integrityz enabled, POST
  /integrityz/audit accepted, the /monitoring?section=integrity filter
  served exactly one block, and dts_tpu_integrity_* Prometheus series
  were present.

Exits 0 on success; prints every failure and exits 1.
"""

import json
import sys

MTTR_BOUND_S = 60.0

# Every client-visible error under integrity chaos must be an integrity
# rejection or the retry/unavailability it causes. Anything else is an
# unexplained failure the gate refuses.
ALLOWED_ERROR_MARKERS = (
    "corrupt",        # corrupt-wire rejects + client-side corrupt response
    "UNAVAILABLE",    # screen-failed rows / quarantine window retries
    "unavailable",
    "readback",       # IntegrityScreenError detail
    "screen",
    "shard",          # failover exhaustion wrapper
)


def main() -> None:
    if len(sys.argv) != 2:
        print(
            "usage: check_integrity_smoke.py SOAK_LINE_JSON",
            file=sys.stderr,
        )
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
                if isinstance(parsed, dict) and "integrity" in parsed:
                    line = parsed
                    break
    except OSError as e:
        print(
            f"check_integrity_smoke: FAIL: cannot read {path}: {e}",
            file=sys.stderr,
        )
        sys.exit(1)
    if line is None or not isinstance(line.get("integrity"), dict):
        print(
            f"check_integrity_smoke: FAIL: no JSON line with an "
            f"`integrity` block in {path}", file=sys.stderr,
        )
        sys.exit(1)

    integ = line["integrity"]
    wire = integ.get("wire") or {}
    screen = integ.get("screen") or {}
    shadow = integ.get("shadow") or {}
    client = integ.get("client") or {}
    rc = integ.get("recovery_counters") or {}
    failures = []
    if integ.get("error"):
        failures.append(f"probe error: {integ['error']}")

    # --- zero corrupted scores delivered -----------------------------
    if client.get("nan_scores_merged", 0) != 0:
        failures.append(
            f"client merged {client.get('nan_scores_merged')} NaN "
            "score(s) into a ranking — corrupt data was DELIVERED"
        )
    if client.get("corrupt_responses", 0) < 1:
        failures.append(
            "client verify never caught a response-side wire flip "
            "(corrupt_responses=0) — the detector did not fire"
        )
    kinds = line.get("error_kinds") or {}
    unexplained = {
        k: v for k, v in kinds.items()
        if not any(m in k for m in ALLOWED_ERROR_MARKERS)
    }
    if unexplained:
        failures.append(
            f"unexplained client-visible errors (not integrity "
            f"rejections/retries): {unexplained}"
        )

    # --- layer 1: wire checksums -------------------------------------
    if wire.get("inputs_rejected", 0) < 1:
        failures.append(
            "server never rejected a request-side wire flip "
            f"(inputs_rejected={wire.get('inputs_rejected')})"
        )
    if wire.get("inputs_verified", 0) < 1:
        failures.append(
            "no clean request ever verified — the wire layer was idle"
        )
    if wire.get("responses_stamped", 0) < 1:
        failures.append("no response score CRC was ever stamped")

    # --- layer 2: readback screen ------------------------------------
    if screen.get("trips", 0) < 1:
        failures.append(
            "the readback screen never caught an injected NaN row "
            f"(trips={screen.get('trips')})"
        )

    # --- layer 3: shadow verification --------------------------------
    if shadow.get("batches", 0) < 1:
        failures.append("no batch ever shadow-verified")
    if shadow.get("mismatches", 0) < 1:
        failures.append(
            "shadow verification never caught an injected bitflip "
            f"(mismatches={shadow.get('mismatches')})"
        )
    if shadow.get("audits_run", 0) < 1:
        failures.append("no on-demand audit ever ran")

    # --- escalation into recovery + MTTR -----------------------------
    if integ.get("escalations", 0) < 1:
        failures.append("no detection ever escalated")
    if rc.get("quarantines", 0) < 1:
        failures.append(
            "escalation never reached the recovery plane "
            f"(quarantines={rc.get('quarantines')})"
        )
    if rc.get("cycles_completed", 0) < 1:
        failures.append("no recovery cycle ever completed")
    mttr = integ.get("detect_to_success_s")
    if mttr is None or mttr < 0 or mttr > MTTR_BOUND_S:
        failures.append(
            f"detection->success MTTR missing or out of bounds: {mttr}s"
        )

    # --- clean-traffic bit-identity ----------------------------------
    if integ.get("clean_bit_identical") is not True:
        failures.append(
            "pre-chaos clean traffic was NOT bit-identical plane-on vs "
            "plane-off"
        )
    if integ.get("clean_bit_identical_post") is not True:
        failures.append(
            "post-chaos clean traffic was NOT bit-identical to the "
            f"pre-chaos reference "
            f"({integ.get('closing_probe_error', 'mismatch')})"
        )

    # --- live surfaces -----------------------------------------------
    if not integ.get("integrityz_enabled"):
        failures.append("/integrityz did not answer enabled=true")
    if not integ.get("audit_post_ok"):
        failures.append("POST /integrityz/audit did not accept")
    if not integ.get("section_filter_ok"):
        failures.append("/monitoring?section=integrity filter failed")
    if integ.get("prom_integrity_series", 0) < 10:
        failures.append(
            f"only {integ.get('prom_integrity_series')} "
            "dts_tpu_integrity_* Prometheus series present "
            "(expected >= 10)"
        )

    if failures:
        print("check_integrity_smoke: FAIL", file=sys.stderr)
        for f_ in failures:
            print(f"  - {f_}", file=sys.stderr)
        sys.exit(1)
    print(
        "check_integrity_smoke: OK "
        f"(wire_rejected={wire.get('inputs_rejected')} "
        f"corrupt_responses={client.get('corrupt_responses')} "
        f"screen_trips={screen.get('trips')} "
        f"shadow_mismatches={shadow.get('mismatches')} "
        f"escalations={integ.get('escalations')} "
        f"mttr={mttr}s nan_merged=0 bit_identical=both)"
    )


if __name__ == "__main__":
    main()
