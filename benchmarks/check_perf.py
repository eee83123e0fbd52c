"""CI gate: fail on a dispatch-layer perf regression vs the committed
baseline (e.g. ``benchmarks/BENCH_runtime.json``).

Absolute rounds/s across heterogeneous CI hosts is pure noise — a GitHub
runner and the laptop that wrote the baseline differ by far more than any
real regression.  What IS machine-portable is each row's throughput
normalised by the SAME payload's eager row (the ``runtime_dispatch_ab``
kind): that ratio isolates the dispatch/metric-transport layer (launch
amortisation, readback barriers, tap overhead) from raw core speed, which
is exactly what these benches exist to track.  The gate fails when any
subject row's normalised throughput (or the grid lane's
``grid_speedup``) drops more than ``--tolerance`` (default 30%) below the
baseline's.  The ``faults`` and ``obs`` kinds instead gate an ABSOLUTE
same-machine ratio (guarded/unguarded, traced/untraced) against a
documented ceiling — see their checkers.

Any other payload kind (e.g. the ``scenarios`` smoke bench, or a future
kind this script predates) is SKIPPED loudly with exit 0 — an
artifact-only bench must never fail CI just because the gate doesn't know
how to read it.  A missing file skips the same way (benches run under
``if: always()``, so an earlier failed step may legitimately leave no
payload behind).

Usage::

    python benchmarks/check_perf.py experiments/figs/BENCH_runtime.json \
        benchmarks/BENCH_runtime.json --tolerance 0.3
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _rows(payload: dict, path: str = "<payload>") -> tuple[dict, float]:
    """(runtime, metrics, K) -> entry, plus the eager rounds/s."""
    eager = [e for e in payload["entries"] if e["runtime"] == "eager"]
    if not eager:
        raise SystemExit(
            f"bench file {path!r} has no eager row to normalise against")
    rows = {(e["runtime"], e.get("metrics", "chunk"),
             e["rounds_per_launch"]): e
            for e in payload["entries"]}
    return rows, float(eager[0]["rounds_per_s"])


def check_runtime(current: dict, baseline: dict, tolerance: float,
                  paths=("<current>", "<baseline>")) -> list:
    cur_rows, cur_eager = _rows(current, paths[0])
    base_rows, base_eager = _rows(baseline, paths[1])
    failures = []
    print(f"{'row':<28} {'base':>8} {'now':>8} {'floor':>8}  verdict")
    for key, base in sorted(base_rows.items(), key=str):
        if key[0] == "eager":
            continue                      # the normaliser, not a subject
        cur = cur_rows.get(key)
        if cur is None:
            failures.append(
                f"{key}: present in baseline {paths[1]!r} but missing "
                f"from current payload {paths[0]!r}")
            print(f"{str(key):<28} {'':>8} {'':>8} {'':>8}  MISSING")
            continue
        base_n = float(base["rounds_per_s"]) / base_eager
        cur_n = float(cur["rounds_per_s"]) / cur_eager
        floor = base_n * (1.0 - tolerance)
        ok = cur_n >= floor
        print(f"{str(key):<28} {base_n:>8.3f} {cur_n:>8.3f} "
              f"{floor:>8.3f}  {'ok' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"{key}: normalised rounds/s {cur_n:.3f} < floor "
                f"{floor:.3f} (baseline {base_n:.3f}, "
                f"tolerance {tolerance:.0%})")
        if "grid_speedup" in base:
            if "grid_speedup" not in cur:
                # a vanished field is a bench-shape change, not a 0.000
                # throughput — report it as such instead of a bogus floor
                # comparison
                failures.append(
                    f"{key}: baseline has grid_speedup but the current "
                    "row lacks the field")
                print(f"{'  grid_speedup':<28} "
                      f"{float(base['grid_speedup']):>8.3f} {'':>8} "
                      f"{'':>8}  MISSING")
                continue
            g_base = float(base["grid_speedup"])
            g_cur = float(cur["grid_speedup"])
            g_floor = g_base * (1.0 - tolerance)
            g_ok = g_cur >= g_floor
            print(f"{'  grid_speedup':<28} {g_base:>8.3f} {g_cur:>8.3f} "
                  f"{g_floor:>8.3f}  {'ok' if g_ok else 'REGRESSION'}")
            if not g_ok:
                failures.append(
                    f"{key}: grid_speedup {g_cur:.3f} < floor "
                    f"{g_floor:.3f}")
    return failures


def check_faults(current: dict, baseline: dict, tolerance: float,
                 paths=("<current>", "<baseline>")) -> list:
    """Fault-injection gate: the ceiling is ABSOLUTE, not baseline-relative.

    The payload's ``guard_overhead_ratio`` (guarded / unguarded rounds/s
    on the same plan, state and machine) is already machine-portable, and
    the guard's documented contract is a ≤10% overhead ceiling — so CI
    passes ``--tolerance 0.1`` and the gate fails when the CURRENT ratio
    drops below ``1 − tolerance``, regardless of what the committed
    baseline measured.  The two smoke flags are gated the same way: the
    unguarded run must actually end poisoned (else the fault channel went
    dead and the overhead number is meaningless) and the guarded run must
    end finite with every poisoned round skipped."""
    failures = []
    if "guard_overhead_ratio" not in current:
        # keep checking the remaining rows — a missing field must not
        # hide whatever ELSE regressed in the same payload
        failures.append(
            f"current bench file {paths[0]!r} has kind 'faults' but no "
            "guard_overhead_ratio field — the bench payload shape changed "
            "under the gate")
        print(f"{'guard_overhead_ratio':<28} {'':>8} {'':>8} {'':>8}  "
              "MISSING")
    else:
        ratio = float(current["guard_overhead_ratio"])
        floor = 1.0 - tolerance
        base_ratio = float(baseline.get("guard_overhead_ratio", 0.0))
        print(f"{'guard_overhead_ratio':<28} {base_ratio:>8.3f} "
              f"{ratio:>8.3f} {floor:>8.3f}  "
              f"{'ok' if ratio >= floor else 'REGRESSION'}")
        if ratio < floor:
            failures.append(
                f"guard_overhead_ratio {ratio:.3f} < floor {floor:.3f} — "
                f"the guard costs more than {tolerance:.0%} of unguarded "
                "scan throughput")
    for flag, why in (
            ("unguarded_poisoned",
             "the injected faults no longer poison an unguarded run — the "
             "fault channel is dead end-to-end"),
            ("guarded_final_finite",
             "the guard let non-finite values reach the final params")):
        ok = bool(current.get(flag, False))
        print(f"{flag:<28} {'':>8} {str(ok):>8} {'True':>8}  "
              f"{'ok' if ok else 'FAILED'}")
        if not ok:
            failures.append(f"{flag} is False: {why}")
    skipped = int(current.get("guarded_skipped_rounds", -1))
    poisoned = int(current.get("poisoned_rounds", -2))
    ok = skipped == poisoned and poisoned > 0
    print(f"{'skipped == poisoned':<28} {'':>8} {skipped:>8} {poisoned:>8}  "
          f"{'ok' if ok else 'FAILED'}")
    if not ok:
        failures.append(
            f"guarded run skipped {skipped} rounds but the plan poisons "
            f"{poisoned} participating rounds — the guard is skipping the "
            "wrong rounds (or the world realised no faults)")
    return failures


def check_obs(current: dict, baseline: dict, tolerance: float,
              paths=("<current>", "<baseline>")) -> list:
    """Observability-overhead gate: the ceiling is ABSOLUTE, like the
    faults gate.  The payload's ``overhead_ratio`` (traced / untraced
    rounds/s on the same plan, state and machine — the tap transport with
    a live Recorder attached vs without one) is machine-portable, and the
    tracing contract is a ≤5% ceiling — CI passes ``--tolerance 0.05``
    and the gate fails when the CURRENT ratio drops below
    ``1 − tolerance`` regardless of the committed baseline.  The
    structural flags are gated too: the emitted Chrome trace and JSONL
    metrics log must have validated, and the traced run must have
    streamed exactly one tap event per round (tracing must observe the
    transport, not perturb it)."""
    failures = []
    if "overhead_ratio" not in current:
        # as in check_faults: record and continue so secondary failures
        # in the same payload still surface
        failures.append(
            f"current bench file {paths[0]!r} has kind 'obs' but no "
            "overhead_ratio field — the bench payload shape changed "
            "under the gate")
        print(f"{'overhead_ratio':<28} {'':>8} {'':>8} {'':>8}  MISSING")
    else:
        ratio = float(current["overhead_ratio"])
        floor = 1.0 - tolerance
        base_ratio = float(baseline.get("overhead_ratio", 0.0))
        print(f"{'overhead_ratio':<28} {base_ratio:>8.3f} {ratio:>8.3f} "
              f"{floor:>8.3f}  {'ok' if ratio >= floor else 'REGRESSION'}")
        if ratio < floor:
            failures.append(
                f"overhead_ratio {ratio:.3f} < floor {floor:.3f} — "
                f"tracing costs more than {tolerance:.0%} of untraced tap "
                f"throughput (current file {paths[0]!r})")
    for flag, why in (
            ("trace_valid",
             "the emitted trace.json is not valid Chrome trace-event "
             "JSON — Perfetto would reject it"),
            ("metrics_valid",
             "the emitted JSONL metrics log failed schema validation"),
            ("tap_events_match",
             "the traced run's tap_events != rounds — tracing perturbed "
             "the tap transport it was supposed to observe")):
        ok = bool(current.get(flag, False))
        print(f"{flag:<28} {'':>8} {str(ok):>8} {'True':>8}  "
              f"{'ok' if ok else 'FAILED'}")
        if not ok:
            failures.append(f"{flag} is False: {why}")
    return failures


def check_resilience(current: dict, baseline: dict, tolerance: float,
                     paths=("<current>", "<baseline>")) -> list:
    """Serving-resilience gate: absolute ceiling, like faults/obs.

    ``retry_overhead_ratio`` is (retry-machinery-armed clean serve tok/s)
    / (plain clean serve tok/s) on the same machine — the documented
    contract is that arming retries on a clean world costs ≤10%, so CI
    passes ``--tolerance 0.1`` and the gate fails below ``1 − tolerance``
    regardless of the committed baseline.  The flags pin the two
    correctness halves: the armed clean run must be TOKEN-IDENTICAL to
    the plain one (retry machinery is a no-op until a failure happens)
    and the chaos run must account every request (completed or in a
    degraded bucket — no silent loss)."""
    failures = []
    if "retry_overhead_ratio" not in current:
        failures.append(
            f"current bench file {paths[0]!r} has kind 'resilience' but "
            "no retry_overhead_ratio field — the bench payload shape "
            "changed under the gate")
        print(f"{'retry_overhead_ratio':<28} {'':>8} {'':>8} {'':>8}  "
              "MISSING")
    else:
        ratio = float(current["retry_overhead_ratio"])
        floor = 1.0 - tolerance
        base_ratio = float(baseline.get("retry_overhead_ratio", 0.0))
        print(f"{'retry_overhead_ratio':<28} {base_ratio:>8.3f} "
              f"{ratio:>8.3f} {floor:>8.3f}  "
              f"{'ok' if ratio >= floor else 'REGRESSION'}")
        if ratio < floor:
            failures.append(
                f"retry_overhead_ratio {ratio:.3f} < floor {floor:.3f} — "
                f"arming retries costs more than {tolerance:.0%} of clean "
                "slot-serve throughput")
    for flag, why in (
            ("clean_token_identical",
             "a clean serve with retries armed emitted different tokens "
             "than the plain serve — the retry machinery is not a no-op "
             "on the clean path"),
            ("all_accounted",
             "the chaos run lost requests: some rid is neither completed "
             "nor in evictions/timeouts/shed/drained — silent loss")):
        ok = bool(current.get(flag, False))
        print(f"{flag:<28} {'':>8} {str(ok):>8} {'True':>8}  "
              f"{'ok' if ok else 'FAILED'}")
        if not ok:
            failures.append(f"{flag} is False: {why}")
    return failures


#: bench kinds this gate knows how to compare (payload "bench" field)
CHECKERS = {
    "runtime_dispatch_ab": check_runtime,
    "faults": check_faults,
    "obs": check_obs,
    "resilience": check_resilience,
}
KNOWN_KINDS = set(CHECKERS)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("current", help="freshly produced bench JSON")
    ap.add_argument("baseline", help="committed baseline JSON")
    ap.add_argument("--tolerance", type=float, default=0.3,
                    help="allowed fractional drop in normalised throughput "
                         "(default 0.3 = 30%%)")
    args = ap.parse_args()
    payloads = {}
    for label, path in (("current", args.current),
                        ("baseline", args.baseline)):
        if not os.path.exists(path):
            print(f"SKIP: {label} bench file {path!r} does not exist — "
                  "nothing to gate (not a failure: benches run under "
                  "if: always(), so an earlier failed step may have left "
                  "no payload)")
            return
        with open(path) as f:
            payloads[label] = json.load(f)
    kinds = {label: payload.get("bench", "<missing>")
             for label, payload in payloads.items()}
    for label, kind in kinds.items():
        if kind not in KNOWN_KINDS:
            print(f"SKIP: {label} bench file {getattr(args, label)!r} has "
                  f"kind {kind!r}, which this gate cannot compare (known: "
                  f"{sorted(KNOWN_KINDS)}) — treating as artifact-only, "
                  "not a failure")
            return
    if kinds["current"] != kinds["baseline"]:
        raise SystemExit(
            f"bench kind mismatch: current file {args.current!r} is "
            f"{kinds['current']!r} but baseline file {args.baseline!r} is "
            f"{kinds['baseline']!r} — not comparable")
    failures = CHECKERS[kinds["current"]](
        payloads["current"], payloads["baseline"], args.tolerance,
        paths=(args.current, args.baseline))
    if failures:
        print("\nPERF REGRESSION vs committed baseline:")
        for msg in failures:
            print(" -", msg)
        sys.exit(1)
    print("\nno dispatch-layer regression "
          f"(tolerance {args.tolerance:.0%})")


if __name__ == "__main__":
    main()
