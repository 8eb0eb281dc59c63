#!/usr/bin/env python
"""Counter regression gate: assert the SLO-like mesh counters recorded in
BENCH_EXTRA.json (by `bench.py --mesh`) still hold their invariants.

The mesh fast path's correctness-performance contract is a set of counters
that must be ZERO on warm executions — a drift means a regression that walls
alone may hide (a retrace can cost little on tiny data and 30x on SF10):

  * `profile.trace_cache.retraces == 0` — warm runs reuse every compiled
    SPMD program (PR 1's contract);
  * `profile.counters.host_restack == 0` — no host batch re-enters the mesh
    between distributed fragments (the device-resident pipeline);
  * `q3_counters.repartition_collective == 0` — under co-partitioned
    layouts the probe repartition is elided (PR 3);
  * `q3_counters.join_capacity_sync == 0` and
    `q3_counters.join_speculative_retry == 0` — the warm speculative join
    neither blocks on capacities nor retries its expand;
  * `membership.*` (tools/membership_bench.py): every attempt of the
    shrink->grow round trip matches local, the shrink re-planned, the grow
    restored W, and the post-round-trip warm repeat re-plans and retraces
    NOTHING (PR 7 — membership churn must not dirty the warm path);
  * `drift.*` (tools/drift_bench.py): the recorded Q3 drift attribution
    names a dominant (phase, fragment), its phase decomposition sums to
    the measured wall, and the warm-Q6 null-diff self check passes (two
    warm archives of one statement must profile_diff to ~zero);
  * `licenses.*` (PR 15, check_licenses): proof-licensed joins ran ZERO
    runtime sizing over the Q3 phase — `join_capacity.runtime_check == 0`
    cold and warm, `proven > 0`, the schedule license pre-dispatched at
    least one build fragment (`collective_async > 0`), and the deleted
    `gather/capacity_sizing` collective stayed deleted;
  * `dictionary.*` (PR 18, check_dictionary): the varchar-keyed join under
    a global-dictionary layout co-located (`exchange_elided > 0`, ZERO
    repartition collectives), its unique business key licensed the
    capacity, and rows matched the local oracle;
  * `decisions.*` (check_decisions): every benched statement archives a
    COMPLETE plan-decision ledger — each all_to_all/all_gather byte maps
    to exactly one recorded decision, the unattributed bucket is empty —
    and the warm benched set carries zero `regret` hindsight verdicts
    (telemetry/decisions).

Modes:
  python tools/compare_bench.py                 # gate the checked-in file
  python tools/compare_bench.py --extra F.json  # gate another file
  python tools/compare_bench.py --snapshot S.json
      # additionally diff a FRESH registry snapshot (the `metrics` section a
      # new `bench.py --mesh` run records) against the same expectations —
      # the zero-counters above must be zero in the fresh snapshot's
      # mesh-events series too.

Exit status: 0 when every invariant holds, 1 on drift (the CI gate next to
lint_tpu.py).  Sections that recorded an error are reported as skipped, not
failed — a bench that could not run is not a counter regression.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

#: profile-level expectations: (path within a mesh schema section, expected)
PROFILE_ZERO = (
    ("profile", "trace_cache", "retraces"),
)

#: MeshProfile counters that must be absent-or-zero on the recorded profile
PROFILE_COUNTER_ZERO = ("host_restack",)

#: q3 (layouts) counters that must be zero warm.  `join_overflow_check`
#: joined the list with proof-licensed execution (verify/capacity.py): a
#: capacity-certified join compiles at its certified fixed capacity, so the
#: warm profile must record NO overflow-flag reads at all
Q3_ZERO = (
    "repartition_collective",
    "join_capacity_sync",
    "join_speculative_retry",
    "join_overflow_check",
)


def check_licenses(schema: str, sec: dict) -> list:
    """Violations over one mesh section's proof-licensed execution
    evidence (`licenses`, recorded by bench.py around the Q3 phase): the
    certified joins must NEVER have run the runtime sizing protocol —
    cold or warm (`join_capacity.runtime_check == 0`, path selection is
    per-expansion), at least one join must actually be proven
    (`proven > 0`), the schedule license must have pre-dispatched an
    independent build fragment (`collective_async > 0`), and the deleted
    sizing gather must stay deleted (zero `gather/capacity_sizing` bytes
    in the warm Q3 profile)."""
    lic = sec.get("licenses")
    if not isinstance(lic, dict):
        return []  # older section: no license evidence recorded yet
    violations = []
    jc = lic.get("join_capacity") or {}
    if jc.get("runtime_check", 1) != 0:
        violations.append(
            f"mesh.{schema}.licenses.join_capacity.runtime_check = "
            f"{jc.get('runtime_check')} (expected 0: certified joins must "
            "never fall back to the runtime sizing protocol over the Q3 "
            "phase — a fallback means a license was refused or unsealed)"
        )
    if jc.get("proven", 0) <= 0:
        violations.append(
            f"mesh.{schema}.licenses.join_capacity.proven = "
            f"{jc.get('proven')} (expected > 0: Q3's joins carry capacity "
            "certificates; zero proven expansions means the license pass "
            "attached nothing)"
        )
    if lic.get("collective_async", 0) <= 0:
        violations.append(
            f"mesh.{schema}.licenses.collective_async = "
            f"{lic.get('collective_async')} (expected > 0: the schedule "
            "license must have pre-dispatched at least one independent "
            "build fragment asynchronously)"
        )
    bytes_by = sec.get("q3_collective_bytes_by") or {}
    if bytes_by.get("gather/capacity_sizing"):
        violations.append(
            f"mesh.{schema}.q3_collective_bytes_by[gather/capacity_sizing]"
            f" = {bytes_by['gather/capacity_sizing']} (expected absent: "
            "the licensed joins' sizing round-trip is deleted, not merely "
            "cheap)"
        )
    # licensed-never-slower: bench.py bisects the SAME warm Q3 with
    # `join_capacity_license = false` and records the runtime path's warm
    # wall next to the licensed one.  A license is only worth holding when
    # it is at least as fast as the protocol it deletes — a licensed wall
    # beyond the runtime wall means the economy policy admitted a
    # too-wide certificate.  1.25x tolerance: warm best-of-n walls on a
    # shared box jitter; a real width blowup is multiples, not percent.
    lw, rw = lic.get("licensed_warm_s"), lic.get("runtime_warm_s")
    if (
        isinstance(lw, (int, float))
        and isinstance(rw, (int, float))
        and rw > 0
        and lw > rw * 1.25
    ):
        violations.append(
            f"mesh.{schema}.licenses licensed_warm_s = {lw} > 1.25x "
            f"runtime_warm_s = {rw} (the licensed path must never be "
            "slower than the runtime sizing path it replaces — the "
            "economy policy admitted a certificate whose certified width "
            "dwarfs the data; bisect with `set session "
            "join_capacity_license = false`)"
        )
    return violations

#: decimal fast-path contract over the Q1 bench phase (PR 10): path
#: selections are TRACE-time, so across cold+warm the licensed workload
#: must compile ZERO runtime fits probes and at least one proven kernel —
#: the `vs_baseline 0.80 -> 0.95+` evidence is structural, not just a wall
DECIMAL_FASTPATH_RULES = (
    ("runtime_check", "== 0", lambda v: v == 0),
    ("proven", "> 0", lambda v: v > 0),
)

#: coldstart (compile observatory) per-query keys that must be present when
#: a mesh section records a `coldstart` block — the cold/warm decomposition
#: is only evidence if the ratio, compile attribution, AND the
#: warm-replay-zero probe are all there (a dropped warm_replay_events key
#: would turn the "warm replays compile nothing" gate into a no-op)
COLDSTART_KEYS = (
    "cold_s", "warm_s", "cold_over_warm", "compile_s",
    "compile_events", "warm_replay_events",
)

#: restart phases (bench.py `coldstart.restart`): first-run wall of a FRESH
#: process — cold (empty XLA cache, and the phase that populates it),
#: persistent (same on-disk cache dir: re-traces but reloads executables),
#: prewarmed (cache + manifest replay at start: the query itself must
#: compile NOTHING)
RESTART_PHASES = ("cold", "persistent", "prewarmed")
RESTART_KEYS = ("wall_s", "compile_s", "compile_events", "query_events")


def check_dictionary(schema: str, sec: dict) -> list:
    """Violations over one mesh section's global-dictionary evidence
    (`dictionary`, recorded by bench.py around a varchar-keyed self-join
    under a c_name layout): the shared versioned code assignment must
    have co-located the join (elided exchanges, ZERO repartition
    collectives), the dictionary-backed unique key must have licensed its
    capacity, and the rows must equal the local oracle."""
    violations = []
    if sec.get("exchange_elided", 0) <= 0:
        violations.append(
            f"mesh.{schema}.dictionary.exchange_elided = "
            f"{sec.get('exchange_elided')} (expected > 0: the varchar-key "
            "layout must elide the co-located join's exchanges)"
        )
    if sec.get("repartition_collective", 0) != 0:
        violations.append(
            f"mesh.{schema}.dictionary.repartition_collective = "
            f"{sec.get('repartition_collective')} (expected 0: globally "
            "coded varchar keys co-locate like integers — a repartition "
            "means the dictionary claim was refused)"
        )
    if sec.get("join_capacity_proven", 0) <= 0:
        violations.append(
            f"mesh.{schema}.dictionary.join_capacity_proven = "
            f"{sec.get('join_capacity_proven')} (expected > 0: the "
            "dictionary-backed unique business key must license the "
            "join's capacity)"
        )
    if sec.get("matches_local") is False:
        violations.append(
            f"mesh.{schema}.dictionary.matches_local = False (the "
            "co-located varchar join changed rows vs the local oracle)"
        )
    return violations


#: exchange-plane collective kinds every benched byte must attribute to a
#: decision (telemetry/decisions EXCHANGE_KINDS; gathers are host pulls,
#: reduces are dynamic-filter summaries — neither is a placement choice)
DECISION_EXCHANGE_KINDS = ("all_to_all", "all_gather")


def check_decisions(schema: str, sec: dict) -> list:
    """Violations over one mesh section's plan-decision ledger evidence
    (`decisions`, recorded by bench.py from one extra warm run of each
    benched query): the ledger must be COMPLETE — every exchange-plane
    byte (all_to_all + all_gather) the profile recorded attributes to
    exactly one decision, the unattributed bucket is empty, at least one
    join-distribution choice and one capacity-economy verdict were
    recorded — and the warm benched set carries ZERO `regret` verdicts (a
    warm regret means the planner keeps re-making a choice the runtime
    has already measured as wrong)."""
    violations = []
    for qname, ev in sorted(sec.items()):
        if not isinstance(ev, dict):
            continue
        led = ev.get("ledger")
        if not isinstance(led, dict) or not led.get("decisions"):
            violations.append(
                f"mesh.{schema}.decisions.{qname}: no ledger recorded "
                "(expected every benched statement to archive a "
                "plan-decision ledger)"
            )
            continue
        if not led.get("finalized"):
            violations.append(
                f"mesh.{schema}.decisions.{qname}: ledger not finalized "
                "(hindsight verdicts never stamped)"
            )
        unatt = led.get("unattributed_bytes_by") or {}
        if unatt:
            violations.append(
                f"mesh.{schema}.decisions.{qname}: unattributed exchange "
                f"bytes {unatt} (every all_to_all/all_gather byte must "
                "map to exactly one decision)"
            )
        # completeness: per exchange kind, decision-attributed bytes ==
        # the profile's collective totals for that kind
        by_kind: dict = {k: 0 for k in DECISION_EXCHANGE_KINDS}
        kinds_seen = set()
        regrets = []
        for d in led["decisions"]:
            kinds_seen.add(d.get("kind"))
            if d.get("hindsight") == "regret":
                regrets.append(
                    f"{d.get('decision_id')} {d.get('kind')}/"
                    f"{d.get('choice')} at {d.get('site')}: "
                    f"{d.get('hindsight_detail')}"
                )
            for key, b in (d.get("bytes_by") or {}).items():
                kind = key.split("/", 1)[0]
                if kind in by_kind:
                    by_kind[kind] += int(b)
        profile_by = ev.get("collective_bytes_by") or {}
        for kind in DECISION_EXCHANGE_KINDS:
            total = sum(
                int(b) for key, b in profile_by.items()
                if key.split("/", 1)[0] == kind
            )
            if total != by_kind[kind]:
                violations.append(
                    f"mesh.{schema}.decisions.{qname}: {kind} bytes "
                    f"attributed to decisions = {by_kind[kind]} but the "
                    f"profile moved {total} (incomplete ledger: a "
                    "placement executed without recording its decision)"
                )
        if "join_distribution" not in kinds_seen:
            violations.append(
                f"mesh.{schema}.decisions.{qname}: no join_distribution "
                "decision recorded (benched queries join)"
            )
        if qname == "q3" and "join_capacity" not in kinds_seen:
            violations.append(
                f"mesh.{schema}.decisions.{qname}: no join_capacity "
                "decision recorded (the licensed/declined/runtime_check "
                "economy verdict must land in the ledger)"
            )
        for r in regrets:
            violations.append(
                f"mesh.{schema}.decisions.{qname}: warm regret — {r} "
                "(zero regrets expected on the warm benched set)"
            )
    return violations


def check_restart(schema: str, sec: dict) -> list:
    """Violations over one mesh section's coldstart.restart block: every
    phase recorded with its decomposition, and the prewarmed process's
    query ran without a single compile event above its prewarm watermark
    (the restart-resilience acceptance bar)."""
    violations = []
    if sec.get("error"):
        return violations  # reported as skipped by the caller
    for phase in RESTART_PHASES:
        p = sec.get(phase)
        if not isinstance(p, dict):
            violations.append(
                f"mesh.{schema}.coldstart.restart.{phase} missing "
                "(re-run bench.py --mesh)"
            )
            continue
        if p.get("error"):
            # a failed phase FAILS the gate: BENCH_EXTRA deep-merges, so
            # stale green numbers from a previous run sit right next to
            # the error — skipping here would gate on ghosts
            violations.append(
                f"mesh.{schema}.coldstart.restart.{phase} errored: "
                f"{p['error']} (stale sibling keys are not evidence)"
            )
            continue
        missing = [k for k in RESTART_KEYS if k not in p]
        if missing:
            violations.append(
                f"mesh.{schema}.coldstart.restart.{phase} missing {missing}"
            )
    pre = sec.get("prewarmed")
    if isinstance(pre, dict) and not pre.get("error"):
        if pre.get("query_events", 1) != 0:
            violations.append(
                f"mesh.{schema}.coldstart.restart.prewarmed.query_events = "
                f"{pre.get('query_events')} (expected 0: after the manifest "
                "replay the first real query must compile nothing)"
            )
        if pre.get("prewarm_state") not in (None, "WARM"):
            violations.append(
                f"mesh.{schema}.coldstart.restart.prewarmed.prewarm_state = "
                f"{pre.get('prewarm_state')} (expected WARM: the executor's "
                "verify replay found the key set unclosed or failed)"
            )
    return violations

#: pressure-section degradation counters that must be ZERO over the
#: unconstrained benched runs (graceful degradation must cost nothing when
#: there is no pressure — PR 12's zero-cost-when-idle bar)
PRESSURE_IDLE_ZEROS = (
    "memory_waves_total",
    "spill_bytes_total",
    "memory_revocations_total",
)


def check_pressure(schema: str, sec: dict) -> list:
    """Violations over one mesh section's `pressure` block (bench.py
    --mesh / tools/pressure_bench.py): Q18 under a pool limit smaller
    than its unconstrained peak must complete in k > 1 partition waves
    with filesystem-SPI spill and rows == the unconstrained local oracle,
    on the local AND mesh paths; the unconstrained runs must have
    recorded zero waves/spill/revocations."""
    violations = []
    unc = sec.get("unconstrained")
    if not isinstance(unc, dict):
        violations.append(
            f"mesh.{schema}.pressure.unconstrained missing (re-run "
            "tools/pressure_bench.py)"
        )
    else:
        for name in PRESSURE_IDLE_ZEROS:
            if unc.get(name, 0) != 0:
                violations.append(
                    f"mesh.{schema}.pressure.unconstrained.{name} = "
                    f"{unc.get(name)} (expected 0: degradation must cost "
                    "nothing without pressure)"
                )
    for side in ("local", "mesh"):
        s = sec.get(side)
        if not isinstance(s, dict):
            violations.append(
                f"mesh.{schema}.pressure.{side} missing (degradation "
                "proof incomplete — re-run tools/pressure_bench.py)"
            )
            continue
        if s.get("rows_match") is not True:
            violations.append(
                f"mesh.{schema}.pressure.{side}.rows_match = "
                f"{s.get('rows_match')} (expected true: constrained "
                "execution must answer the unconstrained oracle's rows)"
            )
        if s.get("waves", 0) < 2:
            violations.append(
                f"mesh.{schema}.pressure.{side}.waves = "
                f"{s.get('waves', 0)} (expected > 1: the pool limit must "
                "have forced multi-wave execution)"
            )
        if s.get("spill_bytes", 0) <= 0:
            violations.append(
                f"mesh.{schema}.pressure.{side}.spill_bytes = "
                f"{s.get('spill_bytes', 0)} (expected > 0: waves must "
                "have spilled through the filesystem SPI)"
            )
    return violations


#: registry-snapshot series (telemetry/metrics names) that must be zero in a
#: fresh `bench.py --mesh` snapshot.  The snapshot is PROCESS-LIFETIME, so
#: only counters that must never fire even cold belong here —
#: `join_capacity_sync` legitimately fires on cold sizing passes and is
#: gated per-warm-run via q3_counters instead.
SNAPSHOT_ZERO_LABELS = (
    "host_restack",
    "join_speculative_retry",
)


#: membership round-trip (tools/membership_bench.py) invariants: every
#: attempt of the shrink->grow story must match local, the shrink must
#: actually have re-planned, the grow must restore the full W, and the warm
#: repeat after the round trip must be clean (no re-plans, no retraces) —
#: membership churn must not leave the warm path dirty
MEMBERSHIP_ATTEMPTS = ("baseline", "shrink", "grow", "post_roundtrip_warm")


def check_membership(sec: dict) -> tuple:
    """-> (violations, skipped) over the BENCH_EXTRA `membership` section
    (the shrink->grow round trip tools/membership_bench.py records)."""
    violations: list[str] = []
    skipped: list[str] = []
    if sec.get("run_error"):
        skipped.append(f"membership: bench errored: {sec['run_error']}")
        return violations, skipped
    for name in MEMBERSHIP_ATTEMPTS:
        att = sec.get(name)
        if not isinstance(att, dict):
            violations.append(f"membership.{name} missing (round trip "
                              "incomplete — re-run tools/membership_bench.py)")
            continue
        if att.get("rows_match") is not True:
            violations.append(
                f"membership.{name}.rows_match = {att.get('rows_match')} "
                "(expected true: every membership state must answer rows "
                "== local)"
            )
    # counter checks only on sections that exist — a missing section was
    # already flagged above, a second violation over {} is noise
    shrink = sec.get("shrink")
    if isinstance(shrink, dict) and shrink.get("replans", 0) < 1:
        violations.append(
            "membership.shrink.replans = "
            f"{shrink.get('replans', 0)} (expected >= 1: the kill must "
            "have triggered mesh-shrink re-planning)"
        )
    workers = sec.get("workers")
    grow = sec.get("grow")
    if (
        isinstance(grow, dict)
        and workers is not None
        and grow.get("plan_workers") != workers
    ):
        violations.append(
            f"membership.grow.plan_workers = {grow.get('plan_workers')} "
            f"(expected {workers}: the grown worker must rejoin the next "
            "query's mesh)"
        )
    warm = sec.get("post_roundtrip_warm")
    if isinstance(warm, dict):
        for counter in ("replans", "retraces"):
            if warm.get(counter, 0) != 0:
                violations.append(
                    f"membership.post_roundtrip_warm.{counter} = "
                    f"{warm[counter]} (expected 0: a shrink->grow round "
                    "trip must leave the warm path clean)"
                )
    return violations, skipped


#: serve-section per-phase keys (bench.py --serve / trino_tpu/bench_serve):
#: the concurrency headline is only evidence with percentiles, throughput,
#: AND the correctness bit all present
SERVE_KEYS = (
    "clients", "queries_total", "qps", "p50_s", "p95_s", "p99_s",
    "shed_total", "rows_match",
)


def check_serve(sec: dict) -> list:
    """Violations over the top-level `serve` section: K >= 2 concurrent
    clients on local lanes AND the mesh, every statement answering the
    serial oracle (or shed — never wrong, never hung), and warm mesh
    serving recording ZERO compile events above the warm-up watermark
    (shared trace cache => near-zero marginal compile cost per client)."""
    violations = []
    for phase in ("local", "mesh"):
        p = sec.get(phase)
        if not isinstance(p, dict):
            violations.append(
                f"serve.{phase} missing (re-run bench.py --serve)"
            )
            continue
        missing = [k for k in SERVE_KEYS if k not in p]
        if missing:
            violations.append(f"serve.{phase} missing {missing}")
            continue
        if p.get("rows_match") is not True:
            violations.append(
                f"serve.{phase}.rows_match = {p.get('rows_match')} "
                f"(expected true: every concurrently served statement "
                f"must answer the serial oracle or be shed; errors: "
                f"{p.get('errors')})"
            )
        if p.get("clients", 0) < 2:
            violations.append(
                f"serve.{phase}.clients = {p.get('clients')} (expected "
                ">= 2: a single client proves nothing about serving)"
            )
        if not p.get("qps", 0) > 0:
            violations.append(
                f"serve.{phase}.qps = {p.get('qps')} (expected > 0)"
            )
    mesh = sec.get("mesh")
    if isinstance(mesh, dict) and mesh.get("warm_compile_events", 1) != 0:
        violations.append(
            f"serve.mesh.warm_compile_events = "
            f"{mesh.get('warm_compile_events')} (expected 0: warm "
            "concurrent serving must share the single warmed trace-cache "
            "key set and compile nothing)"
        )
    return violations


#: chaos-section keys (bench_serve's fault-tolerant recovery phase): the
#: recovery claim is only evidence with the kill count, the per-outcome
#: retry classification, the spool evidence, AND the correctness bit
CHAOS_KEYS = SERVE_KEYS + (
    "injected_kills", "task_retries", "spooled_fragments", "spool_hits",
    "full_replans",
)


def check_chaos(sec) -> list:
    """Violations over `serve.chaos` (trino_tpu/bench_serve._run_chaos):
    a worker killed mid-Q18 under K >= 2 concurrent serve clients, with
    fault_tolerant_execution on, must leave every statement answering the
    serial oracle, the kill classified RETRY (never fail), the statement
    resumed from spooled stage outputs (spool reads happened), and ZERO
    mesh-shrink full re-plans — a retryable kill re-runs lost tasks, it
    never re-fragments the query."""
    if not isinstance(sec, dict):
        return ["serve.chaos missing (re-run bench.py --serve)"]
    violations = []
    missing = [k for k in CHAOS_KEYS if k not in sec]
    if missing:
        return [f"serve.chaos missing {missing}"]
    if sec.get("rows_match") is not True:
        violations.append(
            f"serve.chaos.rows_match = {sec.get('rows_match')} (expected "
            "true: the killed statement must complete with the serial "
            f"oracle's rows; errors: {sec.get('errors')})"
        )
    if sec.get("clients", 0) < 2:
        violations.append(
            f"serve.chaos.clients = {sec.get('clients')} (expected >= 2: "
            "recovery must be exercised UNDER concurrent serve load)"
        )
    if sec.get("injected_kills", 0) < 1:
        violations.append(
            f"serve.chaos.injected_kills = {sec.get('injected_kills')} "
            "(expected >= 1: the chaos phase must actually kill a worker)"
        )
    retries = sec.get("task_retries") or {}
    if retries.get("retry", 0) < 1:
        violations.append(
            f"serve.chaos.task_retries.retry = {retries.get('retry')} "
            "(expected >= 1: the kill must classify as a task RETRY)"
        )
    if retries.get("fail", 0) != 0:
        violations.append(
            f"serve.chaos.task_retries.fail = {retries.get('fail')} "
            "(expected 0: a retryable kill must never exhaust into fail)"
        )
    for key, why in (
        ("spooled_fragments",
         "stage outputs must spool through the filesystem SPI"),
        ("spool_hits",
         "recovery must resume from spooled intermediates, not re-run "
         "finished fragments"),
    ):
        if not sec.get(key, 0) > 0:
            violations.append(
                f"serve.chaos.{key} = {sec.get(key)} (expected > 0: {why})"
            )
    if sec.get("full_replans", 0) != 0:
        violations.append(
            f"serve.chaos.full_replans = {sec.get('full_replans')} "
            "(expected 0: a retryable kill re-runs lost tasks only — the "
            "query is never re-planned)"
        )
    return violations


#: drift-section keys the attribution is only evidence WITH: the era walls
#: on both sides, the multiplicative ratio decomposition, and the named
#: dominant (phase, fragment) of the current profile
DRIFT_KEYS = (
    "schema", "query", "baseline", "current", "mesh_wall_delta_s",
    "local_wall_delta_s", "ratio_factors", "attribution", "null_diff",
)


def check_drift(sec: dict) -> list:
    """Violations over the top-level `drift` section (tools/drift_bench.py
    + tools/profile_diff.py): the ROADMAP item-2 drift must arrive
    ATTRIBUTED — dominant phase and fragment named from an archived
    profile whose phases sum to its wall (conservative and complete), and
    the warm-Q6 null-diff self check must pass (two warm archives of the
    same statement diff to ~zero), or the diff tool itself is not to be
    trusted."""
    violations = []
    missing = [k for k in DRIFT_KEYS if k not in sec]
    if missing:
        return [f"drift section missing {missing} (re-run "
                "tools/drift_bench.py)"]
    att = sec.get("attribution") or {}
    if not att.get("dominant_phase"):
        violations.append(
            "drift.attribution.dominant_phase missing (the attribution "
            "must NAME the dominant phase, not just record walls)"
        )
    if att.get("dominant_fragment") is None:
        violations.append(
            "drift.attribution.dominant_fragment missing (the attribution "
            "must name the fragment the time lives in)"
        )
    if att.get("sums_to_wall") is not True:
        violations.append(
            f"drift.attribution.sums_to_wall = {att.get('sums_to_wall')} "
            "(expected true: the per-phase decomposition must sum to the "
            "measured wall — attribution is conservative and complete)"
        )
    cur = sec.get("current") or {}
    if cur.get("matches_local") is not True:
        violations.append(
            f"drift.current.matches_local = {cur.get('matches_local')} "
            "(the profiled run must still answer the local oracle)"
        )
    null = sec.get("null_diff") or {}
    for key, want in (("pass", True), ("sums_to_wall", True)):
        if null.get(key) is not want:
            violations.append(
                f"drift.null_diff.{key} = {null.get(key)} (expected "
                f"{want}: two warm archives of the same statement must "
                "diff to ~zero with the conservation invariant intact)"
            )
    # ratio ceiling recorded by `drift_bench.py --max-ratio`: the drift
    # section carries its own acceptance threshold, so the gate re-checks
    # it on every CI run without re-benching
    max_ratio = sec.get("max_ratio")
    if max_ratio and cur.get("ratio", 0) > max_ratio:
        violations.append(
            f"drift.current.ratio = {cur.get('ratio')} > recorded "
            f"max_ratio {max_ratio} (the warm mesh/local ratio drifted "
            "past the era's acceptance ceiling — re-run "
            "tools/drift_bench.py and attribute)"
        )
    return violations


def _dig(d: dict, path: tuple):
    cur = d
    for p in path:
        if not isinstance(cur, dict) or p not in cur:
            return None
        cur = cur[p]
    return cur


def check_extra(extra: dict) -> tuple:
    """-> (violations, skipped) over every mesh schema section."""
    violations: list[str] = []
    skipped: list[str] = []
    membership = extra.get("membership")
    if isinstance(membership, dict):
        mv, ms = check_membership(membership)
        violations.extend(mv)
        skipped.extend(ms)
    else:
        skipped.append(
            "no membership section recorded (run tools/membership_bench.py)"
        )
    drift = extra.get("drift")
    if isinstance(drift, dict):
        if drift.get("run_error") or drift.get("error"):
            skipped.append(
                "drift: bench errored: "
                f"{drift.get('run_error') or drift.get('error')}"
            )
        else:
            violations.extend(check_drift(drift))
    else:
        skipped.append(
            "no drift section recorded (run tools/drift_bench.py)"
        )
    serve = extra.get("serve")
    if isinstance(serve, dict):
        if serve.get("run_error") or serve.get("error"):
            skipped.append(
                "serve: bench errored: "
                f"{serve.get('run_error') or serve.get('error')}"
            )
        else:
            violations.extend(check_serve(serve))
            if "chaos" in serve:
                violations.extend(check_chaos(serve.get("chaos")))
            else:
                skipped.append(
                    "no serve.chaos section recorded (re-run bench.py "
                    "--serve for the fault-tolerance gate)"
                )
    else:
        skipped.append(
            "no serve section recorded (run bench.py --serve)"
        )
    mesh = extra.get("mesh")
    if not isinstance(mesh, dict):
        skipped.append("no mesh section recorded (run bench.py --mesh)")
        return violations, skipped
    for schema, sec in sorted(mesh.items()):
        if schema == "run_error":
            if sec:
                skipped.append(f"mesh run_error: {sec}")
            continue
        if not isinstance(sec, dict):
            continue
        if sec.get("error"):
            skipped.append(f"mesh.{schema}: bench errored: {sec['error']}")
            continue
        for path in PROFILE_ZERO:
            v = _dig(sec, path)
            if v is None:
                continue  # older sections without the field
            if v != 0:
                violations.append(
                    f"mesh.{schema}.{'.'.join(path)} = {v} (expected 0: "
                    "warm executions must not retrace)"
                )
        counters = _dig(sec, ("profile", "counters")) or {}
        for name in PROFILE_COUNTER_ZERO:
            if counters.get(name, 0) != 0:
                violations.append(
                    f"mesh.{schema}.profile.counters.{name} = "
                    f"{counters[name]} (expected 0: host batches must not "
                    "re-enter the mesh between fragments)"
                )
        q3 = sec.get("q3_counters")
        if isinstance(q3, dict):
            for name in Q3_ZERO:
                if q3.get(name, 0) != 0:
                    violations.append(
                        f"mesh.{schema}.q3_counters.{name} = {q3[name]} "
                        "(expected 0 under co-partitioned layouts)"
                    )
        # proof-licensed execution gate (verify/capacity + verify/schedule)
        violations.extend(check_licenses(schema, sec))
        fp = sec.get("decimal_fastpath")
        if isinstance(fp, dict):
            for name, desc, ok in DECIMAL_FASTPATH_RULES:
                v = fp.get(name, 0)
                if not ok(v):
                    violations.append(
                        f"mesh.{schema}.decimal_fastpath.{name} = {v} "
                        f"(expected {desc}: Q1 decimal sums must run the "
                        "proof-licensed i64 path with no runtime fits "
                        "checks — see verify.numeric.license_decimal_sums)"
                    )
            if sec.get("q1_matches_local") is False:
                violations.append(
                    f"mesh.{schema}.q1_matches_local = False (the licensed "
                    "fast path changed Q1's rows vs the local oracle)"
                )
        # compile-observatory coldstart block (PR 6): a warm replay must
        # compile NOTHING — any nonzero warm_replay_events means the
        # workload's compile-key set is not closed and the prewarm manifest
        # under-covers it; the cold/warm ratio must be recorded so the
        # ROADMAP item-3 trajectory is measurable
        cold = sec.get("coldstart")
        if isinstance(cold, dict):
            for qname, qsec in sorted(cold.items()):
                if not isinstance(qsec, dict):
                    continue
                if qname == "restart":
                    # restart-resilience block: its own phase shape, not
                    # the per-query cold/warm decomposition
                    if qsec.get("error"):
                        skipped.append(
                            f"mesh.{schema}.coldstart.restart: bench "
                            f"errored: {qsec['error']}"
                        )
                    else:
                        violations.extend(check_restart(schema, qsec))
                    continue
                if qsec.get("warm_replay_events", 0) != 0:
                    violations.append(
                        f"mesh.{schema}.coldstart.{qname}"
                        f".warm_replay_events = "
                        f"{qsec['warm_replay_events']} (expected 0: warm "
                        "replays must not compile)"
                    )
                missing = [k for k in COLDSTART_KEYS if k not in qsec]
                if missing:
                    violations.append(
                        f"mesh.{schema}.coldstart.{qname} missing "
                        f"{missing} (cold/warm decomposition incomplete)"
                    )
        # varchar-key co-location through the global dictionary service
        # (PR 18): recorded by bench.py's dictionary phase
        dsec = sec.get("dictionary")
        if isinstance(dsec, dict):
            if dsec.get("error"):
                skipped.append(
                    f"mesh.{schema}.dictionary: bench errored: "
                    f"{dsec['error']}"
                )
            else:
                violations.extend(check_dictionary(schema, dsec))
        else:
            skipped.append(
                f"mesh.{schema}: no dictionary section recorded (run "
                "bench.py --mesh)"
            )
        # memory-pressure degradation proof (PR 12): waves+spill under a
        # constrained pool, zero cost unconstrained
        p = sec.get("pressure")
        if isinstance(p, dict):
            if p.get("error"):
                skipped.append(
                    f"mesh.{schema}.pressure: bench errored: {p['error']}"
                )
            else:
                violations.extend(check_pressure(schema, p))
        else:
            skipped.append(
                f"mesh.{schema}: no pressure section recorded (run "
                "tools/pressure_bench.py)"
            )
        # plan-decision ledger completeness + zero-regret (this PR):
        # recorded by bench.py's decisions phase
        dec = sec.get("decisions")
        if isinstance(dec, dict):
            if dec.get("error"):
                skipped.append(
                    f"mesh.{schema}.decisions: bench errored: "
                    f"{dec['error']}"
                )
            else:
                violations.extend(check_decisions(schema, dec))
        else:
            skipped.append(
                f"mesh.{schema}: no decisions section recorded (run "
                "bench.py --mesh)"
            )
        # the registry snapshot bench.py records into the section is the
        # fresh-run diff surface: apply the process-lifetime expectations
        snap = sec.get("metrics")
        if isinstance(snap, dict):
            violations.extend(
                f"mesh.{schema}: {v}" for v in check_snapshot(snap)
            )
    return violations, skipped


def check_snapshot(snapshot: dict) -> list:
    """Gate a fresh registry snapshot (REGISTRY.snapshot() flat form:
    'name{labels}' -> value) against the zero-counter expectations."""
    violations = []
    for key, value in sorted(snapshot.items()):
        if not key.startswith("trino_tpu_mesh_events_total"):
            continue
        for label in SNAPSHOT_ZERO_LABELS:
            if f'counter="{label}"' in key and value != 0:
                violations.append(
                    f"registry snapshot {key} = {value} (expected 0)"
                )
    return violations


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="counter regression gate over BENCH_EXTRA.json"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap.add_argument(
        "--extra",
        default=os.path.join(root, "BENCH_EXTRA.json"),
        help="bench side file to gate (default: repo BENCH_EXTRA.json)",
    )
    ap.add_argument(
        "--snapshot",
        default=None,
        help="fresh metrics-registry snapshot JSON to diff against the "
        "same zero-counter expectations",
    )
    args = ap.parse_args(argv)
    if not os.path.exists(args.extra):
        # no capture is checked in: the side file exists only after a
        # bench run (bench.py / tools/*_bench.py) wrote one
        print(
            f"compare_bench: skipped: nothing recorded ({args.extra} does "
            "not exist — run bench.py --mesh/--serve to record one)"
        )
        return 0
    try:
        with open(args.extra, "r", encoding="utf-8") as fh:
            extra = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"compare_bench: cannot read {args.extra}: {e}")
        return 1
    violations, skipped = check_extra(extra)
    if args.snapshot:
        try:
            with open(args.snapshot, "r", encoding="utf-8") as fh:
                violations.extend(check_snapshot(json.load(fh)))
        except (OSError, ValueError) as e:
            print(f"compare_bench: cannot read snapshot {args.snapshot}: {e}")
            return 1
    for s in skipped:
        print(f"compare_bench: skipped: {s}")
    for v in violations:
        print(f"compare_bench: DRIFT: {v}")
    if violations:
        print(f"compare_bench: {len(violations)} counter invariant(s) drifted")
        return 1
    print("compare_bench: all counter invariants hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
