"""One run of one cell: set-up, window, reference, metrics, result line.

`run_cell` is what `benchmark/run.py` calls after it has found the chip; the
CPU rehearsal and the fault tests call it directly (with `config_overrides`
that the command line cannot reach) to drive everything but the look for a chip."""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import sys
import time

from benchmark.harness import compare, spec, traffic, window
from benchmark.harness.watch import CompileWatch


#: set-up repeats the warm-up pass until one compiles nothing, at most this
WARMUP_PASSES = 4


_T0 = time.perf_counter()


def say(**facts) -> None:
    """A free-form fact line (`t_s`: seconds since the harness was imported);
    only the LAST line of stdout is the result."""
    facts["t_s"] = round(time.perf_counter() - _T0, 3)
    print(json.dumps(facts, default=str), flush=True)


def device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def percentile_nearest_rank(values: list, p: float) -> float:
    ordered = sorted(values)
    return ordered[max(0, math.ceil(p / 100.0 * len(ordered)) - 1)]


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def end_to_end(statements: list, t0: float, t_end: float, setup_s: float):
    """The end-to-end metrics, over all the work and all the time of the
    window.  A failed statement counts as missing: it is not a completion,
    and in the tail it stands at the window's whole length."""
    span = t_end - t0
    done = [st for st in statements if not st.error]
    walls = [st.wall_s if not st.error else span for st in statements]
    return {
        "stmt_per_s": len(done) / span,
        "stmt_p95_s": percentile_nearest_rank(walls, 95.0),
        "stmt_max_s": max(walls),
        "setup_s": setup_s,
    }


def check_answers(suite_name: str, schema: str, statements: list) -> tuple:
    """Compare every statement of the window with the plain reference.
    Returns (compared, details): `compared` maps each number compared to
    its value and its limit."""
    module = importlib.import_module(f"benchmark.reference.{suite_name}")
    suite = module.Suite(schema)
    distinct: dict = {}
    for st in statements:
        distinct.setdefault(st.key, (st.query, st.params))
    answers = dict(zip(
        distinct, suite.answers(list(distinct.values()))
    ))
    wrong, missing, details = 0, 0, []
    for st in statements:
        if st.error:
            missing += 1
            details.append(f"{st.query}#{st.seq}: no answer: {st.error}")
            continue
        why = compare.wrong(st.rows, answers[st.key])
        if why:
            wrong += 1
            if len(details) < 5:
                details.append(f"{st.query}#{st.seq} {st.params}: {why}")
    reference_rows = sum(len(a["rows"]) for a in answers.values())
    compared = {
        "answers_wrong": {"value": wrong, "limit": 0},
        "answers_missing": {"value": missing, "limit": 0},
        "reference_rows": {"value": reference_rows, "limit": 1,
                           "at_least": True},
    }
    return compared, details


def is_correct(compared: dict) -> bool:
    return all(
        (c["value"] >= c["limit"]) if c.get("at_least")
        else (c["value"] <= c["limit"])
        for c in compared.values()
    )


def layer_metrics(per_layer: list, run: dict) -> dict:
    """Each of the cell's per-layer metrics through its reader; a reader
    that finds nothing to read leaves its metric out."""
    metrics = {}
    for m in per_layer:
        reader = importlib.import_module(f"benchmark.readers.{m['reader']}")
        value = reader.read(run, **m.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float | None = None, config_overrides: dict | None = None,
             bench: dict | None = None, out=sys.stdout) -> dict:
    """Returns the result object (also printed as the last line of `out`).
    `config_overrides` replaces keys of the configuration (the rehearsal's
    `schema: tiny`); the command line cannot reach it."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.Cell(name, bench)
    config = {**cell.config, **(config_overrides or {})}
    from trino_tpu.parallel.spmd import configure_persistent_cache

    watch = CompileWatch()
    device = device_facts()
    say(workload=name, seed=seed, seconds=seconds, trace=int(trace),
        device=device, jax=jax.__version__, schema=config["schema"],
        compile_cache_dir=configure_persistent_cache())
    mix = traffic.Mix(cell.traffic, seed)
    from benchmark.harness.serve import Served

    served = Served(config, trace)
    # -- set-up: every statement of the mix once (compile or cache load,
    # scan columns into the pool).  Counted as setup_s.
    # A pass is repeated until one compiles nothing (at most
    # WARMUP_PASSES): the first execution of a statement is not always the
    # last that compiles (its second run reads the scan from the pool's
    # device tier and may take another program).
    client = served.client()
    for attempt in range(WARMUP_PASSES):
        before = watch.compiles
        for st in mix.warmup():
            t0 = time.perf_counter()
            client.execute(st.sql)
            say(setup=st.query, warmup_pass=attempt, params=st.params,
                wall_s=time.perf_counter() - t0, **watch.snapshot())
        if watch.compiles == before:
            break
    served.drain_spans()
    watch_setup = watch.snapshot()
    counters_start = served.counters() if trace else None
    setup_s = time.perf_counter() - t_start
    # -- the window
    tracer = None
    trace_dir = os.path.join(spec.REPO_DIR, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = window.DeviceTrace(trace_dir, mix.trace_seconds)
    win = window.run(served, mix, seconds, tracer, collect=trace)
    watch_end = watch.snapshot()
    counters_end = served.counters() if trace else None
    statements = win["statements"]
    for st in statements:
        say(statement=f"{st.query}#{st.seq}", stream=st.stream,
            wall_s=st.wall_s, at_s=st.start_s - win["t0"],
            rows=None if st.rows is None else len(st.rows),
            cpu_s=st.extra.get("cpu_s"), gc_s=st.extra.get("gc_s"),
            **({"error": st.error} if st.error else {}))
    peak = memory_peak_bytes()
    e2e = end_to_end(statements, win["t0"], win["t_end"], setup_s)
    watch_window = _delta(watch_end, watch_setup)
    failed = sum(1 for st in statements if st.error)
    say(window_s=win["t_end"] - win["t0"], statements=len(statements),
        failed=failed, compiles_in_window=watch_window["compiles"],
        setup=watch_setup, memory_peak_bytes=peak, **e2e)
    # -- free the program's state, then the reference (not in setup_s)
    served.close()
    t0 = time.perf_counter()
    compared, details = check_answers(
        config["suite"], config["schema"], statements
    )
    say(reference_s=time.perf_counter() - t0, details=details)
    # -- metrics
    device["memory_peak_bytes"] = peak
    result = {
        "correct": is_correct(compared),
        "attempted": len(statements),
        "failed": failed,
    }
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }
    else:
        from benchmark import trace_reduce

        reduced = None
        t0 = time.perf_counter()
        try:
            reduced = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        except FileNotFoundError as exc:
            say(trace_error=str(exc))
        say(trace_read_s=time.perf_counter() - t0,
            trace={k: v for k, v in (reduced or {}).items()
                   if k not in ("device_ops", "idle_gaps")})
        shutil.rmtree(trace_dir, ignore_errors=True)
        try:
            peaks = spec.peaks(device["kind"])
        except KeyError:
            if device["platform"] == "tpu":
                raise
            peaks = None  # a CPU rehearsal has no peaks: shares are left out
        run = {
            "statements": statements, "spans": win["spans"],
            "counters_start": counters_start, "counters_end": counters_end,
            "watch_setup": watch_setup, "watch_window": watch_window,
            "memory_peak_bytes": peak, "trace": reduced,
            "traced_statements": tracer.covered(statements),
            "config": config,
            "peaks": peaks,
        }
        result["metrics"] = layer_metrics(cell.per_layer, run)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s_mean"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
    result["device"] = device
    result["compared"] = compared
    # each number compared beside its limit: last lines of stderr, and last
    # key of the result line
    for key, c in compared.items():
        print(f"compared {key}: value {c['value']} limit "
              f"{'>=' if c.get('at_least') else '<='} {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result
