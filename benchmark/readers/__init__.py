"""Readers of per-layer metrics, one module per KIND of source.  A metric is
a data file (`benchmark/layer_metrics/<name>.json`: `reader`, `args`); the
harness calls `readers.<reader>.read(run, **args)`.  `run` is a dict of what
the traced run gathered:

- `statements`: the window's statements (query, params, start_s, end_s,
  error, extra["mesh_profile"]);
- `spans`: [(query_id, [span dict])] from the runner's `query_trace` ring;
- `counters_start`, `counters_end`: the scan buffer pool's counters;
- `watch_setup`, `watch_window`: CompileWatch deltas over set-up and window;
- `memory_peak_bytes`; `trace`: `trace_reduce.reduce()` of the traced part,
  or None; `traced_statements`; `config`; `peaks` (None off the chip).

A reader that finds nothing to read returns None and the metric is left out
of the line; it never returns 0 for a share of a roofline or of a peak."""
