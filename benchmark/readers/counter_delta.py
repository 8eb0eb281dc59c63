"""A ratio of counter deltas over the window.

args: `num`, `den`: lists of counter names whose deltas are summed; `den`
may be the string `statements` (completed statements of the window);
`scale`.  Counters are the scan buffer pool's (`host_hits`, `host_misses`,
`device_hits`, `device_misses`, `host_bytes`, `device_bytes`) and
`h2d_bytes`, the bytes moved host->device in the window:

- a distributed runner counts them itself (`FragmentStats.bytes_to_device`
  of each statement's mesh profile);
- the local runner does not, so they are estimated from the pool: every
  device-tier miss is followed by a transfer of that entry; the entries that
  are new at the window's end are counted at their size, further misses at
  the mean entry size.  0 misses is exactly 0 bytes."""


def h2d_bytes(run) -> float:
    profiles = [
        st.extra.get("mesh_profile") for st in run["statements"]
        if st.extra.get("mesh_profile")
    ]
    if profiles:
        return float(sum(
            f.get("bytes_to_device", 0)
            for p in profiles for f in p.get("fragments", [])
        ))
    a, b = run["counters_start"], run["counters_end"]
    misses = b["device_misses"] - a["device_misses"]
    if misses <= 0:
        return 0.0
    before, after = a["device_entries"], b["device_entries"]
    new = [v for k, v in after.items() if k not in before]
    sizes = list(after.values()) or [0]
    rest = max(0, misses - len(new))
    return float(sum(new) + rest * sum(sizes) / len(sizes))


def _delta(run, names) -> float:
    total = 0.0
    for n in names:
        if n == "h2d_bytes":
            total += h2d_bytes(run)
        else:
            total += run["counters_end"][n] - run["counters_start"][n]
    return total


def read(run, num, den, scale=1.0):
    if run.get("counters_start") is None:
        return None
    if den == "statements":
        d = float(sum(1 for st in run["statements"] if not st.error))
    else:
        d = _delta(run, den)
    if d <= 0:
        return None
    return _delta(run, num) / d * scale
