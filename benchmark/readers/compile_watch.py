"""From JAX's compile events.  args: `over` (`setup` or `window`), `field`
(`compile_s`, `compiles`, `cache_hits`, `cache_misses`)."""


def read(run, over, field):
    watch = run.get("watch_" + over)
    if watch is None:
        return None
    return float(watch[field])
