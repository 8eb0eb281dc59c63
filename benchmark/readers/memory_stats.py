"""Peak device memory of the fullest chip after the window
(`memory_stats()["peak_bytes_in_use"]`).  args: `scale`."""


def read(run, scale=1e-9):
    peak = run.get("memory_peak_bytes")
    if not peak:
        return None
    return peak * scale
