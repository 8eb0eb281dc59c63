"""Share of the memory roofline: the least time the chip could take for the
bytes the traced statements must read, over the device's busy time in the
traced part of the window.

The bytes are the algorithm's, never the program's: for each traced
statement, rows of each table it scans (the configuration's `rows`) times
the sum of the narrowest whole-byte lossless widths of the columns it needs
(`column_bytes`), each needed column once per statement.  The peak is the
device kind's published HBM bandwidth (`benchmark/peaks.json`).  A later
narrowing of the program's int64 columns therefore cannot push the share
past 100 %."""


def statement_bytes(config: dict, query: str) -> float:
    total = 0.0
    for table, columns in config["queries"][query]["scans"].items():
        width = sum(config["column_bytes"][c] for c in columns)
        total += config["rows"][table] * width
    return total


def read(run, peak="hbm_bytes_per_s", scale=100.0):
    trace, peaks = run.get("trace"), run.get("peaks")
    traced = run.get("traced_statements") or []
    if not trace or not peaks or not traced or trace["busy_s"] <= 0:
        return None
    least_s = sum(
        statement_bytes(run["config"], st.query) for st in traced
    ) / peaks[peak]
    return least_s / trace["busy_s"] * scale
