"""The benchmark's harness: everything here is general.  What belongs to one
configuration, traffic mix, statement suite or per-layer metric is a data
file (or, for a reference suite or a kind of reader, a module of its own)
found by the name in BENCHMARK.json."""
