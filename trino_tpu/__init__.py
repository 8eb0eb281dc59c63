"""trino_tpu — a TPU-native distributed SQL query engine.

A from-scratch re-design of the capabilities of Trino (the distributed MPP SQL
engine; reference snapshot surveyed in SURVEY.md) built idiomatically on
JAX/XLA: plan fragments compile to jitted XLA computations over device-resident
columnar batches, cross-worker exchanges lower to ICI collectives
(`all_to_all` / `all_gather` / `psum`), and the surrounding runtime (sessions,
scheduling, memory accounting, metrics) is a host-side control plane.

Layer map (mirrors SURVEY.md §1):

    client/        -- client API + CLI                (ref: client/trino-cli, trino-client)
    server/        -- coordinator/worker control plane (ref: core/trino-main/.../server)
    sql/           -- tokenizer/parser/analyzer        (ref: core/trino-parser, sql/analyzer)
    planner/       -- logical plan, optimizer, fragmenter (ref: sql/planner)
    expr/          -- expression IR -> JAX compiler    (ref: sql/relational + sql/gen)
    ops/           -- physical operators (jitted)      (ref: operator/**)
    parallel/      -- mesh, shardings, collectives     (ref: exchange + output buffers)
    runtime/       -- driver, tasks, memory, metrics   (ref: execution/**)
    columnar/      -- device Page/Block analog         (ref: spi/Page.java, spi/block)
    types/         -- SQL type system                  (ref: spi/type)
    connectors/    -- tpch/tpcds/memory/... plugins    (ref: plugin/*)
"""

import jax

# SQL semantics require 64-bit integers (BIGINT keys, decimal-as-i64-cents) and
# 64-bit floats (DOUBLE).  The TPU has neither natively: i64 runs as i32 pairs
# (exact) and f64 is EMULATED (not IEEE: on a v5e an f64 one-hot einsum summed
# Q1's decimal chunks wrong in the 5th digit — PR 21).  So every exact sum stays
# in integer arithmetic end to end (ops/aggregation one-hot reductions, segment
# sums, i128 limb planes); f64 carries only DOUBLE/REAL columns and the final
# division of avg()/stddev-style results, where float semantics are the SQL
# semantics.  Do not route an exactness argument through f64 on the device.
jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"
