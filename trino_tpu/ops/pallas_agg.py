"""Pallas TPU kernel: fused masked grouped aggregation.

Reference role: the generated accumulator loops of
operator/aggregation/GroupedAggregator + AccumulatorCompiler — the hottest
loop of the engine's Q1-shaped workload (low-cardinality GROUP BY over wide
fact scans).

TPU design: for a small group domain G, grouped sums ARE a matmul — the
one-hot group matrix against the value planes rides the MXU instead of
scatter hardware the TPU doesn't have.  The Pallas kernel streams row blocks
HBM->VMEM, builds the one-hot tile in-register, and accumulates partials in
a VMEM scratch across grid steps — one pass over the data, no
re-materialized one-hot in HBM (which is what the equivalent XLA formulation
allocates when N is large).

Layout (what Mosaic accepts — AOT-compiled for a described v5e by
tests/test_tpu_compile.py): ROWS RIDE THE LANES.  Group ids arrive as a
[1, N] int32 plane with dead rows already folded to -1 (no bool ref), the
values as K-major planes [Kp, N] f32 (Kp = K padded to the 8-sublane tile),
and the kernel contracts both over the lane axis: out^T [Kp, Gp] =
values [Kp, B] . onehot [Gp, B]^T with Gp = G padded to 128 lanes.  Every
block is 2-D and (8, 128)-aligned; the only in-kernel broadcast is the
[1, B] -> [Gp, B] sublane broadcast of the gid row.

Used by the engine as an optional fast path for sum/count aggregates with
small integer group ids (session property `pallas_agg`); everything else
takes the sort-based path in ops/aggregation.py.  On CPU (tests) the kernel
runs in interpreter mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from trino_tpu.telemetry.programs import jit_program

_BLOCK = 2048  # rows per grid step (VMEM: Kp*2048*4B + Gp*2048*4B one-hot)
_LANES = 128
_SUBLANES = 8


def _agg_kernel(gid_ref, val_ref, out_ref, acc_ref):
    import jax.experimental.pallas as pl

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gids = gid_ref[...]  # [1, B] int32, dead rows = -1
    vals = val_ref[...]  # [Kp, B] f32
    gp = acc_ref.shape[1]
    # one-hot^T [Gp, B]; built in VMEM, never in HBM.  -1 matches no row of
    # the iota, so dead rows contribute nothing
    onehot_t = (
        jax.lax.broadcasted_iota(jnp.int32, (gp, gids.shape[1]), 0) == gids
    ).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        vals,
        onehot_t,
        (((1,), (1,)), ((), ())),  # contract over rows (lanes): [Kp, Gp]
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(step == pl.num_programs(0) - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def grouped_sums_pallas(
    gids, mask, values, n_groups: int, interpret: bool = False
):
    """sum of values[:, k] per group (masked): [G, K] float32.

    gids int32 [N] in [0, n_groups); mask bool [N]; values float32 [N, K].
    N must be a multiple of the block size (pad with mask=False rows).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, k = values.shape
    block = min(_BLOCK, n)
    assert n % block == 0, f"pad N={n} to a multiple of {block}"
    kp = _round_up(k, _SUBLANES)
    gp = _round_up(n_groups, _LANES)
    gid_row = jnp.where(mask, gids.astype(jnp.int32), -1)[None, :]
    planes = jnp.pad(values.astype(jnp.float32).T, ((0, kp - k), (0, 0)))
    # the engine runs with x64 on: a literal 0 in an index map would trace
    # as i64, which Mosaic refuses — block indices must be i32
    zero = functools.partial(jnp.zeros, (), jnp.int32)
    out_t = pl.pallas_call(
        _agg_kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (zero(), i)),
            pl.BlockSpec((kp, block), lambda i: (zero(), i)),
        ],
        out_specs=pl.BlockSpec((kp, gp), lambda i: (zero(), zero())),
        out_shape=jax.ShapeDtypeStruct((kp, gp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kp, gp), jnp.float32)],
        interpret=interpret,
    )(gid_row, planes)
    return out_t[:k, :n_groups].T


grouped_sums_pallas = jit_program(
    grouped_sums_pallas, "agg_pallas_kernel",
    static_argnames=("n_groups", "interpret"),
)


def grouped_sums_xla(gids, mask, values, n_groups: int):
    """The XLA formulation of the same computation (segment-sum one-hot
    matmul) — the comparison baseline for the micro-bench."""
    onehot = jax.nn.one_hot(gids, n_groups, dtype=jnp.float32)
    onehot = onehot * mask[:, None].astype(jnp.float32)
    return onehot.T @ values.astype(jnp.float32)
