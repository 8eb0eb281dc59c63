"""Row sampling operator (reference: operator/SampleOperator.java —
BERNOULLI keeps each row with probability p).

Determinism note: the keep/drop decision is a splitmix64 hash of the
row's arrival position under a salt derived from the operator's plan
position.  The salt is deterministic, so sampling reproduces exactly when
batch arrival order does (task_concurrency=1, or any serial feed); under
the parallel local exchange the arrival order — and therefore the sampled
row SET — may differ between runs while the sampling probability is
unchanged.  (The reference's per-driver RNG is nondeterministic in all
configurations; the SQL spec leaves sampling implementation-defined.)
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from trino_tpu.columnar import Batch
from trino_tpu.ops.common import splitmix64
from trino_tpu.telemetry.programs import jit_program


def _sample(batch: Batch, offset, ratio) -> Batch:
    """Keep rows where splitmix64(salted position) < ratio.  Salt/offset/
    ratio are TRACED arguments so every sampled query shares ONE compiled
    kernel (the _STEP_CACHE convention, via jit's own signature cache)."""
    cap = batch.capacity
    pos = jnp.arange(cap, dtype=jnp.uint64) + offset
    u = splitmix64(pos)
    # top 53 bits -> uniform [0, 1)
    unif = (u >> jnp.uint64(11)).astype(jnp.float64) / float(1 << 53)
    return batch.filter(unif < ratio)


_sample_step = jit_program(_sample, "sample")


class SampleOperator:
    def __init__(self, ratio: float, seed: int = 0):
        self.ratio = float(ratio)
        self.salt = np.uint64(splitmix64(np.uint64(seed * 2 + 1)))
        self._offset = 0

    def process(self, stream):
        if self.ratio >= 1.0:
            yield from stream
            return
        ratio = jnp.float64(self.ratio)
        for b in stream:
            if self.ratio <= 0.0:
                yield b.filter(jnp.zeros(b.capacity, dtype=bool))
            else:
                yield _sample_step(
                    b, jnp.uint64(self._offset) + self.salt, ratio
                )
            self._offset += b.capacity
        return
