"""jit'd wrapper: bucket-major stable destinations for a partition/shuffle."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.radix_partition.radix_partition import (
    MAX_BLOCK, radix_partition_kernel)


@partial(jax.jit, static_argnames=("n_buckets", "block", "interpret"))
def radix_partition(buckets, n_buckets: int, *, block: int = 1024,
                    interpret: bool = False):
    """buckets (n,) int32 -> (dest (n,), hist (n_buckets,)):
    row i belongs at global position dest[i] of the bucket-major layout."""
    n = buckets.shape[0]
    if n_buckets == 1:
        # degenerate single-bucket partition: the identity.  Short-circuit
        # instead of launching the kernel — the pad-correction below would
        # subtract the padded tail from the SAME bucket the real rows occupy
        # (padding targets bucket n_buckets - 1, which here is also every
        # real row's bucket).
        return jnp.arange(n, dtype=jnp.int32), jnp.full((1,), n, jnp.int32)
    block = min(block, MAX_BLOCK)     # the grid tiling: same result
    pad = (-n) % block if n >= block else block - n
    b = jnp.pad(buckets, (0, pad), constant_values=n_buckets - 1) if pad else buckets
    within2d, hist2d = radix_partition_kernel(b, n_buckets, block=block,
                                              interpret=interpret)
    within = within2d[0, :n]
    hist = hist2d[:n_buckets, 0]
    if pad:
        hist = hist - jnp.bincount(b[n:], length=n_buckets).astype(jnp.int32)
    offsets = jnp.cumsum(hist) - hist
    return offsets[buckets] + within, hist
