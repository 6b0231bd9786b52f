"""Radix/hash partition Pallas TPU kernel — the shuffle hot-spot.

TPU adaptation of CUDA atomic-histogram binning, with every step on the
units the TPU has instead of scatter/gather:

* the block's buckets are one-hot encoded TRANSPOSED, ``(buckets, rows)``,
  so the row vector of bucket ids broadcasts down the sublanes;
* the stable rank of each row inside its bucket is an exclusive prefix count
  along the rows: a matmul with a strictly upper-triangular ones matrix on
  the MXU (0/1 operands, f32 accumulation: exact below 2**24 rows a block);
* each row's running bucket cursor is a one-hot contraction over the
  buckets (a masked sublane sum), not a vector gather;
* the per-block histogram is a lane sum of the one-hot matrix.

Running bucket cursors persist in a VMEM scratch across the sequential block
grid, yielding a globally stable partition in one pass.  The cursors and the
histogram are held lane-replicated as ``(buckets_padded, 128)`` so every
block and scratch shape is (8, 128)-tile aligned.

Outputs: the within-bucket position of each row ``(1, n)`` and the final
histogram ``(buckets_padded, 128)`` (every lane holds the same count).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows a grid step: the prefix-count matrix is (block, block) bf16 in VMEM,
# 2 MiB at 1024 (4096 would be 32 MiB, past v5e's scoped VMEM)
MAX_BLOCK = 1024


def _kernel(bucket_ref, pos_ref, hist_ref, tri_scr, cursor_scr, *,
            n_blocks: int):
    i = pl.program_id(0)
    n_pad, block = cursor_scr.shape[0], tri_scr.shape[0]

    @pl.when(i == 0)
    def _init():
        cursor_scr[...] = jnp.zeros_like(cursor_scr)
        rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        tri_scr[...] = (rows < cols).astype(tri_scr.dtype)

    b = bucket_ref[...]                                      # (1, block)
    ids = jax.lax.broadcasted_iota(jnp.int32, (n_pad, block), 0)
    hit = ids == b                                           # (n_pad, block)
    # rows of the same bucket earlier in this block, for every (bucket, row)
    before = jnp.dot(hit.astype(tri_scr.dtype), tri_scr[...],
                     preferred_element_type=jnp.float32)
    rank = jnp.sum(jnp.where(hit, before, 0.0), axis=0, keepdims=True)
    cursors = cursor_scr[...]                                # (n_pad, LANES)
    base = jnp.sum(jnp.where(hit, cursors[:, :1], 0), axis=0, keepdims=True)
    pos_ref[...] = base + rank.astype(jnp.int32)
    counts = jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)
    cursor_scr[...] = cursors + counts

    @pl.when(i == n_blocks - 1)
    def _emit():
        hist_ref[...] = cursor_scr[...]


def radix_partition_kernel(buckets, n_buckets: int, *, block: int = 1024,
                           interpret: bool = False):
    """buckets (n,) int32 in [0, n_buckets) -> (within_bucket_pos (1, n),
    lane-replicated histogram (n_pad, 128)).  Caller turns (bucket, pos,
    hist-prefix) into final destinations; see ops.py."""
    n = buckets.shape[0]
    block = min(block, n)
    assert n % block == 0
    n_pad = -(-n_buckets // 8) * 8
    kernel = functools.partial(_kernel, n_blocks=n // block)
    return pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, block), lambda i: (0, i)),
                   pl.BlockSpec((n_pad, LANES), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct((n_pad, LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((block, block), jnp.bfloat16),
                        pltpu.VMEM((n_pad, LANES), jnp.int32)],
        interpret=interpret,
    )(buckets.reshape(1, n))
