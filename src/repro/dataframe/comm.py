"""Channel abstraction over jax.lax collectives — the analogue of Cylon's
MPI/UCX/GLOO communicator layer.  All distributed operators go through these
four primitives, so the 'transport' is swappable and mockable (single point
of instrumentation for the collective-traffic accounting in benchmarks/).
The data exchanges run under the ``exchange`` scope, which names them in
the compiled program's metadata.
"""
from __future__ import annotations

import jax


def all_to_all(x, axis: str):
    """x (P, c, ...) per rank -> chunk j goes to rank j; returns (P, c, ...)"""
    with jax.named_scope("exchange"):
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=False)


def all_gather(x, axis: str):
    with jax.named_scope("exchange"):
        return jax.lax.all_gather(x, axis)


def psum(x, axis: str):
    return jax.lax.psum(x, axis)


def pmax(x, axis: str):
    return jax.lax.pmax(x, axis)


def axis_index(axis: str):
    return jax.lax.axis_index(axis)


def axis_size(axis: str):
    return jax.lax.axis_size(axis)
