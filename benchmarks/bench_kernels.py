"""Kernel hot-spot microbenchmarks.

Pallas kernels target TPU; on this CPU container we (a) time the compiled
pure-jnp reference paths (the mathematical spec each kernel implements) and
(b) count kernel-tile FLOPs/bytes to report the VMEM-resident arithmetic
intensity the TPU kernel achieves by construction.  Kernel *correctness* is
covered by tests/test_kernels.py (interpret mode vs ref).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import FAST, emit, time_call


def bench_attention_ref():
    from repro.models.attention import attend_blockwise
    b, s, h, kh, hd = (1, 1024, 8, 2, 64) if FAST else (2, 4096, 16, 4, 128)
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, hd), jnp.float32)
    f = jax.jit(lambda q, k, v: attend_blockwise(q, k, v, causal=True,
                                                 q_block=256, kv_block=256))
    f(q, k, v).block_until_ready()
    t = time_call(lambda: f(q, k, v).block_until_ready(), iters=3)
    flops = 4 * b * h * s * s * hd  # 2 matmuls x 2 (MAC)
    emit("kernels/flash_attention/jnp_ref", t * 1e6,
         f"gflops_s={flops / t / 1e9:.1f};vmem_tile_bytes="
         f"{(128 * hd * 2 + 128 * 128 * 4) * 2}")


def bench_ssm_ref():
    from repro.kernels.ssm_scan.ref import ssm_scan_ref
    B, S, D, N = (1, 512, 256, 16) if FAST else (2, 2048, 1024, 16)
    ks = jax.random.split(jax.random.key(1), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, D)))
    A = -jnp.exp(jax.random.normal(ks[1], (D, N)) * 0.3)
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    x = jax.random.normal(ks[4], (B, S, D))
    f = jax.jit(ssm_scan_ref)
    f(dt, A, Bm, Cm, x).block_until_ready()
    t = time_call(lambda: f(dt, A, Bm, Cm, x).block_until_ready(), iters=3)
    flops = 6 * B * S * D * N
    emit("kernels/ssm_scan/jnp_ref", t * 1e6,
         f"gflops_s={flops / t / 1e9:.2f};state_bytes_vmem={D * N * 4}")


def bench_sort_ref():
    n = 1 << (14 if FAST else 18)
    keys = jax.random.randint(jax.random.key(2), (4, n), 0, 1 << 30, jnp.int32)
    f = jax.jit(lambda k: jnp.sort(k, axis=-1))
    f(keys).block_until_ready()
    t = time_call(lambda: f(keys).block_until_ready(), iters=3)
    emit("kernels/bitonic_sort/jnp_ref", t * 1e6,
         f"mrows_s={4 * n / t / 1e6:.1f}")


def bench_partition_ref():
    from repro.kernels.radix_partition.ref import destinations_ref
    n, buckets = (1 << 14, 64) if FAST else (1 << 18, 256)
    b = jax.random.randint(jax.random.key(3), (n,), 0, buckets, jnp.int32)
    f = jax.jit(lambda x: destinations_ref(x, buckets))
    jax.block_until_ready(f(b))
    t = time_call(lambda: jax.block_until_ready(f(b)), iters=3)
    emit("kernels/radix_partition/jnp_ref", t * 1e6,
         f"mrows_s={n / t / 1e6:.1f};mxu_onehot_matmul_flops={2 * n * buckets}")


def run():
    bench_attention_ref()
    bench_ssm_ref()
    bench_sort_ref()
    bench_partition_ref()


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
