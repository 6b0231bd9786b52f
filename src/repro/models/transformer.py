"""Decoder-only transformer LM covering the dense / moe / vlm families.

Layers are grouped into *superblocks* of ``cfg.moe_layer_period`` layers so a
single ``lax.scan`` handles interleaved MoE stacks (llama4: dense layer + MoE
layer per superblock) and homogeneous stacks (period=1) alike.  Per-superblock
params carry a leading (n_super, ...) axis; attention params additionally a
(period, ...) axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.context import shard_tokens
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.attention import AttnMode
from repro.models.layers import (
    cross_entropy_loss, embed_apply, embed_init, logits_apply,
    mlp_apply, mlp_init, rms_norm, scan_unroll, _cache_dtype,
)


def _stacked(fn, rng, n, *args):
    return jax.vmap(lambda r: fn(r, *args))(jax.random.split(rng, n))


def _n_super(cfg):
    assert cfg.n_layers % cfg.moe_layer_period == 0
    return cfg.n_layers // cfg.moe_layer_period


def init(rng, cfg):
    dtype = jnp.dtype(cfg.dtype)
    ke, kb, kf = jax.random.split(rng, 3)
    ns, period = _n_super(cfg), cfg.moe_layer_period

    def attn_layer(r):
        r1, r2 = jax.random.split(r)
        return {
            "ln": jnp.ones((cfg.d_model,), dtype),
            **attn.attn_init(r1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.head_dim, cfg.qk_norm, dtype),
        }

    blocks = {"attn": _stacked(attn_layer, kb, ns * period)}
    # reshape leading (ns*period) -> (ns, period)
    blocks["attn"] = jax.tree.map(
        lambda a: a.reshape((ns, period) + a.shape[1:]), blocks["attn"])

    kd, km = jax.random.split(kf)
    if cfg.n_experts:
        def moe_layer(r):
            return {"ln": jnp.ones((cfg.d_model,), dtype),
                    **moe_mod.moe_init(r, cfg, dtype)}
        blocks["moe"] = _stacked(moe_layer, km, ns)
        if period > 1:
            def dense_layer(r):
                return {"ln": jnp.ones((cfg.d_model,), dtype),
                        **mlp_init(r, cfg.d_model, cfg.d_ff_dense or cfg.d_ff, dtype)}
            dl = _stacked(dense_layer, kd, ns * (period - 1))
            blocks["mlp_dense"] = jax.tree.map(
                lambda a: a.reshape((ns, period - 1) + a.shape[1:]), dl)
    else:
        def dense_layer(r):
            return {"ln": jnp.ones((cfg.d_model,), dtype),
                    **mlp_init(r, cfg.d_model, cfg.d_ff, dtype)}
        blocks["mlp"] = _stacked(dense_layer, km, ns)

    return {
        "embed": embed_init(ke, cfg.vocab_size, cfg.d_model, dtype, cfg.tie_embeddings),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "blocks": blocks,
    }


# ----------------------------------------------------------------------------
# superblock bodies
# ----------------------------------------------------------------------------
def _attn_sub(p, x, positions, cfg, mode: AttnMode):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = attn.qkv_project(p, h, positions, cfg.rope_theta, cfg.qk_norm, cfg.norm_eps)
    o = attn.attend(q, k, v, causal=True, mode=mode)
    return x + shard_tokens(jnp.einsum("bshk,hkd->bsd", o, p["wo"])), (k, v)


def _ffn_sub(p, x, cfg, is_moe):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    if is_moe:
        return x + moe_mod.moe_ffn(p, h, cfg)
    return x + mlp_apply(p, h)


def _superblock(blk, x, positions, cfg, mode):
    period = cfg.moe_layer_period
    kvs = []
    for j in range(period):
        ap = jax.tree.map(lambda a: a[j], blk["attn"])
        x, kv = _attn_sub(ap, x, positions, cfg, mode)
        kvs.append(kv)
        if cfg.n_experts and j == period - 1:
            x = _ffn_sub(blk["moe"], x, cfg, True)
        elif cfg.n_experts and period > 1:
            dp = jax.tree.map(lambda a: a[j], blk["mlp_dense"])
            x = _ffn_sub(dp, x, cfg, False)
        elif not cfg.n_experts:
            x = _ffn_sub(blk["mlp"], x, cfg, False)
    ks = jnp.stack([kv[0] for kv in kvs])  # (period, B, S, K, hd)
    vs = jnp.stack([kv[1] for kv in kvs])
    return x, (ks, vs)


from repro.models.layers import maybe_remat as _maybe_remat  # noqa: E402


def _embed_input(params, cfg, tokens, prefix_embeds):
    x = embed_apply(params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    return x, positions


def _trunk(params, cfg, x, positions, mode, collect_kv=False):
    body = _maybe_remat(
        lambda xx, blk: _superblock(blk, xx, positions, cfg, mode), cfg)

    def scan_body(xx, blk):
        xx, kv = body(xx, blk)
        return xx, (kv if collect_kv else None)

    if cfg.scan_layers:
        x, kvs = jax.lax.scan(scan_body, x, params["blocks"],
                              unroll=scan_unroll(cfg))
    else:
        kvs_l = []
        ns = _n_super(cfg)
        for i in range(ns):
            blk = jax.tree.map(lambda a: a[i], params["blocks"])
            x, kv = scan_body(x, blk)
            kvs_l.append(kv)
        kvs = (jax.tree.map(lambda *xs: jnp.stack(xs), *kvs_l)
               if collect_kv else None)
    return x, kvs


def forward(params, cfg, batch, mode: AttnMode = AttnMode()):
    """Training forward. batch: tokens (B,S) [+ prefix_embeds (B,P,d)].
    Returns logits (B, S(+P), V)."""
    x, positions = _embed_input(params, cfg, batch["tokens"],
                                batch.get("prefix_embeds"))
    x, _ = _trunk(params, cfg, x, positions, mode)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return logits_apply(params["embed"], x, cfg.tie_embeddings)


def loss_fn(params, cfg, batch, mode: AttnMode = AttnMode()):
    logits = forward(params, cfg, batch, mode)
    prefix = batch.get("prefix_embeds")
    if prefix is not None:
        logits = logits[:, prefix.shape[1]:]
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    return cross_entropy_loss(logits[:, :-1], labels[:, 1:],
                              None if mask is None else mask[:, 1:])


# ----------------------------------------------------------------------------
# prefill / decode
# ----------------------------------------------------------------------------
def cache_init(cfg, batch_size: int, smax: int, dtype=None):
    dtype = dtype or _cache_dtype(cfg)
    ns, period = _n_super(cfg), cfg.moe_layer_period
    shape = (ns, period, batch_size, smax, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(params, cfg, batch, smax: int, mode: AttnMode = AttnMode()):
    """Full forward over the prompt; returns (cache, last-token logits)."""
    x, positions = _embed_input(params, cfg, batch["tokens"],
                                batch.get("prefix_embeds"))
    x, kvs = _trunk(params, cfg, x, positions, mode, collect_kv=True)
    ks, vs = kvs  # (ns, period, B, S, K, hd)
    cache = cache_init(cfg, x.shape[0], smax)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], ks.astype(cache["k"].dtype), 0, axis=3)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], vs.astype(cache["v"].dtype), 0, axis=3)
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return cache, logits_apply(params["embed"], x, cfg.tie_embeddings)[:, 0]


def decode_step(params, cfg, batch, cache):
    """batch: tokens (B,1), positions (B,) write index. Returns (logits, cache).

    The cache rides through the layer scan as carry and each layer writes
    its new token in place, so the step holds one copy of the cache (a scan
    emitting per-layer caches as outputs would build a second one)."""
    tokens, positions = batch["tokens"], batch["positions"]
    x = embed_apply(params["embed"], tokens)
    pos2d = positions[:, None]
    bidx = jnp.arange(tokens.shape[0])

    def block(carry, blk_and_index):
        x, ck, cv = carry
        blk, i = blk_and_index
        period = cfg.moe_layer_period
        for j in range(period):
            ap = jax.tree.map(lambda a: a[j], blk["attn"])
            h = rms_norm(x, ap["ln"], cfg.norm_eps)
            q, k, v = attn.qkv_project(ap, h, pos2d, cfg.rope_theta,
                                       cfg.qk_norm, cfg.norm_eps)
            ck = ck.at[i, j, bidx, positions].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[i, j, bidx, positions].set(v[:, 0].astype(cv.dtype))
            o = attn.attend_decode(q, ck[i, j], cv[i, j], positions + 1)
            x = x + shard_tokens(jnp.einsum("bshk,hkd->bsd", o, ap["wo"]))
            if cfg.n_experts and j == period - 1:
                x = _ffn_sub(blk["moe"], x, cfg, True)
            elif cfg.n_experts and period > 1:
                dp = jax.tree.map(lambda a: a[j], blk["mlp_dense"])
                x = _ffn_sub(dp, x, cfg, False)
            elif not cfg.n_experts:
                x = _ffn_sub(blk["mlp"], x, cfg, False)
        return (x, ck, cv), None

    (x, nk, nv), _ = jax.lax.scan(
        block, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(_n_super(cfg))),
        unroll=scan_unroll(cfg))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_apply(params["embed"], x, cfg.tie_embeddings)[:, 0]
    return logits, {"k": nk, "v": nv}
