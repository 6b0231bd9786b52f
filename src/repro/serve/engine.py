"""Serving engines.  This module holds the STATIC-batch baseline
(``ServeEngine``: requests grouped by prompt length, one prefill + decode
loop per group — the whole batch drains before the next group starts) plus
the pieces it shares with the continuous-batching engine
(``repro.serve.continuous.ContinuousEngine``): the ``Request`` record, the
modal dummy-input builder, and the ``greedy_reference`` oracle.

Both engines are SPMD payloads like any other: the runtime can schedule
generation as tasks on private sub-meshes next to ETL and training tasks
(examples/serve_lm.py, ``repro.serve.driver.ServeDriver``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import registry
from repro.models.attention import AttnMode


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (prompt_len,) int32
    max_new_tokens: int = 16
    uid: int = 0


def modal_dummy_inputs(cfg: ModelConfig, batch_size: int) -> dict:
    """Zero-filled placeholder modal inputs for a ``batch_size`` batch: the
    vision/audio frontends are stubs per the assignment, so vlm prompts carry
    all-zero patch embeddings and audio prompts all-zero frame embeddings.
    Shared by both engines and the oracle so the placeholders can never
    drift apart between them."""
    extras = {}
    if cfg.family == "vlm":
        extras["prefix_embeds"] = jnp.zeros(
            (batch_size, cfg.n_patches, cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.family == "audio":
        extras["frames"] = jnp.zeros(
            (batch_size, cfg.n_encoder_frames, cfg.d_model),
            jnp.dtype(cfg.dtype))
    return extras


def prompt_prefix_len(cfg: ModelConfig) -> int:
    """Positions a prompt's KV entries start AFTER: vlm patch embeddings are
    prepended to the token stream, so generation positions are offset by
    ``n_patches``; every other family starts at 0."""
    return cfg.n_patches if cfg.family == "vlm" else 0


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_seq: int = 256):
        self.cfg = cfg
        self.params = params
        self.api = registry.get_model(cfg)
        self.max_batch = max_batch
        self.max_seq = max_seq
        self._decode = jax.jit(
            lambda p, b, c: self.api.decode_step(p, cfg, b, c))
        self._prefill = jax.jit(
            lambda p, b: self.api.prefill(p, cfg, b, max_seq, AttnMode()))

    def run_requests(self, requests: Sequence[Request]):
        """Static-batch generation; returns dict uid -> generated tokens.
        Requests are grouped by prompt length (causal prefill over padding
        would corrupt the cache), then chunked to max_batch."""
        out = {}
        by_len: dict[int, list] = {}
        for r in requests:
            by_len.setdefault(len(r.prompt), []).append(r)
        for _, group in sorted(by_len.items()):
            for i in range(0, len(group), self.max_batch):
                out.update(self._run_batch(group[i:i + self.max_batch]))
        return out

    def _run_batch(self, requests):
        b = len(requests)
        plen = len(requests[0].prompt)
        toks = jnp.asarray(np.stack([r.prompt for r in requests]).astype(np.int32))
        batch = {"tokens": toks, **modal_dummy_inputs(self.cfg, b)}
        cache, logits = self._prefill(self.params, batch)

        prefix = prompt_prefix_len(self.cfg)
        positions = np.full((b,), prefix + plen, np.int32)
        max_new = max(r.max_new_tokens for r in requests)
        gen = np.zeros((b, max_new), np.int32)
        next_tok = np.asarray(jnp.argmax(logits, -1), np.int32)
        for t in range(max_new):
            gen[:, t] = next_tok
            db = {"tokens": jnp.asarray(next_tok[:, None]),
                  "positions": jnp.asarray(positions)}
            logits, cache = self._decode(self.params, db, cache)
            next_tok = np.asarray(jnp.argmax(logits, -1), np.int32)
            positions += 1
        return {r.uid: gen[i, :r.max_new_tokens] for i, r in enumerate(requests)}


def greedy_reference(cfg, params, prompt: np.ndarray, n_new: int, *,
                     pad_to: Optional[int] = None,
                     return_logits: bool = False):
    """Oracle: a jitted full forward re-run per generated token.

    Each step compiles once per token-row length.  ``pad_to`` pads every
    row to that fixed length so one compilation serves all steps; that is
    exact only for causal families without capacity routing (the logits at
    a position must not depend on later positions — MoE token dropping
    does).  ``return_logits`` also returns every step's float32 logits,
    ``(n_new, vocab)``, for a tolerance check where a low-precision stream
    parts from the oracle."""
    api = registry.get_model(cfg)
    step = jax.jit(lambda p, b, i: api.forward(p, cfg, b)[0, i]
                   .astype(jnp.float32))
    offset = prompt_prefix_len(cfg) - 1
    toks = list(map(int, prompt))
    rows = []
    for _ in range(n_new):
        row = np.asarray(toks, np.int32)
        if pad_to is not None:
            row = np.pad(row, (0, pad_to - len(row)))
        batch = {"tokens": jnp.asarray(row[None]),
                 **modal_dummy_inputs(cfg, 1)}
        logits = step(params, batch, offset + len(toks))
        toks.append(int(jnp.argmax(logits)))
        if return_logits:
            rows.append(np.asarray(logits))
    out = np.asarray(toks[len(prompt):], np.int32)
    return (out, np.stack(rows)) if return_logits else out
