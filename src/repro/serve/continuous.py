"""Continuous-batching serving: a slotted KV cache that never drains.

The static engine (``repro.serve.engine.ServeEngine``) runs prefill + decode
per prompt-length group: the decode batch starts full, bleeds slots as short
requests finish, and fully drains before the next group is admitted.  This
engine keeps ONE decode batch alive for the lifetime of the server:

* the KV cache is allocated once for ``max_batch`` *slots* over a shared
  ``max_seq`` sequence budget;
* a finished sequence frees its slot immediately;
* a queued request is admitted into a free slot *between decode steps* — its
  prompt is prefilled into a single-slot cache and scattered into the shared
  cache at the slot index — so the running batch is re-filled mid-decode and
  the decode loop never restarts from an empty batch.

The cache layout is probed, not assumed: every model family exposes
``cache_init``/``prefill``/``decode_step`` with its own cache pytree
(attention KV, Mamba conv/ssm state, cross-attention KV...), and
:func:`cache_batch_axes` locates the batch axis of every leaf by comparing
``jax.eval_shape`` of the prefill output at two batch sizes — the one axis
whose size tracks the batch size.  Admission is then a per-leaf
``dynamic_update_slice_in_dim`` along that axis, identical for all ten
archs.

Per-slot correctness mirrors the static engine exactly: each slot keeps its
own write position, ``decode_step`` masks attention per element by
``positions + 1``, and free slots decode a dummy token whose garbage cache
writes are overwritten wholesale by the next admission — so a request's
token stream is bit-identical to ``greedy_reference`` regardless of what the
neighbouring slots are doing (asserted under staggered admission in
tests/test_serve_continuous.py).

Observability: counters (``serve_admitted`` / ``serve_completed`` /
``serve_evicted`` / ``serve_decode_steps`` / ``serve_prefill_tokens``) and
gauges (``serve_queue_depth`` / ``serve_slots_active``) live in a
:class:`repro.obs.MetricsRegistry`; ``ServeDriver`` surfaces snapshots as
``telemetry`` TraceEvents and feeds the autoscaler from them.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import registry
from repro.models.attention import AttnMode
from repro.obs.metrics import MetricsRegistry
from repro.serve.engine import Request, modal_dummy_inputs, prompt_prefix_len


def cache_batch_axes(cfg: ModelConfig, params, max_seq: int):
    """Locate the batch axis of every prefill-cache leaf.

    Probes ``prefill`` abstractly (``jax.eval_shape`` — no FLOPs, no
    allocation) at batch sizes 2 and 3: only the batch dimension depends on
    the batch size, so exactly one axis per leaf may differ.  Returns
    ``(axes_tree, cache_shape_tree)`` where ``cache_shape_tree`` is the
    per-request (batch=1 along the batch axis) leaf spec at batch size 2 —
    the dtypes are the ones ``prefill`` actually produces, which is what
    ``decode_step`` must keep seeing for bit-identity with the static path
    (``cache_init`` dtypes can legitimately differ, e.g. fp32 SSM carries).
    """
    api = registry.get_model(cfg)

    def probe(b):
        batch = {"tokens": jnp.zeros((b, 1), jnp.int32),
                 **modal_dummy_inputs(cfg, b)}
        cache, _ = jax.eval_shape(
            lambda p, bt: api.prefill(p, cfg, bt, max_seq, AttnMode()),
            params, batch)
        return cache

    c2, c3 = probe(2), probe(3)

    def axis(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        if len(diffs) != 1:
            raise ValueError(
                f"cannot locate batch axis: shapes {a.shape} vs {b.shape} "
                f"differ in {len(diffs)} axes (family {cfg.family!r})")
        return diffs[0]

    return jax.tree.map(axis, c2, c3), c2


@dataclasses.dataclass
class _Slot:
    """One active sequence: its request, write position, and progress."""
    req: Request
    position: int       # next KV write index (prefix + prompt_len + decoded)
    next_tok: int       # last generated token = next decode input
    generated: list     # tokens generated so far (next_tok included)

    @property
    def remaining(self) -> int:
        return self.req.max_new_tokens - len(self.generated)


@dataclasses.dataclass
class Admission:
    """A prefilled request ready to be inserted into a slot: the single-slot
    cache plus the first generated token (from the prefill logits).  Pure
    output of :meth:`ContinuousEngine.prefill_request` — computing one does
    not touch the shared cache, so prefill work can run concurrently with
    decode rounds (the ServeDriver's task split)."""
    req: Request
    cache: object       # prefill cache pytree, batch size 1
    first_tok: int


class ContinuousEngine:
    """Continuous-batching greedy generation over a slotted KV cache.

    Shared-state methods (``insert``, ``decode_round``, ``step``, ``run``)
    must be called from one control thread at a time; ``submit`` and
    ``prefill_request`` touch only the queue / their own arrays.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_seq: int = 256,
                 metrics: Optional[MetricsRegistry] = None):
        self.cfg = cfg
        self.params = params
        self.api = registry.get_model(cfg)
        self.max_batch = max_batch
        self.max_seq = max_seq
        self._prefix = prompt_prefix_len(cfg)
        # the slot cache is donated to every step that replaces it, so a
        # cache sized to fill the device never needs a second copy of itself
        self._decode = jax.jit(
            lambda p, b, c: self.api.decode_step(p, cfg, b, c),
            donate_argnums=2)
        self._prefill = jax.jit(
            lambda p, b: self.api.prefill(p, cfg, b, max_seq, AttnMode()))
        axes, spec1 = cache_batch_axes(cfg, params, max_seq)
        self._axes = axes
        # the shared slot cache: prefill's own layout/dtypes, batch axis
        # widened to max_batch slots
        self.cache = jax.tree.map(
            lambda s, ax: jnp.zeros(
                s.shape[:ax] + (max_batch,) + s.shape[ax + 1:], s.dtype),
            spec1, axes)
        # admission scatter: one dynamic_update_slice per leaf along its
        # batch axis; slot index is traced so one compilation serves every
        # slot
        self._insert_fn = jax.jit(
            lambda cache, new, slot: jax.tree.map(
                lambda c, n, ax: jax.lax.dynamic_update_slice_in_dim(
                    c, n.astype(c.dtype), slot, axis=ax),
                cache, new, self._axes), donate_argnums=0)
        self.slots: list[Optional[_Slot]] = [None] * max_batch
        self.queue: deque[Request] = deque()
        self.results: dict[int, np.ndarray] = {}
        self.evicted: list[int] = []
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.metrics.gauge("serve_queue_depth", lambda: len(self.queue))
        self.metrics.gauge("serve_slots_active", lambda: self.slots_active)

    # -- introspection -----------------------------------------------------
    @property
    def slots_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @property
    def outstanding(self) -> int:
        """Requests admitted or queued but not yet finished."""
        return self.queue_depth + self.slots_active

    # -- request intake ----------------------------------------------------
    def submit(self, requests: Request | Sequence[Request]):
        """Enqueue requests.  A request that cannot fit the sequence budget
        (``prefix + prompt + max_new_tokens > max_seq`` — its decode writes
        would run off the end of the cache) is EVICTED at admission control:
        its uid lands in ``self.evicted`` and the ``serve_evicted`` counter,
        never in the queue."""
        if isinstance(requests, Request):
            requests = [requests]
        for r in requests:
            if self._prefix + len(r.prompt) + r.max_new_tokens > self.max_seq:
                self.evicted.append(r.uid)
                self.metrics.inc("serve_evicted")
                continue
            self.queue.append(r)

    # -- admission ---------------------------------------------------------
    def prefill_request(self, req: Request) -> Admission:
        """Prefill one request into a fresh single-slot cache (pure w.r.t.
        the shared cache).  The prefill logits yield the first generated
        token, exactly like the static engine."""
        batch = {"tokens": jnp.asarray(req.prompt.astype(np.int32)[None]),
                 **modal_dummy_inputs(self.cfg, 1)}
        cache, logits = self._prefill(self.params, batch)
        self.metrics.inc("serve_prefill_tokens", len(req.prompt))
        return Admission(req=req, cache=cache,
                         first_tok=int(jnp.argmax(logits[0])))

    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def insert(self, adm: Admission) -> Optional[int]:
        """Scatter an admission into a free slot (mutates the shared cache).
        Returns the slot index, or None when the request completed at
        admission (``max_new_tokens == 1``: the prefill logits were the
        whole generation, no slot needed)."""
        self.metrics.inc("serve_admitted")
        if adm.req.max_new_tokens <= 1:
            self._finish(adm.req, [adm.first_tok])
            return None
        free = self.free_slots()
        if not free:
            raise RuntimeError("insert() with no free slot")
        slot = free[0]
        self.cache = self._insert_fn(self.cache, adm.cache,
                                     jnp.int32(slot))
        self.slots[slot] = _Slot(
            req=adm.req,
            position=self._prefix + len(adm.req.prompt),
            next_tok=adm.first_tok, generated=[adm.first_tok])
        return slot

    def _admit_from_queue(self) -> int:
        """Admit queued requests into free slots (inline prefill+insert)."""
        n = 0
        while self.queue and (self.free_slots() or
                              self.queue[0].max_new_tokens <= 1):
            self.insert(self.prefill_request(self.queue.popleft()))
            n += 1
        return n

    # -- decode ------------------------------------------------------------
    def decode_round(self) -> list[Request]:
        """One decode step over ALL slots.  Active slots consume their last
        generated token at their own position; free slots decode a dummy
        token 0 at position 0 whose cache writes are dead (overwritten by
        the next admission's full-slot scatter).  Returns the requests that
        finished this round (their slots are already free)."""
        if self.slots_active == 0:
            return []
        toks = np.zeros((self.max_batch, 1), np.int32)
        pos = np.zeros((self.max_batch,), np.int32)
        for i, s in enumerate(self.slots):
            if s is not None:
                toks[i, 0] = s.next_tok
                pos[i] = s.position
        logits, self.cache = self._decode(
            self.params,
            {"tokens": jnp.asarray(toks), "positions": jnp.asarray(pos)},
            self.cache)
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
        self.metrics.inc("serve_decode_steps")
        finished = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            s.position += 1
            s.next_tok = int(nxt[i])
            s.generated.append(s.next_tok)
            if s.remaining == 0:
                self._finish(s.req, s.generated)
                self.slots[i] = None
                finished.append(s.req)
        return finished

    def decode_rounds(self, max_rounds: int) -> list[Request]:
        """Up to ``max_rounds`` decode steps, stopping early the moment any
        slot finishes — freed capacity should go back to admission, not to
        more rounds of a smaller batch.  The ServeDriver's decode-task
        payload."""
        for _ in range(max_rounds):
            finished = self.decode_round()
            if finished or self.slots_active == 0:
                return finished
        return []

    def _finish(self, req: Request, generated: list):
        self.results[req.uid] = np.asarray(
            generated[:req.max_new_tokens], np.int32)
        self.metrics.inc("serve_completed")

    # -- standalone loop ---------------------------------------------------
    def step(self) -> list[Request]:
        """One engine iteration: admit whatever fits, then one decode step.
        Admission happens BETWEEN decode steps — the continuous-batching
        invariant — so a request arriving mid-generation joins the running
        batch without draining it."""
        self._admit_from_queue()
        return self.decode_round()

    def run(self, requests: Sequence[Request]) -> dict:
        """Convenience: serve ``requests`` to completion; returns
        uid -> generated tokens (evicted uids excluded — see ``evicted``)."""
        self.submit(list(requests))
        while self.outstanding:
            self.step()
        return dict(self.results)
