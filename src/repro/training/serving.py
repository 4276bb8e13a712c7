"""Continuous-batching serving loop.

A slot-based scheduler over the framework's prefill/decode steps: requests
arrive with ragged prompts, occupy fixed decode slots (the production
decode_32k shape = 128 slots), finished slots are refilled from the queue
without stalling the running batch.  The decode step itself is the jitted
``decode_step`` the dry-run lowers at production scale; here it runs at
reduced scale on CPU (examples/serve_decode.py drives it).

Slot semantics: one shared cache of capacity ``max_len``; per-slot position
offsets are handled by left-padding prompts into the slot at prefill time and
masking finished slots.  A model with SSM layers cannot be left-padded (the
pad tokens would enter the recurrent state), so there every admission must
re-prefill contexts of one length, as closed waves of equal prompts do.
Prefill for a refill batches all newly admitted requests together (prefill
and decode alternate — the standard continuous-batching compromise without
paged attention).

Subclass hooks (``repro.serve.engine.PersonalizedBatcher`` uses all four):
``_build_model`` constructs the jitted steps, ``_model_prefill`` /
``_model_decode`` run them, ``_on_admit`` / ``_on_retire`` bracket a
request's residency in a slot (page-in/pin and release in the personalized
engine).  ``publish_stats`` bridges :class:`ServeStats` into the obs metrics
registry so ``python -m repro.obs.report`` covers the serving path.

Each ``step()`` is traced as ``repro.obs`` spans, recorded by the flight
recorder when it is on and written into the profiler's trace while a
``jax.profiler`` session records::

    serve/step                  the whole tick
      serve/admit               filling free slots (tags new, live)
        serve/prefill           dispatch of the prefill (tags rows, tokens,
                                ssm_state_bytes, kv_cache_bytes)
        serve/fetch             the host's wait for the prefill's tokens
      serve/decode              staging the tokens and dispatching the step
                                (tags live, ssm_fused_layers)
      serve/fetch               the host's wait for the sampled tokens
      serve/emit                appending tokens, stop tests, retirements

The device idles inside ``serve/fetch`` while the token travels to the host,
and elsewhere in ``serve/step`` while the scheduler's own Python runs.  The
programs are named ``jit_prefill`` and ``jit_decode``.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MAMBA
from repro.models.mamba import fused_state_steps
from repro.obs import trace as obs_trace


@dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (L,) int32
    max_new: int = 32
    stop_token: Optional[int] = None
    user_id: Optional[int] = None   # personalized-delta user (None = base)
    generated: List[int] = field(default_factory=list)
    done: bool = False


@dataclass
class ServeStats:
    admitted: int = 0
    completed: int = 0
    decode_steps: int = 0
    prefills: int = 0
    tokens_out: int = 0
    # the batch cache held: SSM layers' recurrent state (state and conv
    # tail) and attention layers' K/V, in bytes
    ssm_state_bytes: int = 0
    kv_cache_bytes: int = 0
    # Mamba layers whose state step the decode program runs through the
    # Pallas kernel (counted when it traces; 0 where the jnp step runs)
    ssm_fused_layers: int = 0


def cache_bytes(cache) -> tuple:
    """(recurrent-state bytes, K/V bytes) of a decode cache, from the
    arrays' shapes (no device sync)."""
    ssm = kv = 0
    for path, a in jax.tree_util.tree_flatten_with_path(cache["layers"])[0]:
        name = getattr(path[-1], "key", None)
        if name in ("ssm", "conv"):
            ssm += a.nbytes
        elif name in ("k", "v"):
            kv += a.nbytes
    return ssm, kv


class ContinuousBatcher:
    """Fixed-slot continuous batching over (prefill, decode_step).

    With ``donate_cache`` the decode step is handed the batch cache to
    write its successor into, in place: no step allocates a second cache
    beside the first, and the cache a step was given is deleted once it
    has been dispatched.  Without it (the default) each step's cache is a
    new array and the old one stays readable."""

    def __init__(self, cfg, params, n_slots: int = 4, max_len: int = 128,
                 donate_cache: bool = False):
        self.cfg = cfg
        self.params = params
        self._recurrent = MAMBA in cfg.layer_kinds()
        self.n_slots = n_slots
        self.max_len = max_len
        self.donate_cache = donate_cache
        self._build_model()
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.cache = None
        self.next_tok = np.zeros((n_slots, 1), np.int32)
        self.stats = ServeStats()

    # -- model hooks (overridden by delta-serving subclasses) ---------------
    def _build_model(self) -> None:
        from repro import models

        def prefill(p, b):
            return models.prefill(p, self.cfg, b, cache_len=self.max_len)

        def decode(p, t, c):
            with fused_state_steps() as fused:
                out = models.decode_step(p, self.cfg, t, c)
            self.stats.ssm_fused_layers = sum(n for n, in fused)
            return out

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode,
                               donate_argnums=2 if self.donate_cache else ())

    def _model_prefill(self, batch):
        return self._prefill(self.params, batch)

    def _model_decode(self, tok):
        return self._decode(self.params, tok, self.cache)

    def _on_admit(self, slot: int, req: Request) -> None:
        """A request was just placed into ``slot`` (before its prefill)."""

    def _on_retire(self, slot: int, req: Request) -> None:
        """``req`` in ``slot`` just finished (stop token or max_new)."""

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)
        self.stats.admitted += 1

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None or r.done]

    def _admit(self) -> None:
        """Fill free slots from the queue with one batched prefill.

        All current slots are re-prefilled together (left-padded to a common
        length) — cache capacity is shared, so a refill rebuilds the batch
        cache; running requests keep their full context (prompt+generated)."""
        free = self._free_slots()
        if not free or not self.queue:
            return
        if self._recurrent:
            self._check_unpadded(free)
        with obs_trace.span("serve/admit") as sp:
            n_new = 0
            for i in free:
                if not self.queue:
                    break
                self.slots[i] = self.queue.popleft()
                self._on_admit(i, self.slots[i])
                n_new += 1
            live = [(i, r) for i, r in enumerate(self.slots)
                    if r is not None and not r.done]
            sp.tag(new=n_new, live=len(live))
            if not live:
                return
            ctxs = [np.concatenate([r.prompt,
                                    np.asarray(r.generated, np.int32)])
                    for _, r in live]
            maxlen = max(len(c) for c in ctxs)
            batch_tokens = np.zeros((self.n_slots, maxlen), np.int32)
            for (i, r), c in zip(live, ctxs):
                batch_tokens[i, maxlen - len(c):] = c
            batch = {"tokens": jnp.asarray(batch_tokens)}
            if self.cfg.enc_layers:
                batch["src_embeds"] = jnp.zeros(
                    (self.n_slots, 8, self.cfg.enc_d_model or self.cfg.d_model))
            if self.cfg.vision_tokens:
                batch["vision_embeds"] = jnp.zeros(
                    (self.n_slots, self.cfg.vision_tokens, self.cfg.d_model))
            # the prefill rebuilds the whole batch cache: free the old one
            # first, so that the two are never on the device together
            self.cache = None
            with obs_trace.span("serve/prefill", rows=len(live),
                                tokens=int(maxlen)) as pf:
                logits, self.cache = self._model_prefill(batch)
                (self.stats.ssm_state_bytes,
                 self.stats.kv_cache_bytes) = cache_bytes(self.cache)
                pf.tag(ssm_state_bytes=self.stats.ssm_state_bytes,
                       kv_cache_bytes=self.stats.kv_cache_bytes)
            first = jnp.argmax(logits[:, -1, :self.cfg.vocab_size], -1)
            with obs_trace.span("serve/fetch"):
                first = np.asarray(first)
            self.next_tok = first[:, None].astype(np.int32)
            self.stats.prefills += 1

    def _check_unpadded(self, free: List[int]) -> None:
        """Refuse an admission whose re-prefill would left-pad a context:
        the pad tokens would enter the SSM layers' recurrent state, which no
        mask keeps them out of."""
        incoming = list(self.queue)[:len(free)]
        staying = [r for r in self.slots if r is not None and not r.done]
        lengths = sorted({len(r.prompt) + len(r.generated)
                          for r in staying + incoming})
        if len(lengths) > 1:
            raise ValueError(
                f"{self.cfg.name} has SSM layers: re-prefilling contexts of "
                f"{lengths} tokens together would left-pad the shorter ones "
                f"into the recurrent state; admit requests of one context "
                f"length at a time (closed waves)")

    # -- decode --------------------------------------------------------------
    def step(self) -> int:
        """One scheduler tick: admit if possible, then one decode step for all
        live slots. Returns the number of live requests."""
        with obs_trace.span("serve/step"):
            if self._free_slots() and self.queue:
                self._admit()
            live = [i for i, r in enumerate(self.slots)
                    if r is not None and not r.done]
            if not live or self.cache is None:
                return 0
            with obs_trace.span("serve/decode", live=len(live)) as sp:
                logits, self.cache = self._model_decode(
                    jnp.asarray(self.next_tok))
                sp.tag(ssm_fused_layers=self.stats.ssm_fused_layers)
            nxt = jnp.argmax(logits[:, -1, :self.cfg.vocab_size], -1)
            with obs_trace.span("serve/fetch"):
                nxt = np.asarray(nxt)
            self.stats.decode_steps += 1
            with obs_trace.span("serve/emit"):
                for i in live:
                    r = self.slots[i]
                    tok = int(nxt[i])
                    r.generated.append(tok)
                    self.stats.tokens_out += 1
                    if (r.stop_token is not None and tok == r.stop_token) or \
                            len(r.generated) >= r.max_new:
                        r.done = True
                        self.stats.completed += 1
                        self._on_retire(i, r)
            self.next_tok = nxt[:, None].astype(np.int32)
            return len([i for i in live if not self.slots[i].done])

    def run(self, max_ticks: int = 1000) -> ServeStats:
        for _ in range(max_ticks):
            self.step()
            if not self.queue and all(r is None or r.done for r in self.slots):
                break
        self.publish_stats()
        return self.stats

    # -- observability --------------------------------------------------------
    def publish_stats(self, metrics=None) -> ServeStats:
        """Bridge ServeStats into the obs metrics registry (serve/* gauges)."""
        if metrics is None:
            from repro.obs.metrics import registry as metrics
        metrics.observe_serve(self.stats)
        return self.stats
