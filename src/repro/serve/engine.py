"""Serving engine: prefill + decode with continuous batching.

Slots hold independent sequences; each decode step advances every active
slot by one token (per-slot cache positions via the vectorized ``index``
path in layers.attention_decode).  New requests are prefilled (batch-1)
into free slots without stopping the decode loop — the standard
continuous-batching discipline, here for the dense/vlm families the
LIDC serving endpoints expose.

The engine is the cluster-resident executor of the serving plane
(:mod:`repro.serve.plane`): requests carry per-request ``max_new`` and
``priority`` (admission order under slot pressure), and a request's
decode state can be exported as a *named KV checkpoint*
(:meth:`kv_checkpoint`) and restored into a fresh engine on another
cluster (:meth:`restore`) — greedy decode then continues bit-identically,
which is what makes mid-stream cluster failover invisible to clients.

An engine given a ``device`` keeps its weights and KV cache there, so one
process can run one engine per chip.  ``record_logits=n`` keeps each
request's first ``n`` next-token logit rows (prefill, then decode steps)
on the host, for comparison against a reference implementation.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models.model import bundle_for

__all__ = ["Request", "ServeEngine", "UnsupportedFamilyError",
           "SUPPORTED_FAMILIES"]

# model families the continuous-batching engine can decode; serving
# endpoints advertise exactly this set in their capability record, so the
# network validates family fit *before* placement instead of the engine
# dying after it
SUPPORTED_FAMILIES = ("dense", "vlm")


class UnsupportedFamilyError(ValueError):
    """The engine cannot serve this model family (e.g. moe/hybrid)."""

    def __init__(self, family: str):
        self.family = family
        super().__init__(
            f"continuous batching engine supports families "
            f"{SUPPORTED_FAMILIES}, not {family!r}")


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    eos: Optional[int] = None
    priority: int = 0
    out: List[int] = field(default_factory=list)
    done: bool = False
    logits: List[np.ndarray] = field(default_factory=list)


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 4,
                 max_seq: int = 256, greedy: bool = True, device=None,
                 record_logits: int = 0):
        if cfg.family not in SUPPORTED_FAMILIES:
            raise UnsupportedFamilyError(cfg.family)
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.record_logits = record_logits
        bundle = bundle_for(cfg)
        self._decode = jax.jit(
            lambda p, c, t: bundle.decode_step(cfg, p, c, t))
        self._prefill = jax.jit(
            lambda p, t: bundle.prefill(cfg, p, t, max_seq=max_seq),
            static_argnames=())
        cache = bundle.init_cache(cfg, max_batch, max_seq)
        # vectorized per-slot positions
        cache["index"] = jnp.zeros((max_batch,), jnp.int32)
        if device is not None:
            params, cache = jax.device_put((params, cache), device)
        self.params = params
        self.cache = cache
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.last_tokens = np.zeros((max_batch, 1), np.int32)
        self.queue: List[Request] = []
        self._rid = 0
        self.decode_steps = 0
        self.tokens_out = 0

    # -- API -----------------------------------------------------------------
    def submit(self, prompt: List[int], max_new: int = 16,
               eos: Optional[int] = None, priority: int = 0) -> Request:
        self._rid += 1
        req = Request(rid=self._rid, prompt=list(prompt), max_new=max_new,
                      eos=eos, priority=priority)
        if max_new <= 0:
            # nothing to decode: finished at submission, never takes a slot
            req.done = True
            return req
        self.queue.append(req)
        return req

    def run(self, max_steps: int = 10_000) -> List[Request]:
        done: List[Request] = []
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            done.extend(self._admit())
            finished = self.step()
            done.extend(finished)
            steps += 1
        return done

    # -- internals --------------------------------------------------------------
    def _admit(self) -> List[Request]:
        """Fill free slots from the queue in priority order (stable within
        a class).  Returns requests that finished *at prefill* (max_new
        reached or EOS on the first token) — their slot frees immediately,
        so a queued request can take it the same step."""
        finished: List[Request] = []
        for i in range(self.max_batch):
            while self.slots[i] is None and self.queue:
                self.queue.sort(key=lambda r: (-r.priority, r.rid))
                req = self.queue.pop(0)
                self._prefill_into_slot(i, req)
                if req.done:
                    finished.append(req)
        return finished

    def _prefill_into_slot(self, slot: int, req: Request) -> None:
        toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
        logits, c1 = self._prefill(self.params, toks)
        # copy the single-row cache into the slot
        self.cache["k"] = self.cache["k"].at[:, slot].set(c1["k"][:, 0])
        self.cache["v"] = self.cache["v"].at[:, slot].set(c1["v"][:, 0])
        self.cache["index"] = self.cache["index"].at[slot].set(
            len(req.prompt))
        nxt = int(jnp.argmax(logits[0, -1]))
        if self.record_logits:
            req.logits.append(np.asarray(logits[0, -1], np.float32))
        req.out.append(nxt)
        self.tokens_out += 1
        self.last_tokens[slot, 0] = nxt
        self.slots[slot] = req
        if (len(req.out) >= req.max_new
                or (req.eos is not None and nxt == req.eos)):
            # budget exhausted (or EOS) on the prefill token itself: the
            # request never enters the decode loop and its slot is free
            # for the next queued request this very step
            req.done = True
            self.slots[slot] = None
            self.cache["index"] = self.cache["index"].at[slot].set(0)

    def step(self) -> List[Request]:
        """One decode step for all active slots."""
        if not any(s is not None for s in self.slots):
            return []
        tokens = jnp.asarray(self.last_tokens)
        logits, self.cache = self._decode(self.params, self.cache, tokens)
        nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1), np.int32)
        rows = None
        if any(r is not None and len(r.logits) < self.record_logits
               for r in self.slots):
            rows = np.asarray(logits[:, 0, :], np.float32)
        self.decode_steps += 1
        finished: List[Request] = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(nxt[i])
            if rows is not None and len(req.logits) < self.record_logits:
                req.logits.append(rows[i])
            req.out.append(tok)
            self.tokens_out += 1
            self.last_tokens[i, 0] = tok
            full = len(req.prompt) + len(req.out) >= self.max_seq - 1
            if (len(req.out) >= req.max_new or full
                    or (req.eos is not None and tok == req.eos)):
                req.done = True
                finished.append(req)
                self.slots[i] = None
                self.cache["index"] = self.cache["index"].at[i].set(0)
        return finished

    # -- named KV checkpoint / restore ----------------------------------------
    def kv_checkpoint(self, req: Request) -> Dict[str, Any]:
        """Export a live request's decode state for publication as named
        Data: the used span of its per-slot KV cache plus the token
        context.  :meth:`restore` on *another* engine (another cluster)
        continues greedy decode bit-identically from this state."""
        slot = self.slots.index(req)
        used = int(self.cache["index"][slot])
        return {
            "k": np.asarray(self.cache["k"][:, slot, :used]),
            "v": np.asarray(self.cache["v"][:, slot, :used]),
            "prompt": list(req.prompt),
            "out": list(req.out),
            "max_new": req.max_new,
            "eos": req.eos,
            "priority": req.priority,
        }

    def restore(self, state: Dict[str, Any]) -> Request:
        """Re-create a checkpointed request in a free slot of this engine.

        The imported KV covers ``prompt + out[:-1]`` (the cache index at
        checkpoint time); the last emitted token is re-fed as the decode
        input, exactly as it would have been on the original cluster.
        """
        try:
            slot = self.slots.index(None)
        except ValueError:
            raise RuntimeError("no free slot to restore into") from None
        k = np.asarray(state["k"])
        used = k.shape[1]
        if used > self.max_seq:
            raise ValueError(f"checkpoint spans {used} > max_seq={self.max_seq}")
        self._rid += 1
        req = Request(rid=self._rid, prompt=list(state["prompt"]),
                      max_new=int(state["max_new"]), eos=state.get("eos"),
                      priority=int(state.get("priority", 0)),
                      out=list(state["out"]))
        self.cache["k"] = self.cache["k"].at[:, slot, :used].set(
            jnp.asarray(k))
        self.cache["v"] = self.cache["v"].at[:, slot, :used].set(
            jnp.asarray(np.asarray(state["v"])))
        self.cache["index"] = self.cache["index"].at[slot].set(used)
        self.last_tokens[slot, 0] = int(req.out[-1])
        self.slots[slot] = req
        return req
