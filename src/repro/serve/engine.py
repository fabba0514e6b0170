"""Continuous-batching serve engine over the paged decode path (ISSUE 7).

One jitted ``paged_decode_step`` serves a fixed grid of ``n_slots`` decode
slots; everything dynamic — admission, prefill progress, sampling, EOS/
max-token eviction, page allocation — happens on the host between steps, so
new requests join a RUNNING batch without retracing (the shapes never
change).  Prefill rides the decode path one token per step ("chunked
prefill" with chunk=1): a slot still consuming its prompt feeds the next
prompt token instead of a sampled one and its logits are ignored until the
prompt is exhausted, which is what lets prefill and decode mix freely in
the same batch.

Slot lifecycle:  FREE -> (admit) -> PREFILL -> DECODE -> (EOS | max-tokens)
-> evict -> FREE.  Eviction returns the slot's pages to the allocator,
zeroes its page-table row (pointing it back at the scratch page) and resets
any recurrent per-slot cache state (``api.reset_slot``); the pages' stale
contents are never read because length masks exclude them — recycling costs
zero device work beyond that reset.

Admission policies:
  * ``continuous`` — a request is admitted the moment a slot is free (the
    tentpole path);
  * ``static`` — the serve_batched.py baseline: admit a full batch only
    when EVERY slot is free, then run it to completion (head-of-line
    blocking: early finishers idle until the longest request drains).  The
    benchmark pits the two against the same Poisson arrival stream.

If the page pool runs dry mid-flight the affected slot STALLS: it is not
advanced (its token is re-fed next step), its masked write lands in the
scratch page, and it resumes as soon as an eviction frees pages.  Slots
that must not make progress this step — FREE slots and page-stalled ones —
are excluded from the ``advance`` mask passed to ``paged_decode_step``, so
their recurrent per-slot state (mamba conv/ssm, xLSTM C/n/m) stays bitwise
frozen; the scratch page covers only the attention K/V write.  As a second
line of defense ``reset_slot`` runs at admission as well as at eviction.
If EVERY active slot is stalled the engine raises :class:`OutOfPages`
instead of spinning: pages are only ever freed by an eviction, an eviction
requires some slot to advance, so an all-stalled step can never make
progress again (size ``n_pages`` for the expected concurrency instead).

MoE: every MoE model runs ``moe_forward``, which drops no token, so what
a request gets back does not depend on the other requests in its batch.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import List, Optional

import jax
import numpy as np

from .paging import OutOfPages, PageAllocator

FREE, PREFILL, DECODE = "free", "prefill", "decode"


def _jitted(fn):
    """Many engines over one ModelAPI compile once: the jitted step is
    cached as an attribute of the underlying ``paged_decode_step``
    callable itself, so it lives exactly as long as the model API does
    (no global registry to leak across models)."""
    cached = getattr(fn, "_serve_jitted", None)
    if cached is None:
        cached = jax.jit(fn, donate_argnums=(1,))
        fn._serve_jitted = cached
    return cached


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    arrival_step: int = -1
    first_token_step: int = -1
    finish_step: int = -1

    @property
    def done(self) -> bool:
        return self.finish_step >= 0


class _Slot:
    __slots__ = ("index", "state", "req", "pos")

    def __init__(self, index: int):
        self.index = index
        self.state = FREE
        self.req: Optional[Request] = None
        self.pos = 0          # tokens fed into the cache so far


class ServeEngine:
    def __init__(self, api, params, *, n_slots: int = 4, page_size: int = 16,
                 max_len: int = 128, n_pages: Optional[int] = None,
                 admission: str = "continuous"):
        assert api.has_paged, f"{api.cfg.name}: family has no paged decode"
        assert admission in ("continuous", "static"), admission
        self.api = api
        self.params = params
        self.page_size = page_size
        self.max_pages = -(-max_len // page_size)
        self.max_len = self.max_pages * page_size
        self.n_slots = n_slots
        self.admission = admission
        # default pool: every slot can hold a full-length request (+scratch)
        self.n_pages = n_pages or 1 + n_slots * self.max_pages
        self.alloc = PageAllocator(self.n_pages)
        self.cache = api.init_paged_cache(params, n_slots, self.n_pages,
                                          page_size)
        self.page_table = np.zeros((n_slots, self.max_pages), np.int32)
        self.slots = [_Slot(i) for i in range(n_slots)]
        self.queue: deque = deque()
        self._step_fn = _jitted(api.paged_decode_step)
        self._next_rid = 0
        self.step_count = 0       # the engine clock (idle ticks included)
        self.real_steps = 0       # steps that actually ran the model
        self.generated_total = 0
        self.stall_events = 0

    # ------------------------------------------------------------- intake --
    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None) -> Request:
        prompt = [int(t) for t in prompt]
        assert prompt, "empty prompt"
        need = len(prompt) + max_new_tokens
        assert need <= self.max_len, (
            f"request needs {need} tokens > max_len {self.max_len} "
            "(the paged cache does not wrap)")
        req = Request(rid=self._next_rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      arrival_step=self.step_count)
        self._next_rid += 1
        self.queue.append(req)
        return req

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s.state != FREE for s in self.slots)

    @property
    def active_slots(self) -> int:
        return sum(s.state != FREE for s in self.slots)

    # ---------------------------------------------------------- scheduling --
    def _admit(self) -> None:
        free = [s for s in self.slots if s.state == FREE]
        if self.admission == "static" and len(free) < self.n_slots:
            return                       # head-of-line: wait for the batch
        for slot in free:
            if not self.queue:
                break
            slot.req = self.queue.popleft()
            slot.pos = 0
            slot.state = PREFILL
            # defense in depth vs eviction-time reset: a recycled slot must
            # start from pristine recurrent state no matter what ran (or
            # idled) in it since the last eviction
            if self.api.reset_slot is not None:
                self.cache = self.api.reset_slot(self.cache, slot.index)

    def _ensure_page(self, slot: _Slot) -> bool:
        """Allocate the page slot.pos falls in, if not already owned.
        Returns False (stall) when the pool is dry."""
        if slot.pos % self.page_size:
            return True
        pidx = slot.pos // self.page_size
        if self.page_table[slot.index, pidx]:
            return True
        try:
            self.page_table[slot.index, pidx] = self.alloc.alloc()
            return True
        except OutOfPages:
            self.stall_events += 1
            return False

    def _evict(self, slot: _Slot) -> None:
        row = self.page_table[slot.index]
        self.alloc.free(row[row > 0])
        row[:] = 0
        if self.api.reset_slot is not None:
            self.cache = self.api.reset_slot(self.cache, slot.index)
        slot.req = None
        slot.pos = 0
        slot.state = FREE

    # -------------------------------------------------------------- stepping --
    def idle_tick(self) -> None:
        """Advance the engine clock without touching the device (used by
        open-loop drivers to fast-forward between arrivals)."""
        self.step_count += 1

    def warmup(self) -> None:
        """Compile the step function before any request is admitted (all
        writes land in the scratch page; the all-False advance mask keeps
        every slot's recurrent state bitwise untouched)."""
        S = self.n_slots
        import jax.numpy as jnp
        logits, self.cache = self._step_fn(
            self.params, self.cache, jnp.zeros((S, 1), jnp.int32),
            jnp.zeros((S,), jnp.int32), jnp.asarray(self.page_table),
            jnp.zeros((S,), bool))
        jax.block_until_ready(logits)        # lint: allow-host-sync (warmup)

    def step(self) -> int:
        """One engine step: admit, run the fused decode, sample, evict.
        Returns the number of tokens generated this step (0 on an idle
        step, which still advances the clock)."""
        self._admit()
        active = [s for s in self.slots if s.state != FREE]
        if not active:
            self.step_count += 1
            return 0

        S = self.n_slots
        tokens = np.zeros((S, 1), np.int32)
        positions = np.zeros((S,), np.int32)
        adv_mask = np.zeros((S,), bool)
        advance = []
        for slot in active:
            if not self._ensure_page(slot):
                positions[slot.index] = slot.pos   # stalled: re-fed later;
                continue                           # write -> scratch page
            req = slot.req
            if slot.pos < len(req.prompt):
                tokens[slot.index, 0] = req.prompt[slot.pos]
            else:
                tokens[slot.index, 0] = req.generated[-1]
            positions[slot.index] = slot.pos
            adv_mask[slot.index] = True
            advance.append(slot)

        if not advance:
            # every active slot is page-stalled.  Pages are only freed by
            # evictions and an eviction needs some slot to advance, so no
            # step can ever make progress again — fail fast rather than
            # burn device steps until the run() wedge assert.
            raise OutOfPages(
                f"deadlock: all {len(active)} active slot(s) stalled on an "
                f"exhausted pool of {self.n_pages - 1} page(s) and no "
                "eviction can free any; size n_pages for the expected "
                "concurrency")

        import jax.numpy as jnp
        logits, self.cache = self._step_fn(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(self.page_table),
            jnp.asarray(adv_mask))
        # the engine's ONE sync per step: sampling needs the logits on host
        lg = np.asarray(
            logits[:, 0, :self.api.cfg.vocab])   # lint: allow-host-sync

        made = 0
        for slot in advance:
            req = slot.req
            slot.pos += 1
            if slot.pos < len(req.prompt):
                continue                           # still prefilling
            if slot.state == PREFILL:
                slot.state = DECODE
            tok = int(np.argmax(lg[slot.index]))
            req.generated.append(tok)
            made += 1
            if req.first_token_step < 0:
                req.first_token_step = self.step_count
            if ((req.eos_id is not None and tok == req.eos_id)
                    or len(req.generated) >= req.max_new_tokens):
                req.finish_step = self.step_count
                self._evict(slot)
        self.generated_total += made
        self.step_count += 1
        self.real_steps += 1
        return made

    def run(self, max_steps: int = 100_000) -> None:
        """Drain the queue and all active slots (closed-loop drivers)."""
        while self.has_work:
            self.step()
            assert self.step_count < max_steps, "serve engine wedged"

    # --------------------------------------------------------------- weights --
    def set_params(self, params) -> None:
        """Hot-swap served weights (consensus-view snapshots): same shapes,
        so the compiled step is reused — no retrace."""
        self.params = params
