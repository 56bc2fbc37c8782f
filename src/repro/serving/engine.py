"""Continuous-batching serve engine over a paged KV cache
(docs/continuous-batching.md).

``ContinuousBatchingEngine`` turns the one-shot ``generate()`` path into
a per-step admit/decode/retire loop: requests of mixed prompt/output
lengths share a fixed pool of decode slots and KV pages, new requests
are prefilled (batch-1) and paged in the moment a slot and pages free
up, and finished requests release both immediately.

Token identity with the contiguous path is the load-bearing contract:
admission reuses the REAL prefill program (never prefill-as-decode),
page-in copies the exact prefill rows, and the paged decode step
(``make_paged_serve_step``) reconstructs bitwise the contiguous cache
state before every token — so each request's greedy tokens equal
``launch.serve.generate()`` run at the same ``max_len``, token for
token (asserted in tests/test_serving_engine.py and the --trace
benchmark headline).  On a TPU v5e that held for a one-slot engine; with
four slots some requests took a different greedy token part-way, and
``generate()`` at batch 4 differs from batch 1 as well, so the multi-slot
program's rounding differs from the batch-1 one.  ``chip_smoke.py``
requires each such first difference to be a near-tie of the prefill's
logits.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from repro.serving.pages import (PagedKvAllocator, classify_cache_tree,
                                 data_pages, init_paged_state, pages_for,
                                 paged_state_bytes)
from repro.serving.scheduler import ContinuousScheduler, ServeRequest


class ContinuousBatchingEngine:
    """Drive a paged decode step under the continuous scheduler.

    Parameters mirror ``make_serve_step`` plus the paged knobs:
    ``slots`` decode rows ride every step, ``max_len`` is the global
    decode horizon (every request's rows + max_new must fit it), and
    ``page_size`` (``plan.page_size`` when tuned) carves each slot's KV
    into ``max_len / page_size`` pages.  ``watermark`` reserves free
    pages at admission so in-flight decodes can extend without instant
    preemption (default: one page per slot, clamped to the pool).
    """

    def __init__(self, model, params, plan=None, mesh=None, *, slots: int,
                 max_len: int, page_size: int,
                 watermark: Optional[int] = None,
                 max_admits_per_step: int = 1, lowered=None):
        import jax.numpy as jnp
        from repro.lowering import lower_plan
        from repro.training.step import (make_paged_serve_step,
                                         make_prefill_step)
        if lowered is None and (plan is None or mesh is None):
            raise ValueError("ContinuousBatchingEngine needs either "
                             "lowered= or (plan, mesh)")
        low = lowered or lower_plan(model.cfg, None, plan, mesh)
        self.model, self.params, self.low = model, params, low
        self.slots, self.max_len = int(slots), int(max_len)
        self.page_size = int(page_size)
        self.kv8 = low.plan.kv_cache_dtype == "int8"
        self._cache_dtype = jnp.int8 if self.kv8 else jnp.bfloat16

        self.specs = classify_cache_tree(model.init_caches, self.slots,
                                         self.max_len, self._cache_dtype)
        self.npp = self.max_len // self.page_size
        self.n_data_pages = data_pages(self.slots, self.max_len,
                                       self.page_size)
        self.trash_page = self.n_data_pages
        wm = (min(self.slots, self.n_data_pages - 1) if watermark is None
              else watermark)
        self.allocator = PagedKvAllocator(num_pages=self.n_data_pages,
                                          page_size=self.page_size,
                                          watermark=wm)
        self.sched = ContinuousScheduler(
            slots=self.slots, allocator=self.allocator,
            max_admits_per_step=max_admits_per_step)

        self.state, bt = init_paged_state(
            model.init_caches, self.specs, self.slots, self.max_len,
            self.page_size, self._cache_dtype)
        self.block_table = np.array(bt)   # mutable host copy; (slots, npp)
        self._prefill = make_prefill_step(model, return_cache=True,
                                          lowered=low)
        self._step = make_paged_serve_step(
            model, slots=self.slots, max_len=self.max_len,
            page_size=self.page_size, lowered=low)
        self._tokens = np.zeros((self.slots, 1), np.int32)
        self.results: Dict[Any, List[int]] = {}
        self.steps_run = 0
        self._rid_seq = 0

    # -- public API -----------------------------------------------------------

    def submit(self, prompt: Dict[str, Any], max_new: int,
               rid: Any = None) -> Any:
        """Queue one request; returns its id."""
        if rid is None:
            rid = self._rid_seq
            self._rid_seq += 1
        self.sched.submit(ServeRequest(rid=rid, prompt=prompt,
                                       max_new=int(max_new)))
        return rid

    def run(self) -> Dict[Any, np.ndarray]:
        """Drive the loop until every submitted request retires; returns
        {rid: generated token ids} (length max_new each)."""
        while self.sched.has_work():
            admitted = self._admission_pass()
            self._coverage_pass()
            if not self.sched.active:
                if self.sched.waiting and not admitted:
                    head = self.sched.waiting[0]
                    raise RuntimeError(
                        f"request {head.rid!r} cannot be admitted with an "
                        f"idle engine: pool of {self.n_data_pages} pages x "
                        f"{self.page_size} rows (watermark "
                        f"{self.allocator.watermark}) is too small")
                continue   # everything retired at admission (max_new == 1)
            self._decode_step()
        return {rid: np.asarray(toks, np.int32)
                for rid, toks in self.results.items()}

    def memory_bytes(self) -> int:
        """Exact bytes of the engine's cache state (pools + slot tree +
        block table) — the contract tests compare this bitwise against
        ``concrete_paged_cache_bytes`` at dp == tp == 1."""
        import jax.numpy as jnp
        return paged_state_bytes(self.state,
                                 jnp.asarray(self.block_table))

    # -- scheduling passes ----------------------------------------------------

    def _admission_pass(self) -> int:
        admitted = 0
        while (admitted < self.sched.max_admits_per_step
               and self.sched.can_try_admit()):
            req = self.sched.waiting[0]
            if req.prefilled is None:
                req.prefilled = self._run_prefill(req)
            first_tok, caches, rows = req.prefilled
            if rows + req.max_new > self.max_len:
                raise ValueError(
                    f"request {req.rid!r}: {rows} prompt rows + "
                    f"{req.max_new} new tokens exceed max_len "
                    f"{self.max_len}")
            if self.sched.peak_pages(rows, req.max_new) > self.n_data_pages:
                raise ValueError(
                    f"request {req.rid!r} needs more pages than the "
                    f"whole pool at page_size {self.page_size}")
            # the watermark reserve only makes sense with decodes in
            # flight; an idle engine admits on raw free pages
            idle = not self.sched.active and not admitted
            if not self.allocator.can_admit(rows + 1,
                                            ignore_watermark=idle):
                break                      # pages below watermark: wait
            slot = self.sched.admit(req, rows, ignore_watermark=idle)
            req.prefilled = None           # drop the stashed cache tree
            self.results[req.rid] = []     # preemption replay starts over
            self._install(slot, req.rid, caches, rows)
            self._record_token(slot, first_tok)
            admitted += 1
        return admitted

    def _coverage_pass(self) -> None:
        for slot in self.sched.active_slots():
            if slot not in self.sched.active:
                continue                   # preempted below us this pass
            while True:
                got = self.sched.ensure_coverage(slot)
                if got is not None:
                    if got:
                        self._sync_block_row(slot)
                    break
                victim = self.sched.preempt_youngest()
                self._clear_slot(victim)
                if victim == slot:
                    break                  # we were the youngest: requeued

    def _decode_step(self) -> None:
        import jax.numpy as jnp
        logits, self.state = self._step.fn(
            self.params, jnp.asarray(self._tokens), self.state,
            jnp.asarray(self.block_table))
        # greedy argmax on device — the same op the static path runs
        toks = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                          np.int32)
        self.steps_run += 1
        for slot in self.sched.active_slots():
            st = self.sched.active[slot]
            st.pos += 1                    # mirrors the in-step pos + 1
            self._record_token(slot, int(toks[slot]))

    # -- device-state plumbing ------------------------------------------------

    def _run_prefill(self, req: ServeRequest):
        """Batch-1 prefill (+ int8 quantization under int8 plans); returns
        (first greedy token, cache tree, cache rows)."""
        import jax
        import jax.numpy as jnp
        from repro.models.zoo import quantize_caches
        logits, caches = self._prefill.fn(self.params, req.prompt)
        if self.kv8:
            caches = quantize_caches(caches)
        first = int(jnp.argmax(logits[0, -1]))
        rows = None
        flat = jax.tree.leaves(caches)
        for leaf, spec in zip(flat, self.specs):
            if spec.paged:
                rows = int(leaf.shape[spec.bdim + 1])
                break
            if rows is None and spec.is_pos:
                rows = int(np.asarray(leaf).reshape(-1)[0])
        if rows is None:                   # pure-state families (SSM)
            rows = int(req.prompt["tokens"].shape[1])
        return first, caches, rows

    def _install(self, slot: int, rid, caches, rows: int) -> None:
        """Page prefill KV into the owned pages and copy slot-resident
        state (+ per-request pos) into decode row ``slot``."""
        import jax
        import jax.numpy as jnp
        pages = self.allocator.pages(rid)
        flat = jax.tree.leaves(self.state)
        pflat = jax.tree.leaves(caches)
        out = []
        for leaf, pleaf, spec in zip(flat, pflat, self.specs):
            if spec.paged:
                out.append(self._page_in(leaf, pleaf, pages, rows))
            elif spec.is_pos:
                out.append(leaf.at[..., slot].set(rows))
            elif spec.bdim is not None:
                val = jnp.take(pleaf, 0, axis=spec.bdim)
                ix = (slice(None),) * spec.bdim + (slot,)
                out.append(leaf.at[ix].set(val.astype(leaf.dtype)))
            else:                                    # pragma: no cover
                out.append(leaf)
        self.state = jax.tree.unflatten(jax.tree.structure(self.state),
                                        out)
        self._sync_block_row(slot)

    def _page_in(self, pool, pleaf, pages, rows: int):
        import jax.numpy as jnp
        ps = self.page_size
        n_used = pages_for(rows, ps)
        if n_used == 0:
            return pool
        lead, tail = pool.shape[0], pool.shape[3:]
        x = jnp.take(pleaf, 0, axis=1)               # (lead, rows, *tail)
        pad = n_used * ps - rows
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((lead, pad) + tail, x.dtype)], axis=1)
        x = x.reshape((lead, n_used, ps) + tail)
        ids = jnp.asarray(pages[:n_used], jnp.int32)
        return pool.at[:, ids].set(x)

    def _sync_block_row(self, slot: int) -> None:
        st = self.sched.active[slot]
        pages = self.allocator.pages(st.rid)
        row = np.full((self.npp,), self.trash_page, np.int32)
        row[:len(pages)] = pages
        self.block_table[slot] = row

    def _clear_slot(self, slot: int) -> None:
        """Neutralize a freed slot: all-trash block table (its in-step
        writes land on the trash page), pos = 0 (its gathered rows mask
        to zero), token 0."""
        import jax
        self.block_table[slot] = self.trash_page
        self._tokens[slot] = 0
        flat = jax.tree.leaves(self.state)
        out = [leaf.at[..., slot].set(0) if spec.is_pos else leaf
               for leaf, spec in zip(flat, self.specs)]
        self.state = jax.tree.unflatten(jax.tree.structure(self.state),
                                        out)

    def _record_token(self, slot: int, tok: int) -> None:
        st = self.sched.active[slot]
        self.results[st.rid].append(tok)
        st.emitted += 1
        self._tokens[slot] = tok
        if st.emitted >= st.max_new:
            self.sched.retire(slot)
            self._clear_slot(slot)
