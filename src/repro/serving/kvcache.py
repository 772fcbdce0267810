"""First-class KV-cache API for the serving engine.

Everything the schedulers used to do to cache dicts by hand — allocation
(``model.init_cache``), the decode-position clock (``cache["pos"]``
poking), admission side caches, row scatter (``admit_rows``) — is owned
here, behind one interface with two backends:

* :class:`ContiguousKVCache` — the original layout: ONE ``max_slots``-row
  cache, slot = cache row, a single scalar clock shared by every slot,
  prompts left-padded to the clock at admission. It wraps the exact same
  jitted calls the scheduler used to make, so it is the bit-exactness
  oracle (and the trace-count behavior is unchanged).

* :class:`PagedKVCache` — vLLM-style paging: K/V live in a pool of
  fixed-size blocks; each slot reaches its tokens through a per-slot
  block table, with a free-block pool, refcounts, and copy-on-write.
  Prompts are *not* left-padded: slot ``b``'s tokens sit at absolute
  positions ``0..L-1`` with a per-slot length vector as the clock, which
  removes the contiguous backend's ``clock + max_new <= max_len``
  admission horizon (a long-budget request no longer has to fit under the
  shared clock — only under its own ``prompt + max_new <= max_len``).

Shared-prefix reuse (paged): admitted prompts are chain-hashed at block
granularity; full blocks whose hash (and content — hashes are verified
against stored tokens) matches a registered block are *shared* into the
new slot's table with a refcount bump, and the admission prefill shrinks
to the unshared suffix (a ``prefill_chunk`` continuation over the
gathered prefix, bit-identical to the monolithic prefill by the chunked-
prefill equivalence). A partially-filled tail block can also be shared
for its values; any write into a block with live sharers triggers
copy-on-write — the slot gets a private physical block before the write
lands (``cow_copies`` counts these). Retired slots' blocks drop their
refs; registered blocks park in a reclaimable cached set (evicted FIFO
when the free list runs dry) instead of being freed, so one 512-token
system prompt prefills once across thousands of short turns.

Physical block 0 is reserved as the *trash block*: retired slots' table
rows point at it, so the lockstep decode batch (which writes one K/V row
per slot unconditionally) can never corrupt a live block.

Admission reserves the full ``ceil((len(prompt) + max_new) / block_size)``
block budget up front (allocated lazily as decode crosses block
boundaries), so a request that is admitted can always finish: pool
exhaustion surfaces as admission backpressure, never as a mid-decode
failure.

Chunked admission (``prefill_chunk > 0``) splits the paged admission into
a multi-step lifecycle driven by the scheduler's ``PagedPendingPrefill``:
``reserve_pending`` (full block budget outstanding before the first
chunk), ``begin_chunked_admit`` (prefix pin + gather — pins happen before
any chunk so mid-admission FIFO eviction can't recycle a block about to
be read), N ``prefill_chunk`` continuations on the 1-row side cache while
residents decode, then ``complete_chunked_admit`` (the same alloc/COW/
scatter/register commit as monolithic admission, at the slot's own prompt
length) — or ``abandon_chunked_admit`` on a force-swap, which unpins and
releases the reservation.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import positions_walked

# host spans on the profiler's clock (free while no trace is being taken)
_span = jax.profiler.TraceAnnotation

TRASH = 0   # reserved physical block: write target for retired slots

# layer-state entries: leading dense layers and periods (batch at axis 0),
# scan-stacked periods (batch at axis 1)
LAYER_KEYS = ("lead", "list", "periods")

__all__ = ["KVCache", "ContiguousKVCache", "PagedKVCache", "admit_rows"]


# ---------------------------------------------------------------------------
# jitted cache ops (pure functions; the engine wraps them in trace counters)
# ---------------------------------------------------------------------------

def admit_rows(pool, tmp, pool_logits, tmp_logits, idx):
    """Scatter a ``k``-row prefill cache + its last-token logits into the
    ``max_slots``-row pool at slot indices ``idx``.

    Cache leaves are batch-leading except scan-stacked period caches
    (``(periods, batch, ...)`` — batch at axis 1) and the scalar ``pos``,
    which the admission prefill computed for the new clock and which simply
    replaces the pool's (both equal the clock while slots are in flight; on
    a fresh wave it rewinds the pool).
    """
    out = dict(pool)

    def rows0(a, b):
        return a.at[idx].set(b.astype(a.dtype))

    def rows1(a, b):
        return a.at[:, idx].set(b.astype(a.dtype))

    for key in pool:
        if key == "pos":
            continue
        out[key] = jax.tree_util.tree_map(
            rows1 if key == "periods" else rows0, pool[key], tmp[key])
    out["pos"] = tmp["pos"]
    return out, pool_logits.at[idx].set(tmp_logits.astype(pool_logits.dtype))


def gather_blocks(side, pool, phys):
    """Copy pool blocks ``phys`` (logical order, ``(n,)`` int32) into the
    first ``n * block_size`` positions of the 1-row contiguous ``side``
    cache — the admission-side materialization of a shared prefix."""
    out = dict(side)

    def g0(s, p):                                  # batch-leading leaves
        blk = p[phys]                              # (n, bs, ...)
        flat = blk.reshape((1, -1) + blk.shape[2:]).astype(s.dtype)
        return jax.lax.dynamic_update_slice_in_dim(s, flat, 0, 1)

    def g1(s, p):                                  # (periods, batch, ...)
        blk = p[:, phys]
        flat = blk.reshape((blk.shape[0], 1, -1)
                           + blk.shape[3:]).astype(s.dtype)
        return jax.lax.dynamic_update_slice_in_dim(s, flat, 0, 2)

    for key in LAYER_KEYS:
        if key in pool:
            out[key] = jax.tree_util.tree_map(
                g1 if key == "periods" else g0, side[key], pool[key])
    return out


def scatter_blocks(pool, side, phys, start):
    """Write side-cache positions ``[start*bs, (start+n)*bs)`` into pool
    blocks ``phys`` (``(n,)`` int32; ``start`` may be traced)."""
    out = dict(pool)
    n = phys.shape[0]

    def s0(p, s):
        bs = p.shape[1]
        seg = jax.lax.dynamic_slice_in_dim(s, start * bs, n * bs, 1)
        return p.at[phys].set(
            seg.reshape((n, bs) + seg.shape[2:]).astype(p.dtype))

    def s1(p, s):
        bs = p.shape[2]
        seg = jax.lax.dynamic_slice_in_dim(s, start * bs, n * bs, 2)
        return p.at[:, phys].set(
            seg.reshape((seg.shape[0], n, bs) + seg.shape[3:])
            .astype(p.dtype))

    for key in LAYER_KEYS:
        if key in pool:
            out[key] = jax.tree_util.tree_map(
                s1 if key == "periods" else s0, pool[key], side[key])
    return out


def copy_block(pool, src, dst):
    """Pool-to-pool block copy (decode-time copy-on-write)."""
    out = dict(pool)

    def c0(p):
        return p.at[dst].set(p[src])

    def c1(p):
        return p.at[:, dst].set(p[:, src])

    for key in LAYER_KEYS:
        if key in pool:
            out[key] = jax.tree_util.tree_map(
                c1 if key == "periods" else c0, pool[key])
    return out


# ---------------------------------------------------------------------------
# interface
# ---------------------------------------------------------------------------

class KVCache:
    """Backend-neutral KV-cache state owned on behalf of a scheduler.

    Use :meth:`create` (reads ``ServeConfig.kv_backend``); schedulers talk
    to the returned object and never touch cache dicts or ``cache["pos"]``
    directly — the decode position is the read-only :attr:`clock`.
    """

    backend = "abstract"

    def __init__(self, engine):
        self.eng = engine
        self.cfg = engine.cfg
        self.model = engine.model
        self.max_slots = engine.cfg.max_slots or engine.cfg.max_batch
        self._cache = None            # persistent pool cache (lazy init)
        self._logits = None           # (max_slots, vocab) pending logits
        # admission side caches, keyed by row count and reused across
        # admissions: a fresh allocation per admission owned the admission
        # step's latency at small scales. Stale rows are harmless — every
        # position is rewritten before any masked-in read, and masked
        # columns contribute exact zeros — only the clock is rewound.
        self._side_caches: Dict[int, Any] = {}

    @staticmethod
    def create(engine) -> "KVCache":
        """The one serving entry point for cache construction (unifies the
        old ``models.model.LM.init_cache`` / ``models.transformer.
        init_cache`` call sites and the ``quantize_kv`` flag)."""
        backend = getattr(engine.cfg, "kv_backend", "contiguous")
        if backend == "paged":
            return PagedKVCache(engine)
        return ContiguousKVCache(engine)

    # ------------------------------------------------------------- plumbing
    def fresh(self, rows: int) -> dict:
        """A standalone contiguous cache (the round scheduler's per-round
        cache); replaces direct ``model.init_cache`` calls in serving."""
        return self.model.init_cache(rows, self.cfg.max_len,
                                     quantize_kv=self.cfg.quantize_kv)

    def side_cache(self, k: int) -> dict:
        """A reusable ``k``-row admission cache with the clock rewound.

        Stacks with recurrent state (mamba/rwkv) get a fresh allocation
        every time: their state leaves are read at the first chunk of a
        chunked admission — a retired request's state is not masked out
        the way stale KV rows are, so reuse would leak it into the new
        request's recurrence."""
        if self.model.has_recurrent_state():
            return self.fresh(k)
        cache = self._side_caches.get(k)
        if cache is None:
            cache = self.fresh(k)
            self._side_caches[k] = cache
        cache = dict(cache)
        cache["pos"] = jnp.zeros((), jnp.int32)
        return cache

    @property
    def logits(self):
        """Last-token logits per slot, sampled by the scheduler."""
        return self._logits

    def begin_run(self) -> None:
        """Called at the top of each ``run()``."""

    def check_request(self, req) -> None:
        """Backend-specific admissibility (beyond the shared horizon)."""

    def on_weight_swap(self) -> None:
        """Invalidate weight-version-dependent cached state."""

    def rewind(self, slot_id: int, n: int) -> None:
        """Roll slot ``slot_id`` back by ``n`` positions (speculative
        decoding rejects drafted tokens). Contiguous caches share one
        clock across slots and cannot rewind one slot — the config gate
        keeps speculation off this backend."""
        raise NotImplementedError(
            f"rewind is not supported by the {self.backend!r} KV backend")

    def decode_positions(self) -> Dict[str, int]:
        """What the decode launches since the previous call attended and
        walked (backends that count them; empty otherwise)."""
        return {}

    def stats(self) -> Dict[str, Any]:
        return {"backend": self.backend}


# ---------------------------------------------------------------------------
# contiguous backend (the original layout — bit-exactness oracle)
# ---------------------------------------------------------------------------

class ContiguousKVCache(KVCache):
    """One ``max_slots``-row cache with a shared scalar clock; admission
    left-pads prompts to the clock and scatters side-cache rows into the
    pool (the ``admit_rows`` path). Wraps exactly the device calls the
    continuous scheduler used to issue, so slots' greedy tokens — and the
    engine's jit trace counts — are unchanged by the API move."""

    backend = "contiguous"

    def __init__(self, engine):
        super().__init__(engine)
        self._clock = 0

    @property
    def clock(self) -> int:
        """The shared decode position. Read-only: the clock advances via
        :meth:`decode` and is set by admissions — direct ``cache["pos"]``
        mutation is deprecated in favor of this property."""
        return self._clock

    def begin_run(self) -> None:
        self._clock = 0

    # ----------------------------------------------------------- admission
    def pick(self, queue, nfree: int, fresh: bool, limit_head: bool
             ) -> Tuple[List, Optional[int]]:
        """Choose up to ``nfree`` queued requests admissible at the clock.

        Mid-flight (``fresh=False``): FCFS with skip — a request fits iff
        its prompt fits under the clock (``L <= clock``; the clock advances
        one position per step, so longer prompts become admissible soon)
        and its budget fits the cache horizon. ``limit_head`` narrows the
        scan to the queue head (the starvation guard's anti-skip mode).

        Fresh wave (``fresh=True``): the pool is empty, so the clock
        restarts at the wave's longest admitted prompt. The queue head is
        always admitted (its own ``L + max_new <= max_len`` was validated
        at submit), guaranteeing progress; growing the wave re-checks every
        already-chosen request against the raised clock so admission never
        invalidates an earlier choice.
        """
        max_len = self.cfg.max_len
        clock = self._clock
        chosen: List = []
        new_clock = 0 if fresh else clock
        items = [queue[0]] if (limit_head and not fresh) else list(queue)
        for item in items:
            if len(chosen) >= nfree:
                break
            _, r = item
            if fresh:
                cand = max(new_clock, len(r.prompt))
                if (cand + r.max_new_tokens <= max_len
                        and all(cand + c.max_new_tokens <= max_len
                                for _, c in chosen)):
                    chosen.append(item)
                    new_clock = cand
            else:
                if (len(r.prompt) <= clock
                        and clock + r.max_new_tokens <= max_len):
                    chosen.append(item)
        for item in chosen:
            queue.remove(item)
        return chosen, new_clock

    def solve_target(self, longest: int) -> Optional[int]:
        """Committed completion clock for a mid-flight chunked admission.

        The pending consumes ``chunk`` positions per engine step while
        residents advance the clock one per step, so completing at clock
        ``P = clock + s - 1`` after ``s`` chunk-steps requires the chunks
        to cover all ``P`` positions (``s * chunk >= P``) and the prompt to
        fit the padding (``P >= longest``; prompts *longer than the clock*
        are admissible — the chunks catch up, which the monolithic path
        cannot do at all). Returns None when no ``s`` exists (``chunk == 1``
        against a moving clock can never catch up; such requests wait for
        the pool to empty, where the frozen clock makes any chunk feasible).
        """
        clock = self._clock
        chunk = int(self.cfg.prefill_chunk or 0)
        s = max(1, longest - clock + 1)
        if chunk > 1:
            s = max(s, -(-(clock - 1) // (chunk - 1)))
        elif clock + s - 1 > s:
            return None
        return clock + s - 1

    def _ensure_pool(self, lg) -> None:
        if self._cache is None:
            self._cache = self.fresh(self.max_slots)
            self._logits = jnp.zeros((self.max_slots, lg.shape[-1]),
                                     lg.dtype)

    def admit(self, chosen, slot_ids, clock: int, params) -> None:
        """Monolithic admission: prefill ``chosen`` left-padded to
        ``clock`` on a side cache and scatter the rows into the pool.
        Blocks until the device work is done (callers time around it)."""
        k = len(chosen)
        tokens = np.full((k, clock), self.cfg.pad_id, np.int32)
        for j, (_, r) in enumerate(chosen):
            tokens[j, clock - len(r.prompt):] = np.asarray(r.prompt)
        tmp_cache = self.side_cache(k)
        out, tmp_cache = self.eng._prefill(
            params, {"tokens": jnp.asarray(tokens)}, tmp_cache)
        lg = out.logits
        self._ensure_pool(lg)
        idx = jnp.asarray(np.asarray(slot_ids[:k], np.int32))
        self._cache, self._logits = self.eng._admit_rows(
            self._cache, tmp_cache, self._logits, lg, idx)
        jax.block_until_ready(self._logits)
        self._clock = clock

    def scatter(self, pending) -> None:
        """A completed chunked admission joins the pool: scatter its
        side-cache rows and final-token logits at the committed clock."""
        self._ensure_pool(pending.logits)
        idx = jnp.asarray(np.asarray(pending.slot_ids, np.int32))
        self._cache, self._logits = self.eng._admit_rows(
            self._cache, pending.cache, self._logits, pending.logits, idx)
        jax.block_until_ready(self._logits)
        self._clock = pending.target

    # -------------------------------------------------------------- decode
    def decode(self, params, nxt, active_ids) -> None:
        out, self._cache = self.eng._decode(params, nxt[:, None],
                                            self._cache)
        self._logits = out.logits
        self._clock += 1

    def retire(self, slot_id: int) -> None:
        """Contiguous rows are recycled implicitly (masked by position)."""

    def stats(self) -> Dict[str, Any]:
        return {"backend": self.backend, "clock": self._clock}


# ---------------------------------------------------------------------------
# paged backend
# ---------------------------------------------------------------------------

class PagedKVCache(KVCache):
    """Block-pool KV cache with per-slot block tables, prefix sharing and
    copy-on-write. See the module docstring for the design; the invariants:

    * every physical block is in exactly one of {free list, cached set
      (ref == 0, registered, evictable), active (ref > 0)}, plus the
      reserved trash block — asserted by ``stats()`` consumers;
    * registered blocks are immutable: any admission or decode write into
      a block another slot might read lands on a private copy (COW);
    * an admitted slot can always finish: its remaining decode blocks are
      reserved (``reserved`` outstanding count) and allocation draws from
      the free list, then evicts cached blocks FIFO.
    """

    backend = "paged"

    def __init__(self, engine):
        super().__init__(engine)
        cfg = self.cfg
        self.block_size = cfg.block_size
        self.nb_per_slot = cfg.max_len // cfg.block_size
        self.num_blocks = cfg.kv_blocks or \
            (self.max_slots * self.nb_per_slot + 1)
        # host-authoritative paging state (pushed to device per decode)
        self._tables = np.full((self.max_slots, self.nb_per_slot), TRASH,
                               np.int32)
        self._lengths = np.zeros((self.max_slots,), np.int32)
        self._ref = np.zeros((self.num_blocks,), np.int32)
        self._free: List[int] = list(range(self.num_blocks - 1, TRASH, -1))
        self._cached: Dict[int, None] = {}     # ref==0 registered, FIFO
        self._slot_reserved = np.zeros((self.max_slots,), np.int32)
        self._reserved = 0
        # shared-prefix blocks pinned by an in-flight admission, held OUT
        # of the slot's table until commit: decode writes every batch row
        # at its position, and an in-flight slot sits at position 0 — its
        # table must stay all-TRASH (writes land in the trash block) so a
        # pinned REGISTERED block is never written through mid-admission
        self._pending_pins: Dict[int, List[int]] = {}
        # prefix registry: chain hash -> (phys, block tokens) for full
        # blocks (content-verified on match), parent hash -> (phys, fill,
        # tokens) for one partial tail per chain position
        self._full_map: Dict[int, Tuple[int, tuple]] = {}
        self._hash_of: Dict[int, int] = {}
        self._partial_map: Dict[int, Tuple[int, int, tuple]] = {}
        self._phys_partial: Dict[int, int] = {}
        # observability
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        self.cow_copies = 0
        self.evictions = 0
        self.peak_blocks_active = 0
        # decode launches: context positions the active slots attend, and
        # the positions the decode program walks (the kernel: each row's
        # live page groups; the reference: every table to its end);
        # cumulative, and since the last ``decode_positions``
        self.positions_attended = self.positions_walked = 0
        self._step_attended = self._step_walked = 0
        self._pool_row = None          # (KV, D, itemsize) of attention rows
        # MoE stacks, per decode launch and per prefill chunk: token-expert
        # pairs routed to the experts held here, and the rows the held
        # experts' matmuls process (held experts x capacity, dropless);
        # cumulative, and since the last ``decode_positions``. A launch's
        # pair count stays on the device until the step's token pull has
        # waited for it anyway (``_moe_pending``)
        self.moe_pairs_here = self.moe_rows_computed = 0
        self._step_pairs = self._step_rows = 0
        self._moe_rows_per_token = self.model.moe_rows_per_token()
        self._moe_pending: List[Any] = []
        # jitted paged ops with trace accounting (lazy counters: the
        # contiguous path's trace_counts stay exactly as before). With
        # ``donate_kv`` the ops that write the pool donate it, as decode
        # does
        for name in ("gather", "scatter", "copy"):
            engine.trace_counts.setdefault(name, 0)
        donate = (0,) if engine.cfg.donate_kv else ()
        self._gather = engine._jit_counted("gather", gather_blocks)
        self._scatter = engine._jit_counted("scatter", scatter_blocks,
                                            donate=donate)
        self._copy = engine._jit_counted("copy", copy_block, donate=donate)
        # multi-position verifier forward (speculative decoding only —
        # the counter is lazy so non-speculative paged runs keep their
        # exact trace_counts dict)
        if getattr(cfg, "speculative", False):
            for name in ("verify", "spec_carry"):
                engine.trace_counts.setdefault(name, 0)
            model = self.model

            # the whole cycle tail is fused into the verify dispatch:
            # concat [t0, drafts], the S-position forward, and the
            # verifier's own argmax verdict — one device call and ONE
            # host sync (drafts+verdict together) per cycle, which is
            # where speculation's smoke-scale throughput win lives
            def verify_fused(params, t0, drafts, cache):
                x = jnp.concatenate([t0[:, None], drafts], axis=1)
                lg, cache = model.verify_step(params, x, cache)
                verdict = jnp.argmax(lg[:, :-1], axis=-1).astype(jnp.int32)
                return lg, verdict, cache

            def install_rows(logits, lg, slots, rows):
                return logits.at[slots].set(
                    lg[slots, rows].astype(logits.dtype))

            self._verify = engine._jit_counted("verify", verify_fused)
            self._install = engine._jit_counted("spec_carry", install_rows)

    # ---------------------------------------------------------------- clock
    @property
    def clock(self) -> Optional[int]:
        """Paged slots have per-slot positions, not a shared clock."""
        return None

    def check_request(self, req) -> None:
        need = -(-(len(req.prompt) + req.max_new_tokens) // self.block_size)
        if need > self.num_blocks - 1:
            raise ValueError(
                f"request {req.request_id}: prompt + max_new_tokens needs "
                f"{need} KV blocks but the pool only has "
                f"{self.num_blocks - 1} allocatable blocks")

    # ------------------------------------------------------ block lifecycle
    def _alloc(self) -> int:
        """A fresh writable block (ref=1): free list first, then FIFO
        eviction of cached (ref==0, registered) prefix blocks."""
        if self._free:
            ph = self._free.pop()
        elif self._cached:
            ph = next(iter(self._cached))
            del self._cached[ph]
            h = self._hash_of.pop(ph)
            self._full_map.pop(h, None)
            ent = self._partial_map.pop(h, None)
            if ent is not None:
                self._phys_partial.pop(ent[0], None)
            self.evictions += 1
        else:
            raise RuntimeError(
                "paged KV pool exhausted despite admission reservation")
        self._ref[ph] = 1
        active = self.num_blocks - 1 - len(self._free) - len(self._cached)
        self.peak_blocks_active = max(self.peak_blocks_active, active)
        return ph

    def _pin(self, ph: int) -> None:
        if self._ref[ph] == 0:
            self._cached.pop(ph, None)
        self._ref[ph] += 1

    def _unref(self, ph: int) -> None:
        self._ref[ph] -= 1
        assert self._ref[ph] >= 0
        if self._ref[ph] == 0:
            if ph in self._hash_of:
                self._cached[ph] = None      # reclaimable, keeps its hash
            else:
                h = self._phys_partial.pop(ph, None)
                if h is not None:
                    self._partial_map.pop(h, None)
                self._free.append(ph)

    # ------------------------------------------------------- prefix lookup
    def _lookup(self, prompt) -> Tuple[List[int], Optional[Tuple[int, int]]]:
        """Longest registered prefix of ``prompt``: full blocks (capped so
        at least one suffix token remains to prefill) plus at most one
        partial tail share ``(phys, fill)`` whose values seed the side
        cache (the block itself is COWed by the suffix write)."""
        bs = self.block_size
        L = len(prompt)
        h = 0
        full: List[int] = []
        j = 0
        while (j + 1) * bs <= L - 1:
            blk = tuple(prompt[j * bs:(j + 1) * bs])
            h2 = hash((h, blk))
            ent = self._full_map.get(h2)
            if ent is None or ent[1] != blk:
                break
            full.append(ent[0])
            h = h2
            j += 1
        partial = None
        ent = self._partial_map.get(h)
        if ent is not None:
            ph, fill, toks = ent
            f = min(fill, (L - 1) - j * bs)
            if f > 0 and tuple(prompt[j * bs:j * bs + f]) == toks[:f]:
                partial = (ph, f)
        if partial is None and bs > 1 and (j + 1) * bs == L:
            # the prompt's own last block is registered in full but the
            # keep-one-suffix cap excludes it — share all but its last
            # position (classic identical-prompt case; triggers COW)
            blk = tuple(prompt[j * bs:L])
            ent = self._full_map.get(hash((h, blk)))
            if ent is not None and ent[1] == blk:
                partial = (ent[0], bs - 1)
        return full, partial

    def _register(self, prompt, table) -> None:
        bs = self.block_size
        L = len(prompt)
        h = 0
        for j in range(L // bs):
            blk = tuple(prompt[j * bs:(j + 1) * bs])
            h = hash((h, blk))
            if h not in self._full_map:
                ph = int(table[j])
                self._full_map[h] = (ph, blk)
                self._hash_of[ph] = h
        f = L % bs
        if f and h not in self._partial_map:
            ph = int(table[L // bs])
            if ph not in self._phys_partial and ph not in self._hash_of:
                self._partial_map[h] = (ph, f, tuple(prompt[L - f:L]))
                self._phys_partial[ph] = h

    def on_weight_swap(self) -> None:
        """Cached prefix K/V were computed under the outgoing weights —
        flush the registry (in-use shared blocks keep their refs; parked
        blocks go back to the free list)."""
        for ph in list(self._cached):
            self._free.append(ph)
        self._cached.clear()
        for ph in list(self._hash_of):
            del self._hash_of[ph]
        self._full_map.clear()
        self._partial_map.clear()
        self._phys_partial.clear()

    # ----------------------------------------------------------- admission
    def pick(self, queue, nfree: int, fresh: bool, limit_head: bool
             ) -> Tuple[List, Optional[int]]:
        """FCFS-with-skip under a conservative block budget: a request is
        admissible iff its full ``ceil((L + max_new)/bs)`` block need fits
        in free + evictable blocks net of outstanding reservations (prefix
        sharing only *reduces* the real need at admit time)."""
        bs = self.block_size
        avail = len(self._free) + len(self._cached) - self._reserved
        chosen: List = []
        items = [queue[0]] if (limit_head and not fresh) else list(queue)
        for item in items:
            if len(chosen) >= nfree:
                break
            _, r = item
            need = -(-(len(r.prompt) + r.max_new_tokens) // bs)
            if need <= avail:
                chosen.append(item)
                avail -= need
        for item in chosen:
            queue.remove(item)
        return chosen, None

    def admit(self, chosen, slot_ids, clock, params) -> None:
        """Admit each request into its slot: prefix lookup → gather shared
        blocks → prefill the unshared suffix (batch 1, *unpadded* — the
        same shapes as a solo round, so greedy tokens are bit-identical to
        the contiguous oracle at equal effective context) → allocate/COW →
        scatter the written blocks into the pool."""
        for (_, r), slot in zip(chosen, slot_ids):
            self._admit_one(slot, r, params)
        jax.block_until_ready(self._logits)

    def _ensure_pool(self, lg) -> None:
        if self._cache is None:
            # the pool inherits the config's KV dtype (int8 codes +
            # per-(position, kv-head) scale pools under quantize_kv) —
            # hardcoding quantize_kv=False here silently scattered fp side
            # caches into an fp pool while ``fresh()``/``side_cache()``
            # honored the flag, which is exactly the dtype split the
            # regression test in tests/test_kvcache_paged.py pins down
            self._cache = self.model.init_cache(
                self.num_blocks, self.block_size,
                quantize_kv=self.cfg.quantize_kv)
            self._logits = jnp.zeros((self.max_slots, lg.shape[-1]),
                                     lg.dtype)

    def _pin_prefix(self, slot: int, prompt) -> Tuple[int, dict]:
        """The start of every paged admission (monolithic or chunked):
        longest-registered-prefix lookup, pin the matched blocks into the
        slot's table (ref++ — they leave the evictable cached set HERE,
        before any prefill work runs, so pool pressure during a multi-step
        admission can never recycle a block the admission is about to
        read), and gather their values into a fresh 1-row side cache whose
        clock is the shared length ``lp``.

        The pinned blocks are parked in ``_pending_pins`` — NOT written
        into the slot's table until :meth:`_commit_blocks`: decode writes
        every batch row's K/V at its position, and an in-flight slot sits
        at position 0 with its table all-TRASH, so interleaved resident
        decode steps land in the trash block instead of writing through a
        pinned registered block."""
        bs = self.block_size
        full, partial = self._lookup(prompt)
        nfull = len(full)
        f_part = partial[1] if partial else 0
        lp = nfull * bs + f_part
        pinned = list(full)
        if partial:
            pinned.append(partial[0])
        for ph in pinned:
            self._pin(ph)
        self._pending_pins[slot] = pinned
        side = self.side_cache(1)
        if lp:
            side = self._gather(side, self._cache,
                                jnp.asarray(np.asarray(pinned, np.int32)))
            side["pos"] = jnp.asarray(np.int32(lp))
            self.prefix_hits += 1
            self.prefix_tokens_reused += lp
        return lp, side

    def _commit_blocks(self, slot: int, prompt, lp: int, side, lg,
                       max_new: int) -> int:
        """The end of every paged admission: allocate / copy-on-write the
        write-range blocks, scatter the side-cache rows into them, place
        the last-token logits, register the prompt's blocks for prefix
        reuse, and set the slot's decode position to its own prompt
        length. Returns the decode-only block remainder (the reservation
        that stays outstanding until decode crosses those boundaries)."""
        bs = self.block_size
        L = len(prompt)
        self._ensure_pool(lg)
        table = self._tables[slot]
        for j, ph in enumerate(self._pending_pins.pop(slot, ())):
            table[j] = ph
        nb_prompt = -(-L // bs)
        first_wb = lp // bs
        for j in range(first_wb, nb_prompt):
            ph = int(table[j])
            if ph != TRASH:
                # shared (or registered) block in the write range: the
                # slot gets a private copy before its first divergent
                # write; the side cache already holds the shared values,
                # so the scatter below materializes the copy
                self._unref(ph)
                self.cow_copies += 1
            table[j] = self._alloc()
        phys_w = jnp.asarray(table[first_wb:nb_prompt].copy())
        self._cache = self._scatter(self._cache, side, phys_w,
                                    jnp.asarray(np.int32(first_wb)))
        self._logits = self._logits.at[slot].set(
            lg[0].astype(self._logits.dtype))
        self._register(prompt, table)
        self._lengths[slot] = L
        return -(-(L + max_new) // bs) - nb_prompt

    def _admit_one(self, slot: int, r, params) -> None:
        prompt = [int(t) for t in r.prompt]
        with _span("kv.gather", request_id=r.request_id):
            lp, side = self._pin_prefix(slot, prompt)
        if lp:
            toks = jnp.asarray(np.asarray(prompt[lp:], np.int32))[None]
            out, side = self.eng._prefill_chunk(params, {"tokens": toks},
                                                side)
        else:
            toks = jnp.asarray(np.asarray(prompt, np.int32))[None]
            out, side = self.eng._prefill(params, {"tokens": toks}, side)
        lg = out.logits
        self.note_moe(out.moe_pairs, toks.shape[1])
        with _span("kv.scatter", request_id=r.request_id):
            rem = self._commit_blocks(slot, prompt, lp, side, lg,
                                      r.max_new_tokens)
        self._slot_reserved[slot] = rem
        self._reserved += rem

    # ----------------------------------------- chunked (multi-step) admission
    def reserve_pending(self, slot: int, req) -> None:
        """Reserve a chunked admission's FULL block budget at pending
        creation, before its first chunk runs: ``pick`` chose the request
        against free + evictable net of reservations, and residents keep
        decoding (and allocating at block boundaries) for the whole
        multi-step admission — without the outstanding reservation their
        allocations could consume the blocks the pending needs to land.
        Completion re-points the reservation at the decode-only remainder;
        abandonment releases it."""
        need = -(-(len(req.prompt) + req.max_new_tokens) // self.block_size)
        self._slot_reserved[slot] = need
        self._reserved += need

    def begin_chunked_admit(self, slot: int, req) -> Tuple[int, dict]:
        """First chunk step of a pending entry: prefix lookup + pin +
        gather (see :meth:`_pin_prefix` — pinning happens HERE, before any
        chunk is consumed, never at completion: FIFO eviction of ref-0
        cached blocks under pool pressure between chunk steps could
        otherwise free a block the pending gathered from). Returns the
        shared-prefix length and the positioned side cache; the scheduler
        chunk-prefills ``prompt[lp:]`` on it across engine steps."""
        with _span("kv.gather", request_id=req.request_id):
            return self._pin_prefix(slot, [int(t) for t in req.prompt])

    def complete_chunked_admit(self, slot: int, req, lp: int, side,
                               lg) -> None:
        """A pending entry consumed its whole suffix: scatter the side
        cache into the slot's blocks at the slot's OWN prompt length (the
        per-slot clock — no shared completion clock, no catch-up
        recurrence) and re-point the up-front reservation at the
        decode-only remainder."""
        with _span("kv.scatter", request_id=req.request_id):
            rem = self._commit_blocks(slot, [int(t) for t in req.prompt],
                                      lp, side, lg, req.max_new_tokens)
            resv = int(self._slot_reserved[slot])
            self._reserved -= resv - rem
            self._slot_reserved[slot] = rem
            jax.block_until_ready(self._logits)

    def abandon_chunked_admit(self, slot: int) -> None:
        """A force-swap abandons a pending entry mid-prefill: unpin
        (ref--) the shared-prefix blocks it pinned at begin and release
        the slot's reserved-block budget. Dropping only the side cache —
        the contiguous abandon path — would leak both until pool
        exhaustion."""
        for ph in self._pending_pins.pop(slot, ()):
            self._unref(ph)
        self.retire(slot)

    def check_invariants(self) -> None:
        """Test/debug hook: every non-trash block is in exactly one of
        {free, cached, active (ref > 0)} — i.e. free + cached + active +
        trash == num_blocks — reservations are non-negative and sum
        consistently, and every live table entry holds a reference."""
        free, cached = set(self._free), set(self._cached)
        assert TRASH not in free and TRASH not in cached
        assert not free & cached, "block in free AND cached"
        active = {ph for ph in range(1, self.num_blocks)
                  if self._ref[ph] > 0}
        assert not active & free and not active & cached, \
            "referenced block in free/cached"
        assert len(free) + len(cached) + len(active) + 1 == self.num_blocks
        assert all(self._ref[ph] == 0 for ph in free | cached)
        assert self._reserved == int(self._slot_reserved.sum()) >= 0
        assert np.all(self._slot_reserved >= 0)
        for pins in self._pending_pins.values():
            assert all(self._ref[ph] >= 1 for ph in pins), \
                "in-flight admission pin on unreferenced block"
        for s in range(self.max_slots):
            for j in range(self.nb_per_slot):
                ph = int(self._tables[s, j])
                assert ph == TRASH or self._ref[ph] >= 1, \
                    f"slot {s} table points at unreferenced block {ph}"

    # -------------------------------------------------------------- decode
    def _writable_block(self, i: int, j: int) -> None:
        """Make table entry ``(i, j)`` privately writable: allocate a
        reserved block at a TRASH boundary (drawing down the slot's
        reservation), or copy-on-write a block with other sharers
        (defensive at decode time: admission already privatizes every
        block it writes, so a shared tail here means a new sharing mode —
        keep the invariant regardless)."""
        ph = int(self._tables[i, j])
        if ph == TRASH:
            self._tables[i, j] = self._alloc()
            self._slot_reserved[i] -= 1
            self._reserved -= 1
        elif self._ref[ph] > 1:
            nb = self._alloc()
            self._cache = self._copy(self._cache,
                                     jnp.asarray(np.int32(ph)),
                                     jnp.asarray(np.int32(nb)))
            self._unref(ph)
            self._tables[i, j] = nb
            self.cow_copies += 1

    def decode(self, params, nxt, active_ids) -> None:
        with _span("kv.decode", slots=len(active_ids)):
            bs = self.block_size
            for i in active_ids:
                self._writable_block(i, int(self._lengths[i]) // bs)
            # snapshots, not views: the device arrays may alias host memory
            # (zero-copy transfer) and ``_lengths``/``_tables`` are mutated
            # right after dispatch — aliasing would race the async decode
            pos = self._lengths.copy()
            self._cache["pos"] = jnp.asarray(pos)
            self._cache["block_tables"] = jnp.asarray(self._tables.copy())
            out, self._cache = self.eng._decode(params, nxt[:, None],
                                                self._cache)
            self._logits = out.logits
            self.note_moe(out.moe_pairs, self.max_slots)
            self._lengths[active_ids] += 1
        # slot b attends its lengths[b] + 1 positions (now its length); the
        # program walks what the attention backend walks of every row
        attended = int(self._lengths[active_ids].sum())
        walked = self._walked(pos)
        self.positions_attended += attended
        self.positions_walked += walked
        self._step_attended += attended
        self._step_walked += walked

    def _walked(self, pos) -> int:
        """Pool positions one decode launch walks, per the backend that
        ``paged_attention`` resolves to (one walk stands for every layer:
        their pools share one row shape). A stack without attention rows
        counts its tables."""
        if self._pool_row is None:
            self._pool_row = ()
            leaves = jax.tree_util.tree_flatten_with_path(self._cache)[0]
            for path, leaf in leaves:
                key = getattr(path[-1], "key", None)
                if key in ("k", "latent"):
                    # MLA's latent rows are one "KV head" of their width
                    kv = 1 if key == "latent" else leaf.shape[-2]
                    self._pool_row = (kv, leaf.shape[-1], leaf.dtype.itemsize)
                    break
        if not self._pool_row:
            return pos.size * self.nb_per_slot * self.block_size
        kv, d, itemsize = self._pool_row
        return positions_walked(pos, self.nb_per_slot,
                                block_size=self.block_size, kv=kv, d=d,
                                itemsize=itemsize)

    def decode_positions(self) -> Dict[str, int]:
        self._pull_moe()
        out = {"positions_attended": self._step_attended,
               "positions_walked": self._step_walked,
               "moe_pairs_here": self._step_pairs,
               "moe_rows_computed": self._step_rows}
        self._step_attended = self._step_walked = 0
        self._step_pairs = self._step_rows = 0
        return out

    def note_moe(self, pairs, tokens: int) -> None:
        """Count one launch of ``tokens`` rows (a decode over every slot,
        or a prefill chunk): its held pairs, the launch's ``StepOut.
        moe_pairs`` (pulled later, by :meth:`_pull_moe`), and the held
        experts' rows (each runs every token, dropless). No-op without
        experts."""
        if pairs is None:
            return
        self._moe_pending.append(pairs)
        rows = self._moe_rows_per_token * int(tokens)
        self.moe_rows_computed += rows
        self._step_rows += rows

    def _pull_moe(self) -> None:
        """Bring the noted launches' pair counts to the host: called after
        the step's token pull (or a chunk's wait), when those launches
        have finished, so it adds no wait of its own."""
        if self._moe_pending:
            got = int(sum(int(x) for x in jax.device_get(self._moe_pending)))
            self._moe_pending = []
            self.moe_pairs_here += got
            self._step_pairs += got

    # ------------------------------------------------- speculative verify
    def ensure_rows(self, slot: int, start: int, n: int) -> None:
        """Make positions ``start .. start+n-1`` of ``slot`` writable
        before a multi-position verify: allocate reserved blocks at TRASH
        boundaries exactly as decode does, and privatize (COW) any block
        with other sharers in the write range."""
        bs = self.block_size
        for j in range(start // bs, -(-(start + n) // bs)):
            self._writable_block(slot, j)

    def verify(self, params, t0, drafts, active_ids):
        """One batched multi-position verifier forward over ``[t0,
        drafts]``: slot ``b`` writes K/V for — and scores — absolute
        positions ``lengths[b] .. lengths[b]+S-1``, where ``S = 1 +
        drafts.shape[1]`` and row ``j`` of the returned ``(max_slots, S,
        vocab)`` logits conditions on everything through position
        ``lengths[b]+j``. Also returns the fused per-row argmax
        ``verdict`` (``(max_slots, S-1)``): ``verdict[b, j]`` is the
        token verifier-only decode would emit after ``[t0, d_1..d_j]``.
        Active slots' lengths advance by S (the speculative cycle
        rewinds the rejected suffix); inactive slots' tables are
        all-TRASH so their writes land in the trash block, exactly as in
        lockstep decode."""
        s = int(drafts.shape[1]) + 1
        for i in active_ids:
            self.ensure_rows(i, int(self._lengths[i]), s)
        self._cache["pos"] = jnp.asarray(self._lengths.copy())
        self._cache["block_tables"] = jnp.asarray(self._tables.copy())
        lg, verdict, self._cache = self._verify(params, t0, drafts,
                                                self._cache)
        self._lengths[np.asarray(active_ids, np.int64)] += s
        return lg, verdict

    def carry_logits(self, lg, slot_ids, rows) -> None:
        """Install ``lg[slot, rows[slot]]`` as each listed slot's pending
        logits — the verifier row at the divergence point, carried into
        the scheduler's next sample — in one fused gather+scatter."""
        self._logits = self._install(
            self._logits, lg,
            jnp.asarray(np.asarray(slot_ids, np.int32)),
            jnp.asarray(np.asarray(rows, np.int32)))

    def rewind(self, slot_id: int, n: int) -> None:
        """Roll ``slot_id`` back ``n`` positions (reject drafted tokens).

        A block that no longer holds any live position returns to the
        slot's *reservation* (``_slot_reserved``), never to another
        slot's budget: the slot drew down its reservation when it
        allocated the block and needs the claim back to finish its
        ``max_new_tokens``. The physical block itself goes through
        ``_unref`` — an exclusively-owned unregistered block lands on the
        free list (where the restored reservation keeps it claimable),
        a registered one parks in the cached set, and a block other slots
        still share just drops this slot's ref — so the free/cached/
        active partition and the ``free + cached - reserved`` admission
        budget both stay consistent."""
        if n <= 0:
            return
        new_len = int(self._lengths[slot_id]) - n
        assert new_len >= 0, "rewind past the start of the slot"
        for j in range(-(-new_len // self.block_size), self.nb_per_slot):
            ph = int(self._tables[slot_id, j])
            if ph != TRASH:
                self._unref(ph)
                self._tables[slot_id, j] = TRASH
                self._slot_reserved[slot_id] += 1
                self._reserved += 1
        self._lengths[slot_id] = new_len

    def retire(self, slot_id: int) -> None:
        """Drop the slot's refs; exclusively-owned unregistered blocks go
        back to the free list, registered ones park in the cached set."""
        for j in range(self.nb_per_slot):
            ph = int(self._tables[slot_id, j])
            if ph != TRASH:
                self._unref(ph)
                self._tables[slot_id, j] = TRASH
        self._lengths[slot_id] = 0
        self._reserved -= int(self._slot_reserved[slot_id])
        self._slot_reserved[slot_id] = 0

    # ------------------------------------------------------- observability
    def block_bytes(self) -> int:
        """Device bytes per physical block (all layers)."""
        if self._cache is None:
            return 0
        total = 0
        for key in LAYER_KEYS:
            if key in self._cache:
                total += sum(l.nbytes for l in
                             jax.tree_util.tree_leaves(self._cache[key]))
        return total // self.num_blocks

    def bytes_per_position(self) -> int:
        """Device KV bytes per cached position (all layers) — the unit
        decode attention's HBM traffic scales with: each step reads the
        slot's whole context at this rate. int8 pools pay
        ``2*D + 2*itemsize(scale)`` per (position, kv-head, layer) vs
        ``2*D*itemsize`` for fp pools."""
        return self.block_bytes() // self.block_size if self.block_size \
            else 0

    def pool_bytes(self) -> int:
        """Total device bytes held by the block pool (all layers)."""
        return self.block_bytes() * self.num_blocks

    def stats(self) -> Dict[str, Any]:
        self._pull_moe()
        free, cached = len(self._free), len(self._cached)
        return {"backend": self.backend,
                "quantize_kv": self.cfg.quantize_kv,
                "block_size": self.block_size,
                "bytes_per_position": self.bytes_per_position(),
                "pool_bytes": self.pool_bytes(),
                "blocks_total": self.num_blocks,
                "blocks_free": free,
                "blocks_cached": cached,
                "blocks_active": self.num_blocks - 1 - free - cached,
                "blocks_trash": 1,
                "blocks_reserved": self._reserved,
                "peak_blocks_active": self.peak_blocks_active,
                "block_bytes": self.block_bytes(),
                "prefix_hits": self.prefix_hits,
                "prefix_tokens_reused": self.prefix_tokens_reused,
                "cow_copies": self.cow_copies,
                "positions_attended": self.positions_attended,
                "positions_walked": self.positions_walked,
                "moe_pairs_here": self.moe_pairs_here,
                "moe_rows_computed": self.moe_rows_computed,
                "evictions": self.evictions}
