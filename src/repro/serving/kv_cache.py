"""Serving-side KV cache management: a paged, block-granular allocator.

The model-level cache layout lives in repro.models.attention/transformer;
this module owns the serving concerns. KV memory is a shared pool of
fixed-size blocks — ``(n_blocks, block_size, KVH, hd)`` per layer — and
every slot indexes it through a per-slot **block table** carried in the
jitted decode state (``lm.init_paged_decode_state``). A slot grows one
block at a time as it decodes instead of reserving a contiguous
``max_len`` stripe up front, so a 16-token request no longer pins the
same HBM as a 500-token one (the paper's bulk-granularity tax, applied
to memory).

Layout contract (shared with models.attention / core.flash_decode):
logical position ``p`` of slot ``b`` lives at pool block
``table[b, p // block_size]``, offset ``p % block_size``. Across the
model mesh axis the pool is sharded on the block dim in contiguous
chunks; online-softmax permutation-invariance keeps any block->rank
assignment exact.

``CachePool`` is the single owner of the decode state AND the host-side
block bookkeeping:

* **free list / refcounts** — blocks are refcounted; a block shared by
  several slots (prefix cache) is freed only when the last reference
  drops.
* **prefix caching** — a block holding a fully-written prompt-prefix
  chunk is registered under a chained content key
  ``(parent_block, chunk_tokens)``. Admission walks the chain: matched
  blocks are shared into the new slot's table (refcount++), the slot's
  ``cur_len`` starts at the first novel token, and the engine skips
  re-prefilling the reused span. Ref-0 registered blocks stay RESIDENT
  in an LRU cache and are only evicted (cascading to their ref-0
  descendants, which are unreachable without the parent) when the free
  list runs dry.
* **copy-on-write** — registered blocks are immutable. When a slot must
  write into one (e.g. a full-prefix hit still has to consume its last
  prompt token to produce logits, and that token's KV lands inside the
  final shared block), the block is first cloned to a private copy
  (``lm.copy_cache_block``) and the table repointed.

* **preemption** — ``preempt(slot)`` expresses eviction as block
  bookkeeping: the victim's fully-written prompt chunks are registered
  as prefix blocks first (so a resume is a prefix hit that skips
  re-prefilling them), then every block reference is dropped — private
  blocks return to the free list immediately, shared/registered ones
  stay resident. The engine re-queues the victim with its generated
  tokens folded into an effective prompt.
* **sliding-window reclaim** — ``reclaim_out_of_window(slot, window)``
  frees a slot's blocks whose every position has rolled permanently out
  of the attention window (the mask is ``pos >= cur_len - window`` and
  ``cur_len`` only grows), leaving ``-1`` holes in the table. The paged
  attention paths treat ``-1`` as invalid (masked), so a hole is never
  read; rolling workloads stop pinning dead blocks.

A slot's table is dense from 0 *except* for reclaim holes; ``free()``
and ``register_prompt_chunks`` therefore scan past ``-1`` entries
rather than treating the first one as end-of-table.

The host mirrors (``tables``, ``lengths``, ``active``) let the scheduler
make admission/growth decisions without a device sync; ``sync()``
re-uploads the table to the jitted state only when it changed.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm


def blocks_for(n_tokens: int, block_size: int) -> int:
    return -(-n_tokens // block_size)


def pow2_bucket(n: int, cap: int) -> int:
    """Smallest power of two >= max(n, 1), clamped to ``cap``: the one
    bucketing rule for every STATIC jit width/length in the serving
    layer (paged gather width, prefill scan length, megatick scan
    length), bounding jit specializations at log2(cap) + 1.

    Edge-case contract (relied on by the engine's dispatch paths, and
    what taxlint rule TAX002 sanctions as THE static-arg launderer):

    * ``n <= 0`` -> 1 — an idle tick still compiles a width-1 program
      rather than a degenerate width-0 one;
    * ``n > cap`` -> ``cap`` — the cap is a hard ceiling (a table/scan
      can never be wider than its allocation), so oversized demands
      clamp instead of growing the specialization set;
    * non-power-of-two ``cap`` (e.g. ``max_blocks`` after the pool's
      model-axis rounding) is returned AS-IS when the clamp engages:
      the top bucket is the exact capacity, not a padded power of two
      that would index past it;
    * monotone non-decreasing in ``n`` — a growing watermark can only
      move forward through the bucket sequence 1, 2, 4, ..., cap.

    ``cap < 1`` is a configuration bug (no jit program has width 0):
    raise loudly instead of returning an unusable width.
    """
    if cap < 1:
        raise ValueError(
            f"pow2_bucket: cap must be >= 1, got {cap} — a static jit "
            f"width/length bucket of zero can never be dispatched")
    w = 1
    while w < max(n, 1):
        w *= 2
    return min(w, cap)


# eq/repr off: the pool holds the full params pytree and the decode
# state — the generated __eq__ would crash on array truthiness and
# __repr__ would stringify the whole model
@dataclasses.dataclass(eq=False, repr=False)
class CachePool:
    """Paged block pool + slot table for continuous batching.

    ``n_blocks`` defaults to contiguous parity (batch * max_len worth of
    blocks); size it smaller to serve mixed-length traffic in a fraction
    of the HBM — admission then gates on block availability, not slot
    count. It is rounded up to a multiple of the model-axis size so the
    pool shards evenly on the block dim.
    """
    params: object
    cfg: object
    batch: int
    max_len: int
    block_size: int = 16
    n_blocks: int | None = None

    def __repr__(self):
        return (f"CachePool(batch={self.batch}, max_len={self.max_len}, "
                f"block_size={self.block_size}, "
                f"blocks={self.blocks_in_use}/{self.n_blocks}, "
                f"active={self.n_active}/{self.batch})")

    def __post_init__(self):
        from repro.distributed import context as dctx
        ctx = dctx.current()
        bs = self.block_size
        self.max_blocks = blocks_for(self.max_len, bs)
        if self.n_blocks is None:
            self.n_blocks = self.batch * self.max_blocks
        W = ctx.model_axis_size
        self.n_blocks += (-self.n_blocks) % max(W, 1)
        # rwkv has no KV cache: the block pool is bookkeeping-only there
        self._needs_blocks = self.cfg.block != "rwkv"
        # prefix reuse seeds KV blocks only; recurrent state (mamba) can't
        # be rebuilt from them, so hybrids prefill from scratch
        self._can_share = self.cfg.block in ("attn_mlp", "attn_moe")
        self.state = lm.init_paged_decode_state_sharded(
            self.cfg, self.batch, self.n_blocks, bs, self.max_blocks,
            ctx.rules)
        # host mirrors: scheduler reads/updates these synchronously; the
        # device cur_len advances inside the jitted step and block_tables
        # re-upload via sync() when dirty
        self.tables = np.full((self.batch, self.max_blocks), -1, np.int32)
        self.lengths = np.zeros(self.batch, np.int32)
        self.active = np.zeros(self.batch, bool)
        self.ref = np.zeros(self.n_blocks, np.int32)
        self._free = list(range(self.n_blocks - 1, -1, -1))  # pop -> low ids
        self._lru = OrderedDict()      # ref-0 registered blocks (evictable)
        self._key_of: dict[int, tuple] = {}   # block -> chain key
        self._index: dict[tuple, int] = {}    # chain key -> block
        self._children: dict[int, set] = {}   # block -> registered children
        self._dirty = True
        def pool_copy_block(s, a, b):
            return lm.copy_cache_block(s, self.cfg, a, b)
        self._copy_fn = jax.jit(pool_copy_block)
        # counters
        self.prefix_hits = 0           # admissions that reused >= 1 block
        self.prefix_hit_tokens = 0     # prompt tokens NOT re-prefilled
        self.cow_copies = 0
        self.evictions = 0
        self.admitted = 0
        self.blocks_hwm = 0
        self.preempted_slots = 0
        self.aborted_slots = 0         # mid-stream cancellations (abort())
        self.blocks_reclaimed = 0      # sliding-window dead-block frees
        self._seized: list[int] = []   # fault injection: held-back blocks
        self.blocks_seized = 0         # cumulative seize count

    # ----------------------------------------------------------- block layer
    def _pop_block(self) -> int | None:
        if self._free:
            return self._free.pop()
        if self._lru:                      # evict the LRU resident prefix
            b, _ = next(iter(self._lru.items()))
            self._evict(b)
            self.evictions += 1
            return self._free.pop() if self._free else None
        return None

    def _evict(self, b: int):
        """Unregister block b and cascade to registered descendants —
        without the parent in the index they are unreachable for
        matching. Descendants still referenced by a live slot cannot
        exist here (a table always holds the whole chain)."""
        self._lru.pop(b, None)
        key = self._key_of.pop(b, None)
        if key is not None:
            self._index.pop(key, None)
            parent = key[0]
            if parent in self._children:
                self._children[parent].discard(b)
        for child in sorted(self._children.pop(b, ())):
            if self.ref[child] == 0:
                self._evict(child)
            else:                          # defensive: orphan but live
                ck = self._key_of.pop(child, None)
                if ck is not None:
                    self._index.pop(ck, None)
        if self.ref[b] == 0:
            self._free.append(b)

    def _deref(self, b: int):
        self.ref[b] -= 1
        assert self.ref[b] >= 0, f"block {b} refcount underflow"
        if self.ref[b] == 0:
            if b in self._key_of:
                self._lru[b] = True        # resident prefix, evict-on-demand
                self._lru.move_to_end(b)
            else:
                self._free.append(b)

    def _ref_inc(self, b: int):
        if self.ref[b] == 0:
            self._lru.pop(b, None)         # revive from the resident cache
        self.ref[b] += 1

    @property
    def blocks_in_use(self) -> int:
        return self.n_blocks - len(self._free) - len(self._lru)

    @property
    def max_blocks_in_use(self) -> int:
        """Live gather-width watermark: highest table column holding an
        allocated block across all slots, plus one (0 when nothing is
        allocated). Sliding-window reclaim can hole out LOW columns
        while the high ones stay live, so this tracks the highest used
        column, not a block count. One vectorized scan of the host
        table mirror — cheap enough to call every tick."""
        used = np.nonzero((self.tables >= 0).any(axis=0))[0]
        return int(used[-1]) + 1 if len(used) else 0

    def gather_width(self) -> int:
        """Padded gather-width bucket for the bounded table-gather
        decode: the next power of two >= ``max_blocks_in_use``, clamped
        to [1, max_blocks]. The engine threads this into the jitted
        step as a STATIC width (the attention paths see only the
        leading table slice), so each distinct value is a recompile —
        power-of-two padding bounds that at log2(max_blocks)
        specializations while the scored width tracks the live
        high-water mark instead of the worst case."""
        return pow2_bucket(self.max_blocks_in_use, self.max_blocks)

    @property
    def blocks_resident(self) -> int:
        """In use + ref-0 resident prefix blocks."""
        return self.n_blocks - len(self._free)

    def block_occupancy(self) -> float:
        return self.blocks_in_use / self.n_blocks

    def admissible(self, prompt_len: int) -> bool:
        """Whether a prompt of this length can EVER be admitted: its
        prompt plus one generated token must fit the whole pool.
        (Conservative: prefix sharing could stretch this in theory, but
        a pool smaller than single prompts is a misconfiguration.)"""
        if not self._needs_blocks:
            return True
        return blocks_for(prompt_len + 1, self.block_size) <= self.n_blocks

    def hbm_fraction_vs_contiguous(self) -> float:
        """Allocated KV token-capacity relative to the contiguous layout
        (batch x max_len stripes) this pool replaces."""
        return ((self.n_blocks * self.block_size)
                / float(self.batch * self.max_len))

    # ---------------------------------------------------------- prefix cache
    def _match_prefix(self, prompt) -> tuple[list[int], int]:
        """Longest chain of registered full-chunk blocks matching the
        prompt. Returns (blocks, reused_tokens); reuse is capped at
        len(prompt)-1 — at least one prompt token must run through the
        model to produce the first logits."""
        if not self._can_share or not prompt:
            return [], 0
        bs = self.block_size
        blocks, parent = [], -1
        for c in range(len(prompt) // bs):
            b = self._index.get((parent, tuple(prompt[c * bs:(c + 1) * bs])))
            if b is None:
                break
            blocks.append(b)
            parent = b
        reuse = min(len(blocks) * bs, len(prompt) - 1)
        return blocks, reuse

    def register_prompt_chunks(self, slot: int, prompt):
        """Register the slot's fully-written full-prompt chunks as
        shareable prefix blocks. Idempotent — called after every prefill
        tick. If identical content is already registered under another
        block (two identical prompts racing), the canonical block keeps
        the registration and the chain continues through it: the
        duplicate's KV is identical (same token prefix, same positions),
        so either block is a correct parent for the next chunk's key."""
        if not self._can_share:
            return
        bs = self.block_size
        n_full = min(int(self.lengths[slot]), len(prompt)) // bs
        parent = -1
        for c in range(n_full):
            b = int(self.tables[slot, c])
            if b < 0:
                break    # window-reclaim hole: the chain is unreachable
            if b in self._key_of:
                parent = b
                continue
            key = (parent, tuple(prompt[c * bs:(c + 1) * bs]))
            cur = self._index.get(key)
            if cur is None:
                self._index[key] = b
                self._key_of[b] = key
                if parent >= 0:
                    self._children.setdefault(parent, set()).add(b)
                cur = b
            parent = cur

    # ------------------------------------------------------------- slot layer
    def alloc(self, prompt=None) -> tuple[int, int] | None:
        """Claim a free slot, seeding its block table from the prefix
        cache. Returns (slot, reused_tokens), or None when no slot is
        free OR the pool cannot cover the request's prompt + first
        generated token (block-availability admission control)."""
        free_slots = np.nonzero(~self.active)[0]
        if len(free_slots) == 0:
            return None
        slot = int(free_slots[0])
        prompt = list(prompt) if prompt is not None else []
        blocks, reuse = self._match_prefix(prompt)
        bs = self.block_size
        # a capped full match still writes its last token into the final
        # shared block -> that block needs a copy-on-write clone
        cow = 1 if (blocks and reuse < len(blocks) * bs) else 0
        if self._needs_blocks:
            total = blocks_for(len(prompt) + 1, bs)
            need = total - len(blocks) + cow
            # matched blocks about to be revived are NOT evictable supply
            avail = (len(self._free) + len(self._lru)
                     - sum(1 for b in blocks if b in self._lru))
            if need > avail:
                return None
        for b in blocks:
            self._ref_inc(b)
        self.tables[slot, :len(blocks)] = blocks
        self.tables[slot, len(blocks):] = -1
        self.active[slot] = True
        self.lengths[slot] = reuse
        self.state = lm.reset_slot_paged(self.state, self.cfg, slot)
        if reuse:
            self.state = lm.set_slot_len(self.state, slot, reuse)
            self.prefix_hits += 1
            self.prefix_hit_tokens += reuse
        if cow:
            copied = self._cow(slot, len(blocks) - 1)
            assert copied is not None, \
                "COW block was reserved by admission accounting"
        self.admitted += 1
        self._dirty = True
        self.blocks_hwm = max(self.blocks_hwm, self.blocks_in_use)
        return slot, reuse

    def _cow(self, slot: int, chunk: int) -> int | None:
        """Clone the (shared/immutable) block at ``chunk`` into a private
        copy before the slot writes into it. Returns the new block, or
        None when the pool is exhausted (growth path backpressure; the
        admission path pre-reserves, so there it cannot fail)."""
        old = int(self.tables[slot, chunk])
        new = self._pop_block()
        if new is None:
            return None
        self.state = self._copy_fn(self.state, jnp.int32(old), jnp.int32(new))
        self.ref[new] = 1
        self.tables[slot, chunk] = new
        self._deref(old)
        self.cow_copies += 1
        self._dirty = True
        return new

    def writable(self, slot: int, n: int) -> int:
        """Make the blocks covering the next ``n`` positions of ``slot``
        writable — allocating fresh blocks at chunk boundaries and
        copy-on-writing shared/registered ones. Returns how many of the
        ``n`` tokens can actually be written this tick (0 = the slot must
        stall; the engine applies backpressure or raises on a full
        deadlock)."""
        if not self._needs_blocks:
            return n
        bs = self.block_size
        start = int(self.lengths[slot])
        ok = 0
        for p in range(start, start + n):
            c = p // bs
            if c >= self.max_blocks:
                break
            b = int(self.tables[slot, c])
            if b < 0:
                nb = self._pop_block()
                if nb is None:
                    break
                self.ref[nb] = 1
                self.tables[slot, c] = nb
                self._dirty = True
            elif self.ref[b] > 1 or b in self._key_of:
                if self._cow(slot, c) is None:
                    break
            ok += 1
        self.blocks_hwm = max(self.blocks_hwm, self.blocks_in_use)
        return ok

    def reserve(self, slot: int, k: int) -> int:
        """Megatick pre-allocation: make the blocks covering the slot's
        next ``k`` write positions writable BEFORE the fused program
        runs (allocating at chunk boundaries, copy-on-writing
        shared/registered blocks — same mechanics as :meth:`writable`).
        ``k`` covers EVERY position the megatick will write: pure
        decode steps, and in a MIXED megatick the prompt-chunk tokens
        plus the piggybacked decode steps together (one call per slot
        per dispatch — the engine shrinks the prefill span first when
        the reservation comes back short). Returns the slot's megatick
        token budget: how many of the ``k`` positions the pool can
        back. A short budget freezes the slot mid-megatick (the
        engine's per-slot budget mask), it never corrupts memory — the
        jitted scan only writes positions the reservation covered. 0
        means the slot must stall this megatick (the engine preempts a
        victim if every slot stalls)."""
        return self.writable(slot, k)

    def free(self, slot: int):
        """Release the slot. Its private blocks return to the free list;
        registered prefix blocks it referenced stay resident (LRU) for
        future prefix hits. Scans the whole table row: window reclaim
        leaves -1 holes with live chunks beyond them. Chunks deref in
        REVERSE order so registered blocks enter the resident LRU
        deepest-first — eviction then consumes chain leaves before
        chain roots, and a partially-evicted prefix keeps its matchable
        head (a child without its parent is unreachable anyway)."""
        for c in reversed(range(self.max_blocks)):
            b = int(self.tables[slot, c])
            if b < 0:
                continue
            self._deref(b)
        self.tables[slot] = -1
        self.active[slot] = False
        self.lengths[slot] = 0
        self._dirty = True

    def _release_slot(self, slot: int, tokens=None):
        """Shared eviction mechanics for :meth:`preempt` and
        :meth:`abort`: register the slot's fully-written chunks as
        prefix blocks BEFORE the references drop (so they land in the
        resident LRU instead of vanishing), free every block reference,
        and clear the device-side position (``lm.release_slot_paged``)
        so the jitted state never carries a stale length into the
        slot's inactive period."""
        if tokens is not None:
            self.register_prompt_chunks(slot, tokens)
        self.free(slot)
        self.state = lm.release_slot_paged(self.state, slot)

    def preempt(self, slot: int, tokens=None):
        """Evict the slot so its blocks can back other requests.

        ``tokens`` — the victim's effective token history (prompt plus
        generated tokens). Its fully-written chunks are registered as
        prefix blocks BEFORE the references drop, so they land in the
        resident LRU instead of vanishing: the resumed request gets a
        prefix hit and re-prefills only the final partial block and the
        last token. (Under pool pressure the resident blocks are
        ordinary eviction supply — preemption never pins memory.)"""
        self._release_slot(slot, tokens)
        self.preempted_slots += 1

    def abort(self, slot: int, tokens=None) -> int:
        """Cancellation: drop the slot mid-stream because the REQUEST
        went away (the user hung up, a timeout fired), not because the
        pool needs the memory. Same block mechanics as :meth:`preempt`
        — every reference is dropped, private blocks return to the
        free list immediately — but the registered prefix chunks of
        ``tokens`` (the victim's prompt + generated history) stay
        LRU-RESIDENT: a later identical prompt is still a prefix hit
        even though this stream never resumes. Returns the number of
        blocks the abort made re-allocatable (the ``blocks_in_use``
        delta — LRU-resident registered chunks count, they are
        ordinary eviction supply for the next admission)."""
        before = self.blocks_in_use
        self._release_slot(slot, tokens)
        self.aborted_slots += 1
        return before - self.blocks_in_use

    def reclaim_out_of_window(self, slot: int, window: int) -> int:
        """Free the slot's blocks that have rolled out of the attention
        window for good. Every decode path masks with
        ``pos >= cur_len - window`` and ``cur_len`` only grows, so a
        block whose last position is below ``lengths - window`` can
        never be attended again. Freed chunks leave ``-1`` holes (the
        paged gather/ownership paths treat -1 as invalid, so a hole is
        masked, never read). Returns the number of blocks freed."""
        if not self._needs_blocks:
            return 0
        dead_chunks = (int(self.lengths[slot]) - window) // self.block_size
        freed = 0
        for c in range(min(dead_chunks, self.max_blocks)):
            b = int(self.tables[slot, c])
            if b < 0:
                continue
            self._deref(b)
            self.tables[slot, c] = -1
            freed += 1
        if freed:
            self.blocks_reclaimed += freed
            self._dirty = True
        return freed

    # ---------------------------------------------------- fault injection
    def seize_blocks(self, n: int) -> int:
        """Fault injection: pull up to ``n`` blocks out of the FREE
        list so they back nothing until :meth:`release_seized` — a
        deterministic pool-exhaustion spike. Only free supply is
        seized (never residents, never referenced blocks), so the
        spike starves admission/growth exactly the way a burst of
        long requests would; the normal preemption/eviction machinery
        is what absorbs it. Returns how many blocks were taken."""
        taken = []
        while self._free and len(taken) < n:
            taken.append(self._free.pop())
        self._seized.extend(taken)
        self.blocks_seized += len(taken)
        return len(taken)

    def release_seized(self) -> int:
        """Return every seized block to the free list (spike over)."""
        n = len(self._seized)
        self._free.extend(reversed(self._seized))
        self._seized = []
        return n

    # ------------------------------------------------- snapshot / restore
    def snapshot_meta(self) -> dict:
        """JSON-serializable host bookkeeping (the device-side
        ``self.state`` pytree travels separately through the
        Checkpointer). Captures everything :meth:`restore_meta` needs
        to resurrect the pool bit-for-bit: tables, lengths, refcounts,
        free-list ORDER (allocation order determines block ids, which
        determine nothing semantically but keep restored runs
        byte-comparable), LRU order, and the prefix-chain registry
        (``_index``/``_children`` are derived from ``_key_of``)."""
        return {
            "geometry": {"batch": self.batch, "max_len": self.max_len,
                         "block_size": self.block_size,
                         "n_blocks": self.n_blocks},
            "tables": self.tables.tolist(),
            "lengths": self.lengths.tolist(),
            "active": self.active.tolist(),
            "ref": self.ref.tolist(),
            "free": list(self._free),
            "lru": list(self._lru.keys()),
            "key_of": [[b, key[0], list(key[1])]
                       for b, key in self._key_of.items()],
        }

    def restore_meta(self, meta: dict):
        """Rebuild host bookkeeping from :meth:`snapshot_meta` output.
        The pool must have the same geometry it was snapshotted with —
        block ids are geometry-relative, so restoring into a different
        shape would silently corrupt; raise instead."""
        g = meta["geometry"]
        mine = {"batch": self.batch, "max_len": self.max_len,
                "block_size": self.block_size, "n_blocks": self.n_blocks}
        if g != mine:
            raise ValueError(
                f"pool geometry mismatch: snapshot {g} vs engine {mine}")
        self.tables = np.asarray(meta["tables"], np.int32)
        self.lengths = np.asarray(meta["lengths"], np.int32)
        self.active = np.asarray(meta["active"], bool)
        self.ref = np.asarray(meta["ref"], np.int32)
        self._free = [int(b) for b in meta["free"]]
        self._lru = OrderedDict((int(b), True) for b in meta["lru"])
        self._seized = []
        self._key_of = {int(b): (int(parent), tuple(toks))
                        for b, parent, toks in meta["key_of"]}
        self._index = {key: b for b, key in self._key_of.items()}
        self._children = {}
        for b, (parent, _) in self._key_of.items():
            if parent >= 0:
                self._children.setdefault(parent, set()).add(b)
        self._dirty = True

    def advance(self, slot: int, n: int):
        """Record that `slot` consumed n tokens this tick (host mirror;
        the device cur_len advanced inside the jitted step)."""
        self.lengths[slot] += n

    def sync(self):
        """Mirror the host block table into the jitted state (no-op when
        unchanged — admission/growth/COW set the dirty bit)."""
        if self._dirty:
            self.state = {**self.state,
                          "block_tables": jnp.asarray(self.tables)}
            self._dirty = False

    # --------------------------------------------------------------- metrics
    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def n_free(self) -> int:
        return self.batch - self.n_active

    def occupancy(self) -> float:
        return self.n_active / self.batch

    def metrics(self) -> dict:
        return {
            "kv_blocks": self.n_blocks,
            "kv_blocks_in_use": self.blocks_in_use,
            "kv_blocks_resident": self.blocks_resident,
            "kv_block_occupancy": round(self.block_occupancy(), 4),
            "kv_blocks_hwm": self.blocks_hwm,
            "kv_max_blocks_in_use": self.max_blocks_in_use,
            "kv_gather_width": self.gather_width(),
            "kv_hbm_vs_contiguous": round(self.hbm_fraction_vs_contiguous(),
                                          4),
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_hit_rate": round(self.prefix_hits
                                     / max(self.admitted, 1), 4),
            "cow_copies": self.cow_copies,
            "block_evictions": self.evictions,
            "kv_blocks_reclaimed": self.blocks_reclaimed,
            "kv_slots_aborted": self.aborted_slots,
            "kv_blocks_seized": self.blocks_seized,
        }
