"""Paged KV cache: ONE block pool + per-slot block tables.

Capability parity: vLLM's PagedAttention memory architecture, realized
against this repo's stacked fixed-shape serving stack. PRs 2-5 stored
KV three different ways — the dense per-slot ring [L, 2, B, H, Smax, D]
(generation.py), the prefix block pool [L, 2, NB, H, Bt, D]
(prefix_cache.py), and spec-verify's write-masked scatters — stitched
together by compiled gather-copies. Here they collapse into ONE paged
layout:

  * ``BlockPool`` — the single device pool [L, 2, NBtotal, H, Bt, D]
    (+ mirrored int8 scales [L, 2, NBtotal, H, 1, Bt]) plus a host
    free-list allocator with per-block refcounts. A block is storage
    for Bt consecutive token positions of ONE sequence; who uses it is
    pure host bookkeeping (refcounts), so prefix sharing and
    copy-on-write forking are index operations, not data movement.
  * per-slot ``block_tables`` [B, Smax/Bt] int32 live in the engine as
    pure data: position ``s`` of slot ``b`` resolves to
    ``pool[l, kv, tables[b, s // Bt], h, s % Bt, :]``. Unmapped entries
    hold the sentinel ``num_blocks`` — a write through a sentinel (or a
    masked row sent to position Smax) lands out of bounds and is
    DROPPED (``mode="drop"``), the same write-mask discipline as the
    dense path, and the FIFTH client of the decode_attention
    ``cache_lens < Smax`` clamp inventory.
  * ``PagedPrefixStore`` / ``PagedPrefixCache`` — the radix-store
    machinery of prefix_cache.py re-pointed at the shared pool: adopt
    = writing the matched chain's pool indices into the slot's table
    (+refcount; ZERO device copies), publish = taking a store
    reference on the slot's own prompt blocks (zero-copy commit).
    Store eviction merely drops the store's reference; the block
    physically frees when its last user (slot table or store) lets go.
  * copy-on-write: a slot about to write into a block with
    refcount > 1 first allocates a private block and copies just that
    block (ONE fixed-shape compiled dispatch, src/dst as data). In the
    steady serving flow writes never land in shared blocks (adoption
    and publication are block-aligned and strictly below every write
    position), so COW exists as the invariant guard — and as the
    primitive that makes ``ServingEngine.fork_slot`` (parallel
    sampling / N-best) nearly free.

Memory math: the dense layout reserves ``B x Smax`` positions whether
used or not; the pool holds ``NBtotal x Bt`` positions shared by
everything (slots, prefixes, forks — refcounted blocks counted once),
so slot capacity is bounded by actual token residency, not slot count.
"""
from __future__ import annotations


import numpy as np

from .prefix_cache import PrefixNode, PrefixStore

__all__ = ["BlockPool", "PagedPrefixStore", "PagedPrefixCache",
           "counted_jit", "flat_gather_view"]


def counted_jit(jit_cache, key, build, bump, donate=()):
    """ONE owner for the retrace-spy jit wrapper the serving stack's
    zero-retrace contracts are asserted against: ``bump()`` runs at
    TRACE time only (python side effects execute only while tracing),
    so the counter counts executable builds, not calls. One place, so
    the engine's and the pool's spies cannot drift."""
    import jax
    fn = jit_cache.get(key)
    if fn is None:
        inner = build()

        def spied(*args):
            bump()
            return inner(*args)
        fn = jax.jit(spied, donate_argnums=donate)
        jit_cache[key] = fn
    return fn


def _pool_sharding():
    """The pool's head-sharded layout under an active mp mesh
    (NamedSharding over P(None, None, None, 'mp', None, None) — axis 3
    is the head axis of both the kv blocks and the int8 scales), else
    None. The pool executables below constrain their kv/sc outputs
    with it so every donation round-trip hands back a buffer in the
    SAME layout it consumed — no silent resharding between a COW copy
    / migration write and the next engine step. All the block-index
    slices run on the (replicated) NB axis, so none of these dispatches
    needs a collective."""
    from ..parallel import current_mesh
    mesh = current_mesh()
    if mesh is None or dict(mesh.shape).get("mp", 1) < 2:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(None, None, None, "mp", None, None))


class BlockPool:
    """Host allocator for the ONE paged KV pool.

    Owns the free list and per-block refcounts; the device arrays
    themselves are built by ``FusedDecoder.init_paged_cache`` and ride
    the engine's compiled steps as donated buffers (the pool object
    must stay pure host state so it can be shared/inspected without
    touching the device)."""

    def __init__(self, num_blocks, block_tokens, max_seq_len):
        self.num_blocks = int(num_blocks)
        self.block_tokens = int(block_tokens)
        self.smax = int(max_seq_len)
        if self.num_blocks < 1:
            raise ValueError("BlockPool needs num_blocks >= 1")
        bt = self.block_tokens
        if bt < 1 or bt & (bt - 1):
            raise ValueError(
                f"BlockPool block_tokens must be a power of two >= 1, "
                f"got {bt} (it is the serving engine's prefill_cap — "
                "ONE knob for the prefill ladder, the prefix blocks, "
                "and the pool block size)")
        if self.smax % bt:
            # fail HERE with a clear message instead of a downstream
            # gather OOB: a non-aligned table would leave a ragged last
            # block whose positions index past Bt
            raise ValueError(
                f"BlockPool: max_seq_len {self.smax} must be a multiple "
                f"of block_tokens {bt} — the per-slot block table has "
                f"Smax/Bt entries and position s resolves to "
                "(table[s // Bt], s % Bt); a ragged tail block would "
                "gather out of bounds")
        self.refcounts = np.zeros(self.num_blocks, np.int32)
        # pop() from the end: low ids hand out first (stable tests)
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._jit_cache = {}
        self.trace_count = 0             # COW copy-path retrace spy
        self.used_peak = 0               # residency high-water mark

    # ---------------------------------------------------------- allocator
    @property
    def free_count(self):
        return len(self._free)

    @property
    def used(self):
        return self.num_blocks - len(self._free)

    def alloc(self, n=1):
        """Take ``n`` blocks (refcount 1 each); None if the free list is
        short — all-or-nothing, the caller reclaims/backs off."""
        if len(self._free) < int(n):
            return None
        ids = [self._free.pop() for _ in range(int(n))]
        self.refcounts[ids] = 1
        if self.used > self.used_peak:
            self.used_peak = self.used
        return ids

    def ref(self, blocks):
        for b in blocks:
            if self.refcounts[b] < 1:
                raise RuntimeError(
                    f"BlockPool.ref on free block {int(b)} — a table or "
                    "store entry outlived its allocation")
            self.refcounts[b] += 1

    def deref(self, blocks):
        for b in blocks:
            if self.refcounts[b] < 1:
                raise RuntimeError(
                    f"BlockPool refcount underflow on block {int(b)}")
            self.refcounts[b] -= 1
            if self.refcounts[b] == 0:
                self._free.append(int(b))

    def stats(self):
        return {"blocks_total": self.num_blocks, "blocks_used": self.used,
                "blocks_free": self.free_count}

    def gauges(self):
        """Prometheus-ready pool gauges (telemetry.render_prometheus and
        telemetry.snapshot consume these): residency now + the lifetime
        high-water mark — the number an operator sizes
        ``PADDLE_SERVING_KV_BLOCKS`` against."""
        return {"kv_blocks_total": self.num_blocks,
                "kv_blocks_used": self.used,
                "kv_blocks_free": self.free_count,
                "kv_blocks_used_peak": self.used_peak}

    # -------------------------------------------------------- the COW copy
    def _bump_traces(self):
        self.trace_count += 1

    @staticmethod
    def _pin(out, sh):
        """Constrain the pool arrays of ``out`` to the head-sharded
        layout ``sh`` (no-op when unsharded) — see _pool_sharding."""
        if sh is None:
            return out
        import jax
        out = dict(out, kv=jax.lax.with_sharding_constraint(
            out["kv"], sh))
        if "sc" in out:
            out["sc"] = jax.lax.with_sharding_constraint(out["sc"], sh)
        return out

    def _build_copy(self):
        import jax
        sh = _pool_sharding()

        def copy(caches, src, dst):
            kv = caches["kv"]
            L, _, _, H, Bt, D = kv.shape
            blk = jax.lax.dynamic_slice(kv, (0, 0, src, 0, 0, 0),
                                        (L, 2, 1, H, Bt, D))
            out = dict(caches, kv=jax.lax.dynamic_update_slice(
                kv, blk, (0, 0, dst, 0, 0, 0)))
            if "sc" in caches:
                sc = caches["sc"]
                sb = jax.lax.dynamic_slice(sc, (0, 0, src, 0, 0, 0),
                                           (L, 2, 1, H, 1, Bt))
                out["sc"] = jax.lax.dynamic_update_slice(
                    sc, sb, (0, 0, dst, 0, 0, 0))
            return self._pin(out, sh)
        return copy

    def copy_block(self, caches, src, dst):
        """Device-copy pool block ``src`` -> ``dst`` (kv + int8 scales)
        in ONE fixed-shape dispatch; src/dst are data. The caches dict
        (WITHOUT the table — pure pool arrays) is donated and the
        updated dict returned. This is the entire cost of a COW fault:
        one block, not a row, not the pool."""
        import jax.numpy as jnp
        fn = counted_jit(self._jit_cache, ("copy",), self._build_copy,
                         self._bump_traces, donate=(0,))
        return fn(caches, jnp.asarray(src, jnp.int32),
                  jnp.asarray(dst, jnp.int32))

    # ------------------------------------------- block transfer (migration)
    # The live-migration primitive (and the groundwork for cross-replica
    # prefix shipping): ONE pool block moves device <-> host per
    # fixed-shape dispatch with the block index as DATA, so exporting a
    # whole slot is n_blocks reuses of one executable each way — zero
    # retraces across any sequence length, same discipline as copy_block.
    def _build_read(self):
        import jax
        # the exported block leaves as FULLY REPLICATED data (P() on
        # every axis): read_block hands it to np.asarray for the host
        # migration payload, and a replicated output makes that one
        # device-local copy instead of a cross-device assembly
        from ..parallel import current_mesh
        mesh = current_mesh()
        rep = None
        if mesh is not None and dict(mesh.shape).get("mp", 1) >= 2:
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(mesh, P())

        def read(caches, src):
            kv = caches["kv"]
            L, _, _, H, Bt, D = kv.shape
            out = {"kv": jax.lax.dynamic_slice(kv, (0, 0, src, 0, 0, 0),
                                               (L, 2, 1, H, Bt, D))}
            if "sc" in caches:
                out["sc"] = jax.lax.dynamic_slice(
                    caches["sc"], (0, 0, src, 0, 0, 0),
                    (L, 2, 1, H, 1, Bt))
            if rep is not None:
                out = {k: jax.lax.with_sharding_constraint(v, rep)
                       for k, v in out.items()}
            return out
        return read

    def read_block(self, caches, src):
        """Gather ONE pool block to host arrays ``{"kv"[, "sc"]}`` —
        the migration-export half of the transfer primitive. The caches
        are NOT donated (the pool keeps serving while a slot exports)."""
        import jax.numpy as jnp
        fn = counted_jit(self._jit_cache, ("read",), self._build_read,
                         self._bump_traces)
        out = fn(caches, jnp.asarray(src, jnp.int32))
        return {k: np.asarray(v) for k, v in out.items()}

    def _build_write(self):
        import jax
        sh = _pool_sharding()

        def write(caches, blk, dst):
            kv = caches["kv"]
            out = dict(caches, kv=jax.lax.dynamic_update_slice(
                kv, blk["kv"].astype(kv.dtype), (0, 0, dst, 0, 0, 0)))
            if "sc" in caches:
                sc = caches["sc"]
                out["sc"] = jax.lax.dynamic_update_slice(
                    sc, blk["sc"].astype(sc.dtype), (0, 0, dst, 0, 0, 0))
            return self._pin(out, sh)
        return write

    def write_block(self, caches, block, dst):
        """Scatter one exported host block into pool block ``dst`` —
        the migration-import half. The caches dict is donated like every
        other pool-mutating dispatch; returns the updated dict. The
        block must match this pool's layout exactly (the engine-level
        import validates shapes with a readable error first)."""
        import jax.numpy as jnp
        fn = counted_jit(self._jit_cache, ("write",), self._build_write,
                         self._bump_traces, donate=(0,))
        blk = {k: jnp.asarray(v) for k, v in block.items()
               if k in ("kv", "sc")}
        return fn(caches, blk, jnp.asarray(dst, jnp.int32))


class PagedPrefixStore(PrefixStore):
    """The radix store of prefix_cache.py, re-pointed at the SHARED
    BlockPool: a node's ``block`` is a pool id the store holds one
    refcount on. ``num_blocks`` becomes the store's PIN BUDGET (how
    many pool blocks the prefix cache may keep alive), not a private
    free list — there is exactly one physical pool.

    Publication is zero-copy (``publish`` takes a reference on the
    slot's own block), and eviction merely drops the store's
    reference: a block shared with a live slot table stays resident
    until that slot finishes. ``reclaim`` is the memory-pressure hook
    the engine calls when the pool's free list runs short — prefix
    blocks are cache, droppable by LRU, never load-bearing."""

    def __init__(self, num_blocks, block_tokens, pool):
        super().__init__(num_blocks, block_tokens)
        if pool.block_tokens != int(block_tokens):
            raise ValueError(
                f"PagedPrefixStore block_tokens={int(block_tokens)} but "
                f"the shared BlockPool has block_tokens="
                f"{pool.block_tokens} — the prefix blocks ARE pool "
                "blocks, the sizes must be ONE value")
        self.pool = pool
        self._free = []                  # no private ids in paged mode
        self._pinned = 0

    def insert(self, tokens):
        raise NotImplementedError(
            "PagedPrefixStore has no private blocks to allocate — "
            "publication is zero-copy; use publish(tokens, block_ids) "
            "with the owning slot's pool block ids")

    def publish(self, tokens, block_ids):
        """Paged commit: walk/extend the radix chain over ``tokens``'
        full blocks, taking a store reference on ``block_ids[i]`` (the
        owning slot's pool block) for every node that does not exist
        yet. Returns ``[(node, is_new), ...]`` root-first — no device
        copy ever happens; dedup hits simply resolve to the already-
        published block. Stops early when the pin budget is exhausted
        and nothing is evictable (partial chains are valid, as in the
        dense store)."""
        out = []
        node = self._root
        keys = self._blocks_of(tokens)
        try:
            for i, key in enumerate(keys):
                if i >= len(block_ids):
                    break
                child = node.children.get(key)
                if child is None:
                    if self._pinned >= self.num_blocks:
                        victim = self._lru_evictable_leaf()
                        if victim is None:
                            break        # budget full, nothing cold
                        self._evict(victim)
                    blk = int(block_ids[i])
                    self.pool.ref([blk])
                    self._pinned += 1
                    child = PrefixNode(key, node, blk)
                    node.children[key] = child
                    self._update_evictable(node)
                    self.committed_blocks += 1
                    out.append((child, True))
                else:
                    out.append((child, False))
                self._touch(child)
                # pin the chain under construction (same rationale as
                # the dense insert: a long chain must not evict its own
                # fresh tail to pin the next block)
                self.acquire((child,))
                node = child
        finally:
            self.release(n for n, _ in out)
        return out

    def _evict(self, node):
        blk = super()._evict(node)
        self._pinned -= 1
        # drop the STORE's reference only: a slot still mapping this
        # block keeps it resident; it frees when the last user derefs
        self.pool.deref([blk])
        return blk

    def reclaim(self, n_free):
        """Evict LRU refcount-0 leaves until the POOL free list grew by
        ``n_free`` blocks (or nothing evictable remains). Prefers
        store-only blocks (pool refcount 1 — evicting them actually
        frees memory); falls back to shared nodes to unlock the
        eviction cascade (a parent becomes a leaf only once its
        children are gone). Returns how many blocks were freed."""
        start = self.pool.free_count
        while self.pool.free_count - start < int(n_free):
            singles = [x for x in self._evictable
                       if self.pool.refcounts[x.block] == 1]
            pickable = singles or self._evictable
            if not pickable:
                break
            self._evict(min(pickable, key=lambda x: x.last_use))
        return self.pool.free_count - start

    def stats(self):
        s = super().stats()
        # budget headroom, not a private free list (the POOL owns the
        # physical free list; leak visibility lives in the engine's
        # kv_blocks_used + kv_blocks_free == NBtotal reconciliation)
        s["blocks_free"] = self.num_blocks - s["blocks_used"]
        return s


class PagedPrefixCache:
    """The paged twin of prefix_cache.PrefixCache: same engine-facing
    surface (lookup / hit counters / ``store`` / ``block_tokens`` /
    ``trace_count``), but adopt and publish are INDEX operations on the
    slot's block table — zero device dispatches, zero copies. One
    PagedPrefixCache belongs to one engine (the tables do); the dense
    PrefixCache remains the cross-engine-shareable flavor."""

    def __init__(self, num_blocks, block_tokens, pool):
        self.store = PagedPrefixStore(num_blocks, block_tokens, pool)
        self.pool = pool
        self.num_blocks = int(num_blocks)
        self.block_tokens = int(block_tokens)
        self.trace_count = 0             # index writes never trace

    def lookup(self, tokens):
        """Longest ADOPTABLE chain — prefix_cache.lookup_adoptable is
        the ONE owner of the cap + counter rules, so the dense and
        paged hit semantics cannot drift."""
        from .prefix_cache import lookup_adoptable
        return lookup_adoptable(self.store, self.block_tokens, tokens)

    def adopt_into(self, tables, slot, nodes):
        """THE zero-copy prefix hit: write the chain's pool indices
        into the slot's table row and take a per-slot reference on each
        block. Returns the adopted token count. (The dense path's
        compiled gather-splat is an index write here — a hit costs
        nanoseconds of host bookkeeping, not an HBM block copy.)"""
        ids = [nd.block for nd in nodes]
        self.pool.ref(ids)
        tables[slot, :len(ids)] = ids
        return len(ids) * self.block_tokens

    def publish_from(self, tables, slot, tokens):
        """Zero-copy commit-on-prefill: publish every full block of
        ``tokens`` by referencing the slot's OWN pool blocks. Dedup
        hits against an already-published twin switch the slot's table
        onto the shared block and free the private copy (storage
        dedup — the intra-admission gang case). Returns #new blocks."""
        t = np.asarray(tokens).reshape(-1)
        nfull = t.size // self.block_tokens
        ids = [int(tables[slot, i]) for i in range(nfull)]
        if any(i >= self.pool.num_blocks for i in ids):
            raise RuntimeError(
                "publish_from before the slot's prompt blocks were "
                "mapped — prefill must land before publication")
        plan = self.store.publish(t, ids)
        new = 0
        for i, (node, is_new) in enumerate(plan):
            if is_new:
                new += 1
            elif ids[i] != node.block:
                # the slot computed a private copy of content someone
                # already published: point at the shared block, drop
                # the duplicate (decode never writes below plen, so
                # sharing a full prompt block is always safe)
                self.pool.ref([node.block])
                self.pool.deref([ids[i]])
                tables[slot, i] = node.block
        return new


def flat_gather_view(pool_l, tbl, tslot, smax, sc_l=None):
    """Per-TOKEN gather-through-table view for the flat budget core's
    dense-fallback attention (generation._build_flat_budget_core):
    resolve each flat-stream token's slot through the block tables and
    materialize its full [Smax]-position K/V row.

    pool_l: [2, NB, Hk, Bt, D] (ONE layer's pool slice); tbl:
    [B, Smax/Bt] int32 per-slot tables (sentinel NB for unmapped);
    tslot: [T] int32 per-token slot ids ALREADY CLAMPED in-bounds
    (pad tokens point at any valid slot — their positions are masked
    by the caller); sc_l: optional [2, NB, Hk, 1, Bt] int8 dequant
    scales (the int8 pool flavor — quantized pools come through here
    whenever decode_attention.paged_flat_i8_is_supported refuses the
    shape, e.g. Bt below the int8 sublane minimum of 32; this view is
    the parity ORACLE the flat i8 Pallas kernel is tested against).
    Returns [2, T, Hk, Smax, D] float32 (dequantized when sc_l given).

    Sentinel/unmapped table entries clamp to an arbitrary block —
    their positions are >= the row's lens and masked by the caller's
    block-causal mask, exactly like the row-aligned gather fallback."""
    import jax.numpy as jnp
    nb = pool_l.shape[1]
    hk, bt, d = pool_l.shape[2], pool_l.shape[3], pool_l.shape[4]
    rows = jnp.take(tbl, tslot, axis=0)               # [T, Smax/Bt]
    tc = jnp.minimum(rows, nb - 1)
    kvg = jnp.take(pool_l, tc, axis=1)          # [2, T, Nblk, Hk, Bt, D]
    kvg = jnp.transpose(kvg, (0, 1, 3, 2, 4, 5)).reshape(
        2, tslot.shape[0], hk, smax, d)
    if sc_l is None:
        return kvg.astype(jnp.float32)
    scg = jnp.take(sc_l, tc, axis=1)            # [2, T, Nblk, Hk, 1, Bt]
    scg = jnp.transpose(scg, (0, 1, 3, 4, 2, 5)).reshape(
        2, tslot.shape[0], hk, 1, smax)
    return kvg.astype(jnp.float32) * jnp.swapaxes(scg, -1, -2)
