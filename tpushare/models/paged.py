"""Paged KV cache: block-table memory management for serving.

SlotServer (models/serving.py) reserves max_len cache rows per slot;
under bin-packed HBM budgets (the whole point of the plugin) that
wastes the difference between a slot's actual length and max_len. The
paged cache allocates fixed-size KV *blocks* from a shared pool and
maps them per slot through a block table — storage scales with live
tokens, not slots×max_len, so a tenant fits more concurrent sequences
into its HBM share.

Design (TPU-first):
- Pool: [L, n_blocks, block_size, Hkv*Dh] per K/V — static shapes; a
  page holds its kv heads merged, the shape the paged kernels read.
- Block table: [n_slots, max_blocks] int32 pool indices; host-side
  free-list decides allocation (admit/evict), device code only ever
  sees static-shaped gathers/scatters.
- Decode: one jitted step writes each active slot's new KV into
  (layer, block_table[slot, t // bs], t % bs) of the stacked pool via
  scatter and attends straight off it through forward()'s paged-cache
  branch: the stacks are the layer loop's carry and are donated into
  the step, so the rows land in place and no layer of the pool is
  sliced out, restacked or copied. The pallas paged-attention kernel on
  TPU reads the stack at its layer (layer and block table ride scalar
  prefetch into the BlockSpec index_map — pages are DMA'd from HBM
  once, nothing is gathered into a dense view); elsewhere one gather
  of the slots' blocks with the ragged kv_mask.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpushare.models.serving import upload_mirror
from tpushare.models.transformer import TransformerConfig, forward
from tpushare.parallel.multihost import addressable_fetch, host_scalar
from tpushare.router.chainkeys import chain_keys
from tpushare.utils.profiling import span


class SlotCapacityExceeded(RuntimeError):
    """ONE slot's block table is full (its sequence outgrew
    max_blocks x block_size): a per-slot terminal condition, not pool
    pressure and not a device fault. Carries ``slot`` so the engine
    can retire exactly that request (tokens so far) instead of
    preempting or quarantining the whole batch over one sequence
    hitting its ceiling."""

    def __init__(self, slot: int, msg: str):
        super().__init__(msg)
        self.slot = slot


class PoolExhausted(RuntimeError):
    """Transient pool/slot pressure: the block pool (or the slot
    array) cannot hold this admission RIGHT NOW, but blocks free as
    in-flight generations complete. The serving engine's admission and
    preemption paths catch exactly this type — a broad
    ``except RuntimeError`` there would also swallow genuine
    device/runtime failures (an ``XlaRuntimeError`` out of a forward)
    and misread them as pool pressure, holding a request forever
    instead of routing the failure to the quarantine/replay path.

    Tier-aware (ISSUE 9): ``tenant``/``tier`` carry who hit the
    pressure when the raising path knows (admission does; the batched
    growth path doesn't), so the engine's preempt-low-for-high and
    hold policies can act per-tier instead of treating every
    exhaustion as anonymous."""

    def __init__(self, msg: str, *, tenant: Optional[str] = None,
                 tier: Optional[str] = None):
        super().__init__(msg)
        self.tenant = tenant
        self.tier = tier


class QuotaExceeded(PoolExhausted):
    """A per-tenant KV-block quota verdict (tpushare.slo.quota), not
    pool-wide pressure: ``kind`` is "ceiling" (the tenant's own burst
    cap — only its own completions cure it) or "reserve" (the
    admission would dig into another tenant's guaranteed floor — any
    completion cures it). A PoolExhausted subclass so the engine's
    hold/preempt machinery composes; the engine branches on ``kind``
    to aim preemption and rejection per tier. ``need`` carries the
    fresh-block count the verdict refused so the engine can tell a
    curable reserve hold from one no amount of waiting can satisfy
    (need > pool minus other tenants' floors)."""

    def __init__(self, msg: str, *, kind: str,
                 tenant: Optional[str] = None,
                 tier: Optional[str] = None,
                 need: Optional[int] = None):
        super().__init__(msg, tenant=tenant, tier=tier)
        self.kind = kind
        self.need = need


@dataclasses.dataclass
class PagedCache:
    """Pool + table state (a pytree; host mutates table via methods)."""
    # A page holds its kv heads merged, [bs, Hkv*Dh]: the shape the
    # paged kernels read, so the forward hands them the stack as it
    # lies (a split [.., Hkv, Dh] minor pair is a relayout of the whole
    # layer on the chip). Whoever wants heads apart reshapes what is
    # small: gathered rows, one block's payload (block_shapes).
    pool_k: jnp.ndarray        # [L, n_blocks, bs, Hkv*Dh]
    pool_v: jnp.ndarray
    block_table: jnp.ndarray   # [n_slots, max_blocks] int32 (-1 = none)
    lengths: jnp.ndarray       # [n_slots] int32
    block_size: int
    free: List[int]            # host-side free list of pool block ids
    # kv_quant pools: int8 pool_k/pool_v plus per-(slot-in-block,
    # kv-head) scales stored in the decode kernel's page layout
    # [L, n_blocks, Hkv_pad, bs] (quant.scales_to_pool_layout) so the
    # hot step never transposes the pool; None for full precision.
    pool_k_scale: Optional[jnp.ndarray] = None
    pool_v_scale: Optional[jnp.ndarray] = None
    # A third kind of row under the same block table, for a family that
    # caches one (latent: the selector's keys of the full layers,
    # [n_full, n_blocks, bs, index_dim]); None for every other.
    pool_x: Optional[jnp.ndarray] = None
    # Prefix-cache bookkeeping (host-side, all empty unless the prefix
    # path is used). A *published* block holds the KV of one full block
    # of some prompt whose entire token chain up to that block is the
    # index key — an exact identity (incremental sha256 over the token
    # bytes), so a hit is bit-identical KV, never a lossy lookalike.
    refs: Dict[int, int] = dataclasses.field(default_factory=dict)
    index: Dict[bytes, int] = dataclasses.field(default_factory=dict)
    chains: Dict[int, bytes] = dataclasses.field(default_factory=dict)
    # Zero-ref published blocks, oldest-first: data stays resident so a
    # later admit with the same prefix still hits; reclaimed (and
    # unpublished) only under pool pressure.
    lru: "collections.OrderedDict[int, None]" = dataclasses.field(
        default_factory=collections.OrderedDict)
    # Host mirrors of the scheduler state the engine tick branches on.
    # Every table entry and every length is decided (or deducible) on
    # the host — admit/evict pick the block ids, decode advances active
    # slots by exactly 1, a speculative round by the fetched a+1 — so
    # the hot loop never needs to device_get control state; the device
    # copies exist only for the jitted gathers/scatters. Mutate ONLY
    # through the module's host-side functions (or the servers' step
    # bookkeeping), which keep both representations in lockstep.
    # Like ``free``/``refs``/``lru``, the mirrors are SHARED across
    # dataclasses.replace generations and mutated in place: a
    # PagedCache held from before a mutating call is invalidated by it
    # (snapshot-and-retry is not a supported pattern on any of the
    # host-side state, mirrors included).
    table_np: Optional[np.ndarray] = None
    lengths_np: Optional[np.ndarray] = None
    # Host offload tier (r18; models/kvtier.HostKvTier or None).
    # Shared across dataclasses.replace generations like the other
    # host-side state. When attached, a published block reclaimed
    # from the zero-ref LRU under ADMISSION pressure is DEMOTED (its
    # KV copied to host numpy, keyed by its chain digest) instead of
    # destroyed — and a later admit whose chain misses the device
    # index but hits the tier PROMOTES the blocks back instead of
    # recomputing the prefix. Growth-path reclaims (_grow_active,
    # inside the policed step loop) still destroy: the device_get a
    # demotion needs is exactly the sync the one-fetch-per-tick
    # invariant forbids there, and growth reclaims are the cold tail
    # of the LRU anyway.
    host_tier: Optional[Any] = None
    # blk -> tenant that paid for the block's FIRST write (admission
    # quota principal) — the host-tier byte ledger charges demoted
    # blocks to this tenant. Overwritten on every fresh allocation,
    # so stale entries are bounded by the pool size and never read
    # (demotion reads an entry the moment alloc reclaims it).
    owners: Dict[int, str] = dataclasses.field(default_factory=dict)
    # kv heads of one page (the pool shape keeps only Hkv*Dh): what a
    # block's payload is split by on its way out of the pool.
    kv_heads: int = 1

    @property
    def n_slots(self) -> int:
        return self.block_table.shape[0]

    @property
    def max_blocks(self) -> int:
        return self.block_table.shape[1]

    def host_table(self) -> np.ndarray:
        """Host truth of the block table; built lazily (one sync) for
        hand-constructed caches, exact-by-construction afterwards.
        np.array, not np.asarray: the latter returns a READ-ONLY view
        of the jax buffer and every mutator writes in place."""
        if self.table_np is None:
            self.table_np = np.array(self.block_table)
        return self.table_np

    def host_lengths(self) -> np.ndarray:
        if self.lengths_np is None:
            self.lengths_np = np.array(self.lengths)
        return self.lengths_np

    def live_blocks(self) -> int:
        return int((self.host_table() >= 0).sum())


def init_paged_cache(cfg: TransformerConfig, *, n_slots: int,
                     n_blocks: int, block_size: int = 16,
                     max_blocks_per_slot: Optional[int] = None,
                     kv_quant: bool = False) -> PagedCache:
    """The last pool block is a sacrificial 'trash' block: slots with
    no table entry (inactive / -1) read and write there, never
    corrupting live blocks. It is excluded from the free list.

    ``kv_quant``: int8 pools + per-row scales — the pool holds ~2x
    (bf16) the tokens in the same HBM. Composes with prefix caching
    (shared blocks carry their scale rows along). Reads take the
    gathered-view path (transformer.py paged+kvq note)."""
    mb = max_blocks_per_slot or n_blocks
    # A family whose layers cache rows of more than one shape says what
    # its pools hold (latent.LatentConfig.pool_shapes); all of them lie
    # under the one block table.
    shape_x = None
    if hasattr(cfg, "pool_shapes"):
        shape_k, shape_v, shape_x = cfg.pool_shapes(n_blocks, block_size)
    else:
        shape_k = shape_v = (cfg.n_layers, n_blocks, block_size,
                             cfg.n_kv_heads * cfg.head_dim)
    kv_dtype = jnp.int8 if kv_quant else cfg.dtype
    if kv_quant:
        from tpushare.models.quant import kv_scale_pad
        # Kernel page layout from init on (no per-step transpose).
        scale_shape = (cfg.n_layers, n_blocks,
                       kv_scale_pad(cfg.n_kv_heads), block_size)
    return PagedCache(
        pool_k=jnp.zeros(shape_k, kv_dtype),
        pool_v=jnp.zeros(shape_v, kv_dtype),
        block_table=jnp.full((n_slots, mb), -1, jnp.int32),
        lengths=jnp.zeros((n_slots,), jnp.int32),
        block_size=block_size,
        free=list(range(n_blocks - 1)),
        pool_k_scale=(jnp.zeros(scale_shape, jnp.float32)
                      if kv_quant else None),
        pool_v_scale=(jnp.zeros(scale_shape, jnp.float32)
                      if kv_quant else None),
        pool_x=(jnp.zeros(shape_x, kv_dtype) if shape_x else None),
        table_np=np.full((n_slots, mb), -1, np.int32),
        lengths_np=np.zeros((n_slots,), np.int64),
        kv_heads=cfg.n_kv_heads,
    )


def blocks_needed(n_tokens: int, block_size: int) -> int:
    return -(-n_tokens // block_size)


def admit(cache: PagedCache, slot: int, n_tokens: int) -> PagedCache:
    """Host-side: reserve blocks for a prompt of ``n_tokens`` (+ room
    for the next token). Raises if the pool is exhausted."""
    need = blocks_needed(n_tokens + 1, cache.block_size)
    if need > cache.max_blocks:
        raise ValueError(f"{n_tokens} tokens exceed slot capacity")
    if need > len(cache.free):
        raise PoolExhausted(
            f"KV pool exhausted: need {need} blocks, {len(cache.free)} free")
    ids = [cache.free.pop() for _ in range(need)]
    tnp = cache.host_table()
    tnp[slot, :] = -1
    tnp[slot, :need] = ids
    cache.host_lengths()[slot] = n_tokens
    table = cache.block_table.at[slot, :].set(-1)
    table = table.at[slot, :need].set(jnp.asarray(ids, jnp.int32))
    return dataclasses.replace(
        cache, block_table=table,
        lengths=cache.lengths.at[slot].set(n_tokens))


def grow_if_needed(cache: PagedCache, slot: int) -> PagedCache:
    """Host-side: ensure the slot has a block for position lengths[slot].
    Reads only the host mirrors — no device sync on the decode path."""
    t = int(cache.host_lengths()[slot])
    bi = t // cache.block_size
    if bi >= cache.max_blocks:
        raise SlotCapacityExceeded(
            slot, f"slot {slot} exceeded max_blocks")
    if int(cache.host_table()[slot, bi]) >= 0:
        return cache
    if not cache.free:
        raise PoolExhausted("KV pool exhausted")
    blk = cache.free.pop()
    cache.host_table()[slot, bi] = blk
    return dataclasses.replace(
        cache, block_table=cache.block_table.at[slot, bi].set(blk))


def evict(cache: PagedCache, slot: int) -> PagedCache:
    """Host-side: return the slot's blocks to the pool.

    Delegates to release(): same free-list-only outcome when nothing
    is published (refs/chains empty — though blocks re-enter the free
    list leaf-first now, so allocation order of recycled ids differs
    from the pre-release ordering), and safe —
    not silently corrupting — when prefix caching is in play (freeing
    a published block while its index entry survives would let a later
    admit match a reallocated, overwritten block)."""
    return release(cache, slot)


# ---------------------------------------------------------------------------
# Automatic prefix caching (vLLM-style) over the same pool.
#
# Identity of a cached block = the exact token chain from position 0
# through the block's end (incremental sha256 over int32 token bytes).
# Positions are absolute (rope), so only prefixes anchored at 0 are
# shareable — which is exactly the serving pattern that matters (shared
# system prompts / few-shot headers). Invariants:
#   * only FULL blocks wholly inside [0, S-1) are ever published; the
#     partial tail (and the decode-growth blocks after it) are always
#     freshly allocated, so decode scatters never touch a shared block
#     (copy-on-write by construction — writes only happen at positions
#     >= S, which live in fresh blocks);
#   * at least the prompt's last token is always recomputed, so admit
#     always has real last-position logits to sample from;
#   * refs[b] counts slot tables referencing b. At zero a published
#     block parks on an LRU of resident reclaimables — a later admit
#     with the same prefix hits it for free; allocation reclaims from
#     that LRU (unpublishing) only after the free list runs dry.
# ---------------------------------------------------------------------------


# The chain-key digest moved to tpushare/router/chainkeys.py (jax-free)
# so the cluster front door can compute the SAME routing keys without
# dragging a device runtime into its process; this alias keeps the
# engine-side spelling (and every existing caller/test) unchanged.
# Byte-identity between the two import paths is pinned by
# tests/test_router.py.
_chain_keys = chain_keys


def reclaimable_blocks(cache: PagedCache) -> int:
    """Blocks allocatable right now: free list + zero-ref cached."""
    return len(cache.free) + len(cache.lru)


def alloc_blocks(cache: PagedCache, need: int) -> List[int]:
    """Pop ``need`` block ids: free list first, then reclaim the
    oldest zero-ref published blocks (unpublishing them). Mutates the
    host-side lists in place; raises with them intact on shortfall."""
    if need > reclaimable_blocks(cache):
        raise PoolExhausted(
            f"KV pool exhausted: need {need} blocks, "
            f"{len(cache.free)} free + {len(cache.lru)} reclaimable")
    ids = [cache.free.pop() for _ in range(min(need, len(cache.free)))]
    while len(ids) < need:
        blk, _ = cache.lru.popitem(last=False)          # oldest first
        key = cache.chains.pop(blk)
        cache.index.pop(key, None)
        cache.refs.pop(blk, None)
        ids.append(blk)
    return ids


def _unref(cache: PagedCache, blk: int) -> None:
    """Drop one reference to ``blk``: >0 keep; at zero, published
    blocks park on the resident LRU (still hittable), unpublished ones
    return to the free list. The single home of the refcount
    invariant — release() and admit_prefix's rollback both use it."""
    n = cache.refs.get(blk, 1) - 1
    if n > 0:
        cache.refs[blk] = n
        return
    cache.refs.pop(blk, None)
    if blk in cache.chains:
        cache.lru[blk] = None
    else:
        cache.free.append(blk)


def _demote_block(cache: PagedCache, blk: int) -> bool:
    """Copy one published block's pool rows to the host tier before a
    reclaim destroys them. Returns False when the block was dropped
    instead (no tier, policy says recompute, chaos fault, tier
    refused) — exactly the pre-r18 eviction, never corruption.

    The ``jax.device_get`` here is the d2h transfer demotion IS; it
    runs only on the ADMISSION path (admit_prefix -> demote_for_alloc),
    never inside the policed step loop — see PagedCache.host_tier."""
    tier = cache.host_tier
    key = cache.chains.get(blk)
    if tier is None or key is None:
        return False
    bs = cache.block_size
    nbytes = block_nbytes(cache)
    if tier.estimator.decide("d2h", nbytes, bs) == "recompute":
        return False
    if tier.fault_demote is not None:
        try:
            tier.fault_demote()
        except Exception:
            tier.demote_failures += 1
            return False
    t0 = time.perf_counter()
    data = jax.device_get(read_block(cache, blk))
    tier.estimator.observe_transfer("d2h", nbytes,
                                    time.perf_counter() - t0)
    return tier.put(key, data, tenant=cache.owners.get(blk),
                    tokens=bs, kind="demote")


def demote_for_alloc(cache: PagedCache, need: int) -> None:
    """Demote the zero-ref LRU blocks an allocation of ``need`` is
    about to reclaim (oldest first — the same order alloc_blocks
    consumes them). Pure copy: the reclaim itself still runs through
    alloc_blocks unchanged, so a failed/refused demotion degrades to
    the old destroy-and-recompute behavior, never to a leak."""
    if cache.host_tier is None:
        return
    shortfall = need - len(cache.free)
    if shortfall <= 0:
        return
    for blk in list(cache.lru)[:shortfall]:
        _demote_block(cache, blk)


def admit_prefix(cache: PagedCache, slot: int, prompt: np.ndarray,
                 keys: Optional[List[bytes]] = None
                 ) -> Tuple[PagedCache, int, List[int]]:
    """Reserve the slot's blocks, reusing every published block whose
    chain matches the prompt's prefix. Returns (cache, cached_len,
    blocks): the caller prefills only positions >= cached_len, and
    ``blocks`` is the slot's host-side block-id row — hand it to
    publish_prefix so neither call re-reads the device table.

    Matching stops at (S-1)//bs full blocks so the tail block (which
    decode will write into) is always fresh, and at the first chain
    miss (a chain hit implies all earlier blocks hit — the digest is
    cumulative). With a host tier attached (r18), the match continues
    past the device index into the tier: consecutive tier-resident
    chain blocks are PROMOTED into freshly-allocated pool blocks (a
    host→device upload — never a fetch) and count toward cached_len,
    so the caller prefills only what neither tier holds. ``keys``
    (>= (S-1)//bs chain digests) lets the caller hash the prompt once
    and share the list with publish_prefix."""
    S = int(prompt.shape[0])        # host array by contract (no sync)
    bs = cache.block_size
    need_total = blocks_needed(S + 1, bs)
    if need_total > cache.max_blocks:
        raise ValueError(f"{S} tokens exceed slot capacity")
    if keys is None:
        keys = _chain_keys(prompt, bs, (S - 1) // bs)
    tier = cache.host_tier
    if tier is not None:
        tier.last_promoted_n = 0
    matched: List[int] = []
    for key in keys[:(S - 1) // bs]:
        blk = cache.index.get(key)
        if blk is None:
            break
        matched.append(blk)
    # Continue the chain into the host tier: each consecutive hit is
    # promotion work for the fresh blocks allocated below. Stops at a
    # key the device index holds after all (a stale tier copy would
    # publish a duplicate chain) and at the tier's own gate — chaos
    # fault, crossover policy says recompute, or simply not resident.
    promote_keys: List[bytes] = []
    if tier is not None:
        for key in keys[len(matched):(S - 1) // bs]:
            if key in cache.index:
                break
            if not tier.begin_promote(key, tokens=bs):
                break
            promote_keys.append(key)
    # Pin the matched blocks BEFORE allocating: alloc_blocks reclaims
    # from the zero-ref LRU, and an unpinned matched block sitting
    # there could be handed out as "fresh" — silent KV corruption.
    for b in matched:
        cache.refs[b] = cache.refs.get(b, 0) + 1
        cache.lru.pop(b, None)              # resident hit: back in use
    try:
        n_need = need_total - len(matched)
        # Demote (copy to host) what this allocation is about to
        # reclaim — eviction becomes demotion, only on this path.
        demote_for_alloc(cache, n_need)
        fresh = alloc_blocks(cache, n_need)
    except RuntimeError:
        # Roll back the pins LEAF-FIRST (same invariant as release):
        # root-first re-parking would make the next reclaim orphan the
        # chain's still-resident descendants.
        for b in reversed(matched):
            _unref(cache, b)
        raise
    for b in fresh:
        cache.refs[b] = 1
    n_landed = 0
    pool_updates: Dict[str, jnp.ndarray] = {}
    if promote_keys:
        n_landed, pool_updates = _land_promoted(
            cache, promote_keys, fresh[:len(promote_keys)])
        tier.last_promoted_n = n_landed
    row = matched + fresh
    tnp = cache.host_table()
    tnp[slot, :] = -1
    tnp[slot, :need_total] = row
    cache.host_lengths()[slot] = S
    table = cache.block_table.at[slot, :].set(-1)
    table = table.at[slot, :need_total].set(jnp.asarray(row, jnp.int32))
    return (dataclasses.replace(
        cache, block_table=table,
        lengths=cache.lengths.at[slot].set(S), **pool_updates),
        (len(matched) + n_landed) * bs, row)


def _land_promoted(cache: PagedCache, keys: List[bytes],
                   blk_ids: List[int]) -> Tuple[int, Dict[str, Any]]:
    """Write promoted host-tier chains into freshly-allocated pool
    blocks (one batched scatter per pool leaf) and publish them.
    Returns (n_landed, pool-field updates for the caller's replace).

    Host→device only (``jnp.asarray`` + ``.at[].set``) — promotion
    never performs a device→host fetch, so the sync-free invariant is
    untouched wherever admission runs. Entries that vanished or fail
    shape validation between begin_promote and here (a racing
    eviction, a malformed migrated payload) break the chain at that
    block: the rest of the landing blocks stay fresh and the caller
    prefills them — token-exact, never corrupt.

    Staged entries (the overlap-window prefetch already uploaded
    them) stack device-side for free; host-sourced entries pay their
    upload here, timed as the estimator's h2d observation."""
    tier = cache.host_tier
    shapes = block_shapes(cache)
    fields = list(shapes)
    datas = []
    for key in keys:
        data, _staged = tier.take_promote(key)
        if (data is None or set(data) != set(fields)
                or any(tuple(np.shape(data[pf])) != shapes[pf]
                       for pf in fields)):
            break
        datas.append(data)
    if not datas:
        return 0, {}
    n = len(datas)
    host_bytes = sum(int(a.nbytes) for d in datas for a in d.values()
                     if isinstance(a, np.ndarray))
    t0 = time.perf_counter()
    updates: Dict[str, Any] = {}
    stacked_leaves = []
    ids = jnp.asarray(blk_ids[:n], jnp.int32)
    for pf in fields:
        stacked = jnp.stack([jnp.asarray(d[pf]) for d in datas],
                            axis=1)             # [L, n, *block row]
        stacked_leaves.append(stacked)
        pool = getattr(cache, pf)
        updates[pf] = pool.at[:, ids].set(
            stacked.reshape(pool.shape[0], n, *pool.shape[2:]))
    if host_bytes:
        # Wait on the uploads (NOT the scatters) so the h2d rate the
        # crossover policy cites is the transfer, not queue luck.
        jax.block_until_ready(stacked_leaves)
        tier.estimator.observe_transfer(
            "h2d", host_bytes, time.perf_counter() - t0)
    for key, blk in zip(keys[:n], blk_ids[:n]):
        if key not in cache.index and blk not in cache.chains:
            cache.index[key] = blk
            cache.chains[blk] = key
    return n, updates


def publish_prefix(cache: PagedCache, blocks: List[int],
                   prompt: np.ndarray,
                   keys: Optional[List[bytes]] = None) -> None:
    """Index the slot's freshly-filled full prompt blocks so later
    admits can share them. Call after the prefill scatter. In-place
    (host dicts only). First-writer-wins on identical chains published
    from racing slots — both keep their copy; one is indexed.
    ``blocks``: the slot's host-side block-id row from admit_prefix
    (no device read here). ``keys``: precomputed chain digests
    (>= S//bs of them)."""
    S = int(prompt.shape[0])        # host array by contract (no sync)
    bs = cache.block_size
    n_pub = S // bs
    if keys is None:
        keys = _chain_keys(prompt, bs, n_pub)
    for i, key in enumerate(keys[:n_pub]):
        blk = int(blocks[i])
        if blk in cache.chains or key in cache.index:
            continue
        cache.index[key] = blk
        cache.chains[blk] = key


def release(cache: PagedCache, slot: int) -> PagedCache:
    """Refcount-aware evict. Published blocks whose refcount hits zero
    stay resident on the LRU (still hittable); everything else returns
    to the free list immediately.

    Blocks park LEAF-FIRST (reversed table order): reclaim pops the
    LRU oldest-first, so a chain under pool pressure is consumed from
    its leaf inward and the surviving prefix stays matchable. Parked
    root-first, the first reclaim would take the chain ROOT —
    orphaning every still-resident descendant (chain matching stops at
    the first miss), degrading the hit rate to zero."""
    for b in reversed(cache.host_table()[slot]):
        b = int(b)
        if b >= 0:
            _unref(cache, b)
    cache.host_table()[slot, :] = -1
    cache.host_lengths()[slot] = 0
    return dataclasses.replace(
        cache,
        block_table=cache.block_table.at[slot, :].set(-1),
        lengths=cache.lengths.at[slot].set(0))



def decode_core(params, tokens, pool_k, pool_v, table, lengths, active,
                *, cfg: TransformerConfig, block_size: int,
                attn_impl: str = "auto", pctx=None, layers_hook=None,
                pool_k_scale=None, pool_v_scale=None,
                mlora_idx=None, mlora_scale: float = 1.0,
                forward_fn=None):
    """Pure-array paged decode step (jit/shard_map-friendly: no host
    state, static shapes). tokens [B, 1]; active [B] bool. Returns
    (logits, pool_k, pool_v, pool_k_scale, pool_v_scale, lengths) —
    the scale slots are None unless kv_quant pools were passed — with
    lengths advanced only for active slots. One fixed arity so every
    caller unpacks unconditionally (None is a perfectly good jit
    pytree leaf).

    Delegates to forward()'s paged-cache branch: each layer scatters
    its new KV into its pool slice and attends through the block table
    (pallas paged kernel on TPU, per-layer gathered view elsewhere).
    No [L, B, mb*bs, ...] dense cache is ever materialized.

    ``forward_fn``: a transformer.forward-shaped callable with a
    paged-cache branch — the seam that lets the MoE family
    (moe.paged_forward) ride the same block pool; default is the dense
    LM's forward."""
    del block_size  # carried by the pool shape (pool_k.shape[2])
    paged_cache = {"pool_k": pool_k, "pool_v": pool_v,
                   "table": table, "active": active}
    kvq = pool_k_scale is not None
    if kvq:
        paged_cache["pool_k_scale"] = pool_k_scale
        paged_cache["pool_v_scale"] = pool_v_scale
    fwd = forward if forward_fn is None else forward_fn
    logits, new_cache = fwd(
        params, tokens, cfg, cache=paged_cache, pos_offset=lengths,
        attn_impl=attn_impl, layers_hook=layers_hook,
        mlora_idx=mlora_idx, mlora_scale=mlora_scale,
        **({"pctx": pctx} if pctx is not None else {}))
    return (logits, new_cache["pool_k"], new_cache["pool_v"],
            new_cache.get("pool_k_scale"), new_cache.get("pool_v_scale"),
            lengths + active.astype(jnp.int32))


def verify_core(params, tokens, pool_k, pool_v, table, lengths, active,
                *, cfg: TransformerConfig, attn_impl: str = "auto",
                pool_k_scale=None, pool_v_scale=None, layers_hook=None,
                mlora_idx=None, mlora_scale: float = 1.0,
                forward_fn=None):
    """Multi-token paged forward (the speculative-verify primitive):
    tokens [B, Sq] are scattered at positions lengths..lengths+Sq-1 of
    each active slot and scored in ONE weight stream. Returns
    (logits [B, Sq, V], pool_k, pool_v, pool_k_scale, pool_v_scale) —
    lengths are NOT advanced (the caller decides acceptance first;
    rejected positions leave stale KV that the length mask keeps
    unattended until the next round overwrites it — the paged version
    of speculative.py's free-rollback discipline)."""
    paged_cache = {"pool_k": pool_k, "pool_v": pool_v,
                   "table": table, "active": active}
    if pool_k_scale is not None:
        paged_cache["pool_k_scale"] = pool_k_scale
        paged_cache["pool_v_scale"] = pool_v_scale
    fwd = forward if forward_fn is None else forward_fn
    logits, new_cache = fwd(
        params, tokens, cfg, cache=paged_cache, pos_offset=lengths,
        attn_impl=attn_impl, layers_hook=layers_hook,
        mlora_idx=mlora_idx, mlora_scale=mlora_scale)
    return (logits, new_cache["pool_k"], new_cache["pool_v"],
            new_cache.get("pool_k_scale"), new_cache.get("pool_v_scale"))


def growth_width(extra: int, block_size: int) -> int:
    """How many blocks a slot can newly need to write positions
    length .. length+extra: the columns of ``_grow_active``'s array (1
    for a plain tick)."""
    return -(-extra // block_size) + 1


def apply_growth(table, lengths, grow, block_size: int):
    """The block table with a tick's new blocks written in, inside the
    tick's own program: ``grow`` [n_slots, w] int32 holds, a slot, the
    ids of blocks ``lengths[slot] // block_size`` + 0..w-1 that the
    host just allocated (-1 = none: the block is there already, or the
    slot is not growing). The host half is
    ``PagedSlotServer._grow_active``, which wrote the same ids into the
    host mirror; ``None`` leaves a table someone else grew as it is."""
    if grow is None:
        return table
    n, mb = table.shape
    col = (lengths // block_size)[:, None] + jnp.arange(grow.shape[1])
    # -1 entries aim past the row and are dropped
    return table.at[jnp.arange(n)[:, None],
                    jnp.where(grow >= 0, col, mb)].set(grow, mode="drop")


def tick_decode(params, tokens, pool_k, pool_v, table, lengths, active,
                grow=None, *, pool_k_scale=None, pool_v_scale=None,
                mlora_idx=None, **static):
    """The plain tick as ONE program: this tick's block growth
    (``apply_growth``), then ``decode_core`` (whose keywords ``static``
    carries) over the grown table. Returns decode_core's six and the
    table."""
    table = apply_growth(table, lengths, grow, pool_k.shape[2])
    return (*decode_core(params, tokens, pool_k, pool_v, table, lengths,
                         active, pool_k_scale=pool_k_scale,
                         pool_v_scale=pool_v_scale, mlora_idx=mlora_idx,
                         **static), table)


def tick_fused(params, last_token, pool_k, pool_v, table, lengths, active,
               grow, chunk, slot, done, n_valid, *, pool_k_scale=None,
               pool_v_scale=None, mlora_idx=None, **static):
    """The fused tick as ONE program. Everything the forward needs is
    built here, from host values, not ahead of the launch: the grown
    table, the [B, width] token batch (``serving.fused_token_batch``: ``chunk`` [width] is
    prompt[done:done+n_valid] of the admitting ``slot``, zero-padded on
    the host), the positions (the cache lengths, ``done`` for the
    admitting slot), the write mask (the decode rows and the admitting
    slot, whose table row is reserved; every other row writes to the
    trash block) and the decode rows' length advance; ``static``
    carries verify_core's keywords. Returns (decode logits [B, V], the
    logits after the chunk's last real token [1, V], verify_core's four
    pools, lengths, table)."""
    from tpushare.models.serving import fused_token_batch
    width = chunk.shape[0]
    table = apply_growth(table, lengths, grow, pool_k.shape[2])
    toks = fused_token_batch(last_token, chunk, 0, width, width, slot)
    logits, pk, pv, pks, pvs = verify_core(
        params, toks, pool_k, pool_v, table, lengths.at[slot].set(done),
        active.at[slot].set(True), pool_k_scale=pool_k_scale,
        pool_v_scale=pool_v_scale, mlora_idx=mlora_idx, **static)
    first = jax.lax.dynamic_slice(
        logits, (slot, n_valid - 1, 0), (1, 1, logits.shape[2]))[:, 0]
    return (logits[:, 0], first, pk, pv, pks, pvs,
            lengths + active.astype(jnp.int32), table)


# The speculation cores moved to models/spec.py — the ONE seam every
# family (dense loops, paged slots, MoE slots) shares. draft_sample/
# spec_accept stay re-exported here because they were this module's
# public API (benches and older callers import them from paged); the
# implementation has one home now.
from tpushare.models.spec import SpecDecodeMixin  # noqa: E402
from tpushare.models.spec import draft_sample_core  # noqa: E402,F401
from tpushare.models.spec import spec_accept_core  # noqa: E402,F401


def paged_decode_step(params: Dict[str, Any], tokens: jnp.ndarray,
                      cfg: TransformerConfig, cache: PagedCache,
                      *, active: Optional[jnp.ndarray] = None,
                      attn_impl: str = "auto"
                      ) -> Tuple[jnp.ndarray, PagedCache]:
    """One ragged decode step over the paged pool. tokens [n_slots, 1].

    Equivalent to transformer.forward's ragged branch on the gathered
    dense view; the scatter writes go to the pool so storage stays
    paged. ``active`` [n_slots] bool masks which slots advance —
    inactive slots keep their length and write only to the trash block
    (PagedSlotServer drives this per step; default: all active).
    """
    # Keep the host lengths mirror in lockstep with the device +1
    # advance BEFORE dispatch, so grow_if_needed (which reads only the
    # mirror) sees the post-step truth. This module-level wrapper may
    # sync a device ``active`` (np.array below); the servers never go
    # through it — they drive decode_core directly and maintain their
    # mirrors from the host active bitmap.
    if active is None:
        act_np = np.ones((cache.n_slots,), bool)
        active = jnp.ones((cache.n_slots,), bool)
    else:
        act_np = np.array(active)
    logits, pool_k, pool_v, pks, pvs, lengths = decode_core(
        params, tokens, cache.pool_k, cache.pool_v,
        cache.block_table, cache.lengths, jnp.asarray(active),
        cfg=cfg, block_size=cache.block_size, attn_impl=attn_impl,
        pool_k_scale=cache.pool_k_scale,
        pool_v_scale=cache.pool_v_scale)
    cache.host_lengths()[act_np] += 1
    return logits, dataclasses.replace(
        cache, pool_k=pool_k, pool_v=pool_v, lengths=lengths,
        pool_k_scale=pks, pool_v_scale=pvs)


def prefill_into(params, prompt: jnp.ndarray, cfg: TransformerConfig,
                 cache: PagedCache, slot: int,
                 prefill_fn=None) -> Tuple[jnp.ndarray, PagedCache]:
    """Prefill one prompt [S] and scatter its KV into the slot's blocks.
    Returns (last-position logits [V], cache).

    ``prefill_fn(params, tokens, cache, pos_offset)`` lets callers pass
    a jitted forward (PagedSlotServer does); the prompt is zero-padded
    to a power-of-two block count so each bucket compiles once.
    Positions >= S hold junk KV inside the last blocks, but decode
    masks by length (and position S is overwritten by the first decode
    scatter), so they are never attended — same trash discipline as
    the dense ragged path.

    This is exactly the ``cached_len == 0`` case of
    ``prefill_suffix_into`` (same bucketing, padding, scatter, and
    compile keys) — one implementation, two entry points.
    """
    return prefill_suffix_into(params, prompt, cfg, cache, slot, 0,
                               prefill_fn=prefill_fn)


def prefill_suffix_into(params, prompt: jnp.ndarray,
                        cfg: TransformerConfig, cache: PagedCache,
                        slot: int, cached_len: int,
                        prefill_fn=None) -> Tuple[jnp.ndarray, PagedCache]:
    """Prefix-cached prefill: compute KV only for positions >=
    ``cached_len`` (the suffix), attending over the shared prefix
    blocks gathered from the pool, and scatter only the slot's fresh
    blocks. Returns (last-position logits [V], cache).

    The FLOPs saved are the whole point: a hit skips the prefix's
    attention+MLP entirely; the prefix KV moves as bytes (one gather),
    not as recompute. The suffix is padded to a power-of-two block
    count, so compiles key on (cached_len, padded-suffix) pairs —
    bounded by hit granularity, and a given serving mix (fixed system
    prompts) sees O(#distinct prefixes) compiles, same as bucketing.

    This is the one-shot composition of ``_admission_row`` (row build +
    one prefix gather) and ``_prefill_chunk`` (forward + scatter) —
    chunked admission holds the row across chunks instead, so the
    gather happens once per admission, not once per chunk.
    """
    S = int(prompt.shape[0])
    row, comp_len, n_blk = _admission_row(cfg, cache, slot, S, cached_len)
    last, cache, _ = _prefill_chunk(
        params, prompt, cfg, cache, slot, row, cached_len, S,
        n_blk, comp_len, chunk=0, prefill_fn=prefill_fn)
    return last, cache


def _row_pairs(cache: PagedCache):
    """(pool field, row-cache key) for every leaf the gather/scatter
    moves; scale leaves (no trailing Dh axis) reshape generically."""
    pairs = [("pool_k", "k"), ("pool_v", "v")]
    if cache.pool_k_scale is not None:
        pairs += [("pool_k_scale", "k_scale"), ("pool_v_scale", "v_scale")]
    if cache.pool_x is not None:
        pairs.append(("pool_x", "x"))
    return pairs


def block_shapes(cache: PagedCache) -> Dict[str, Tuple[int, ...]]:
    """Shape of ONE block's payload per pool leaf — what the host tier
    holds and the /kv/blocks wire carries: KV leaves [L, bs, Hkv, Dh]
    (the pool stores a page's heads merged; a payload keeps them
    apart, as it always was), scale leaves [L, Hkv_pad, bs]."""
    out = {}
    for pf, _ in _row_pairs(cache):
        L, _, *row = getattr(cache, pf).shape
        if not pf.endswith("_scale"):
            row = [row[0], cache.kv_heads, row[1] // cache.kv_heads]
        out[pf] = (L, *row)
    return out


def block_nbytes(cache: PagedCache) -> int:
    """Bytes of one block's payload over every pool leaf."""
    return sum(int(np.prod(shape)) * getattr(cache, pf).dtype.itemsize
               for pf, shape in block_shapes(cache).items())


def read_block(cache: PagedCache, blk: int) -> Dict[str, jnp.ndarray]:
    """One block's payload off the pools, still on the device (the
    caller's device_get is the transfer)."""
    return {pf: getattr(cache, pf)[:, blk].reshape(shape)
            for pf, shape in block_shapes(cache).items()}


def _admission_row(cfg: TransformerConfig, cache: PagedCache, slot: int,
                   S: int, cached_len: int):
    """The dense row cache one admission computes into, with the
    [0, cached_len) prefix gathered from the pool ONCE. Returns
    (row, comp_len, n_blk).

    Chunked admissions hold this row in their admission state, so
    every chunk's attention reads the prefix KV that is already
    sitting in the row — the per-chunk pool re-gather (the old
    ~S^2/(2*chunk) extra HBM traffic) does not exist. The row is
    bit-identical to a re-gather by construction: the pool holds
    exactly the rows this admission scattered from it. Cost: one
    [L, comp_len] KV row resident per in-flight admission (the same
    size the one-shot path allocates transiently).
    """
    bs = cache.block_size
    n_blk = blocks_needed(S + 1, bs)
    cached_blk = cached_len // bs
    fresh_blk = n_blk - cached_blk
    comp_fresh = max(1, 1 << (fresh_blk - 1).bit_length())   # pow2 bucket
    comp_fresh = max(min(comp_fresh, cache.max_blocks - cached_blk),
                     fresh_blk)
    comp_len = cached_len + comp_fresh * bs
    kvq = cache.pool_k_scale is not None
    if kvq:
        from tpushare.models.quant import init_cache_q8
        row = init_cache_q8(cfg, 1, comp_len)
    elif hasattr(cfg, "init_row_cache"):
        row = cfg.init_row_cache(1, comp_len)   # the family's own rows
    else:
        from tpushare.models.transformer import init_cache
        row = init_cache(cfg, 1, comp_len)
    # Device-side table slices: no host sync on the admit path (the
    # non-prefix case never needs host values; the gather below is a
    # device gather either way).
    Hkv = cfg.n_kv_heads
    if cached_blk:
        from tpushare.models.quant import pool_scales_to_rows
        blk_ids = cache.block_table[slot][:cached_blk]
        for pf, rk_ in _row_pairs(cache):
            pool = getattr(cache, pf)
            g = pool[:, blk_ids]             # [L, cached_blk, bs, ...]
            if pf.endswith("_scale"):
                # Pool stores scales in the kernel page layout
                # [L, nb, Hkv_pad, bs]; the row cache wants
                # [L, cached_len, Hkv].
                g = pool_scales_to_rows(g, Hkv)
            # KV pages hold their heads merged; the row keeps them
            # apart ([L, cached_len, Hkv, Dh]).
            row[rk_] = row[rk_].at[:, 0, :cached_len].set(
                g.reshape(g.shape[0], cached_len, *row[rk_].shape[3:]))
    return row, comp_len, n_blk


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_blocks_donated(pool, ids, rows):
    """``pool.at[:, ids].set(rows)`` on the pool's own buffer: the eager
    form returns a copy, and a pool is gigabytes."""
    return pool.at[:, ids].set(rows)


def _prefill_chunk(params, prompt: jnp.ndarray, cfg: TransformerConfig,
                   cache: PagedCache, slot: int, row, done: int, end: int,
                   n_blk: int, comp_len: int, chunk: int,
                   prefill_fn=None, inplace: bool = False):
    """Forward prompt positions [done, end) against the admission row
    (which already holds [0, done) — no pool re-gather) and scatter
    this chunk's block rows to the pool. Returns
    (last-position logits [V] on the final chunk else None, cache, row).

    Padding: mid chunks run at the fixed ``chunk`` length (compile
    keys on (comp_len, pad_len) — ``done`` rides as a traced jit
    argument through the server's jitted prefill, so chunk index does
    NOT recompile); the final chunk pads to the
    row tail (comp_len - done), reproducing the one-shot path's
    padded-forward bytes — including the masked garbage KV the padded
    tail writes into the last block, which decode's length mask never
    attends and the first decode scatter at position S overwrites.

    ``inplace``: scatter into the pools' own buffers (donated; between
    the first leaf's scatter and the returned cache, ``cache`` names
    deleted buffers, so the caller rebinds at once and recovers the pools
    if this raises). Off, every leaf's scatter copies its pool, and
    old and new pools are alive together until the caller rebinds.
    """
    S = int(prompt.shape[0])
    bs = cache.block_size
    final = end >= S
    pad_len = (comp_len - done) if final else chunk
    with span("slot.admit.prefill"):
        padded = jnp.zeros((pad_len,), prompt.dtype
                           ).at[:end - done].set(prompt[done:end])
        if prefill_fn is None:
            logits, row = forward(params, padded[None, :], cfg,
                                  cache=row, pos_offset=done)
        else:
            logits, row = prefill_fn(params, padded[None, :], cache=row,
                                     pos_offset=done)
    with span("slot.admit.scatter"):
        start_blk = done // bs
        end_blk = n_blk if final else end // bs
        ids = cache.block_table[slot][start_blk:end_blk]
        n_fresh = end_blk - start_blk
        updates = {}
        for pf, rk_ in _row_pairs(cache):
            r = row[rk_][:, 0, start_blk * bs:end_blk * bs]
            # a page: [bs, Hkv*Dh]; a leaf has its own count of layers
            # (spelt out, not -1: a family may have a pool with no layer)
            r = r.reshape(r.shape[0], n_fresh, bs, math.prod(r.shape[2:]))
            if pf.endswith("_scale"):
                from tpushare.models.quant import scales_to_pool_layout
                r = scales_to_pool_layout(r)    # -> [L, fb, Hkv_pad, bs]
            updates[pf] = (_scatter_blocks_donated(getattr(cache, pf), ids, r)
                           if inplace else getattr(cache, pf).at[:, ids].set(r))
        last = logits[0, S - 1 - done] if final else None
    return last, dataclasses.replace(cache, **updates), row


def _program(name: str, fn, **kw):
    """``functools.partial(fn, **kw)`` under a stable ``__name__``, so
    the jitted program is ``jit_<name>`` in a trace's ``XLA Modules``
    (a bare partial is ``jit__unknown``, and decode, prefill and fused
    tick cannot be told apart). The names are read by
    tpubench/readers/program_trace.py."""
    part = functools.partial(fn, **kw)
    part.__name__ = name
    return part


class PagedSlotServer(SpecDecodeMixin):
    """Continuous batching over the paged pool — the integration the
    block cache exists for: admit/step/evict over a fixed slot array,
    with KV storage that scales with live tokens instead of
    slots×max_len, so a tenant fits more concurrent sequences into its HBM share.

    Host/device split: the host owns the free list, the active bitmap,
    and exact mirrors of the block table and per-slot lengths
    (PagedCache.table_np/lengths_np — every mutation is host-decided
    or host-deducible, see the field comment); one jitted static-shape
    decode step advances every active slot, and each tick costs
    exactly ONE device→host transfer — the sampled tokens (plus the
    accepted counts on a speculative round). Growth, retirement, and
    the spec-round guard all read the mirrors.

    Speculation rides the shared seam (models/spec.py,
    SpecDecodeMixin): this class contributes only the paged hook
    surface — donated-pool draft/verify dispatches over the block
    table — while the round driver, acceptance cores, horizon
    semantics, and NaN discipline have their one home in the mixin.
    """

    def __init__(self, params, cfg: TransformerConfig, *, n_slots: int,
                 n_blocks: int, block_size: int = 16,
                 max_blocks_per_slot: Optional[int] = None,
                 attn_impl: str = "auto", layers_hook=None,
                 prefix_cache: bool = False,
                 kv_quant: bool = False,
                 temperature: float = 0.0, top_k=None, top_p=None,
                 seed: int = 0,
                 multi_lora=None, mlora_scale: float = 1.0,
                 speculative_draft=None, gamma: int = 4,
                 spec_horizon: int = 1,
                 draft_layers_hook=None,
                 forward_fn=None, draft_forward_fn=None,
                 mesh=None, param_specs=None, draft_param_specs=None,
                 kv_quota=None):
        from tpushare.models.serving import (MultiLoraSlots,
                                             TokenSampler,
                                             make_placement,
                                             mesh_attn_impl)
        attn_impl = mesh_attn_impl(mesh, attn_impl)
        # forward_fn: a transformer.forward-shaped callable with a
        # paged-cache branch — the family seam. moe.paged_forward here
        # serves the MoE LM over the SAME block pool, prefix cache,
        # chunked admission, and speculative machinery (the cache is
        # pure KV for both families; routing holds no slot state).
        # kv_quant/multi_lora stay dense-LM-only: their pool-scale and
        # adapter branches live in transformer.forward.
        if forward_fn is not None and (kv_quant or multi_lora is not None):
            raise ValueError(
                "forward_fn overrides (paged MoE) do not support "
                "kv_quant or multi_lora — those branches live in the "
                "dense LM's forward")
        self._forward_fn = forward_fn
        base_fwd = forward if forward_fn is None else forward_fn
        # multi_lora: an adapter bank (lora.stack_adapters) — each slot
        # picks its adapter at admit(prompt, adapter=i); rows apply
        # their own activation-path delta in one batched decode.
        # Composes with prefix_cache: chain keys are SALTED with the
        # adapter id, because wk/wv adapters change the KV a prompt
        # produces — identical tokens under different adapters must
        # never share blocks.
        if multi_lora is not None:
            from tpushare.models.lora import multi_lora_params
            params = multi_lora_params(params, multi_lora)
        self._ml = MultiLoraSlots(multi_lora, n_slots)
        # mesh: span a jax.sharding Mesh — weights per ``param_specs``
        # (default: the family's full-precision tree resolved off the
        # cfg shape, so paged MoE infers moe.param_specs; int8 trees
        # need the quant specs passed explicitly), both KV pools split
        # on the kv-head axis over tp, block table / lengths / free
        # list untouched (block ids stay host-global — the pool's
        # block axis is never sharded, so admission/evict/prefix logic
        # is placement-blind). The jitted decode/verify compile SPMD
        # from placement alone; every tick method runs unchanged.
        self.mesh = mesh
        if mesh is not None and (kv_quant or multi_lora is not None):
            raise ValueError(
                "mesh sharding does not compose with kv_quant/"
                "multi_lora yet (the int8 scale pools' padded-head "
                "layout and the adapter bank have no sharded "
                "placement contract — documented seams)")
        self._placement = make_placement(mesh, cfg, param_specs)
        if self._placement is not None:
            params = self._placement.place_params(params)
        self.params = params
        self.cfg = cfg
        self._sampler = TokenSampler(temperature, top_k, top_p, seed)
        # kv_quant: int8 pools + scales — ~2x tokens per HBM grant;
        # composes with prefix_cache (shared blocks carry scales). The
        # mode lives entirely in the cache (pool dtype + scale pools);
        # every method branches off cache.pool_k_scale.
        self.cache = init_paged_cache(
            cfg, n_slots=n_slots, n_blocks=n_blocks, block_size=block_size,
            max_blocks_per_slot=max_blocks_per_slot, kv_quant=kv_quant)
        if self._placement is not None:
            self.cache = dataclasses.replace(
                self.cache,
                pool_k=self._placement.place_pool(self.cache.pool_k),
                pool_v=self._placement.place_pool(self.cache.pool_v))
        # Device->host transfers made by the tick paths (step/
        # _spec_step/_fused_tick/admit_step completions) — the /stats
        # observability counter for the one-fetch-per-host invariant.
        self.device_fetches = 0
        # prefix_cache: share published full prompt blocks across slots
        # (admit_prefix / publish_prefix / release protocol); admits
        # then prefill only the uncached suffix.
        self.prefix_cache = prefix_cache
        self.last_cached_len = 0            # tokens reused by last admit
        self.prefix_hit_tokens = 0          # cumulative reused tokens
        self.prefix_prompt_tokens = 0       # cumulative admitted tokens
        self.active = np.zeros(n_slots, dtype=bool)       # host truth
        self._active_dev = jnp.zeros((n_slots,), bool)    # device mirror
        # The mirror is uploaded by COPY (jnp.array, never jnp.asarray):
        # on the CPU backend asarray may alias the numpy buffer, and
        # ``active`` is mutated in place while a dispatch that reads
        # the device mask can still be in flight.
        self._admissions: Dict[int, Dict[str, Any]] = {}  # chunked admits
        # Per-tenant KV-block quotas (tpushare.slo.quota.KvQuota; None
        # = unquota'd pool). The server is the ledger's single writer:
        # FRESH allocations charge the admitting slot's tenant (shared
        # prefix hits charge nothing — sharing is the product), growth
        # charges the grown slot's tenant, evict refunds the slot's
        # whole charge. _slot_charge holds the per-slot balance so the
        # refund is exact whatever mix of admission/growth paid in.
        self.kv_quota: Optional["KvQuota"] = kv_quota
        self._slot_tenant: Dict[int, str] = {}
        self._slot_charge: Dict[int, int] = {}
        self.last_token = jnp.zeros((n_slots, 1), jnp.int32)
        # layers_hook: per-layer transform seam (quant.dequant_hook
        # for int8 params).
        # donate_argnums=(2, 3) and the int8 pools' scale leaves by
        # name: the KV pools are DONATED into every jitted tick
        # dispatch — each tick writes at most B block rows into pools
        # that can be many GiB (sharded: the dominant per-device
        # resident). The forward carries the stacks through its layer
        # loop and writes them in place, so the returned pools ARE the
        # donated buffers: a step holds one pool generation and copies
        # none (tests/test_paged_inplace.py holds it to that). The old
        # arrays are dead the moment the call returns (the tick
        # methods rebind self.cache/self._dpk to the returned pools
        # and nothing else holds a pool reference — DN601/DN602 police
        # exactly this surface); a PagedCache snapshot from before a
        # tick was already invalidated by the host-mirror contract.
        #
        # A tick launches ONE program: what it changes on the device
        # ahead of its forward (the new block ids, the fused tick's
        # token batch, positions and write mask) is an argument built
        # on the host in numpy at a fixed shape and applied inside the
        # program (tick_decode, tick_fused), which hands back the
        # grown table beside the pools. The table is not donated: it
        # is small, and snapshots of it (the draft's view, the prefix
        # cache's) stay valid.
        self._decode = jax.jit(_program(
            "paged_decode",
            tick_decode, cfg=cfg, block_size=block_size,
            attn_impl=attn_impl, layers_hook=layers_hook,
            mlora_scale=mlora_scale, forward_fn=forward_fn),
            donate_argnums=(2, 3),
            donate_argnames=("pool_k_scale", "pool_v_scale"))
        self._prefill = jax.jit(_program(
            "paged_prefill",
            base_fwd, cfg=cfg, attn_impl=attn_impl,
            layers_hook=layers_hook, mlora_scale=mlora_scale))
        # The fused engine tick: decode rows contribute 1 token each,
        # the admitting slot its next chunk — one weight stream.
        self._fused = jax.jit(_program(
            "paged_fused",
            tick_fused, cfg=cfg, attn_impl=attn_impl,
            layers_hook=layers_hook, mlora_scale=mlora_scale,
            forward_fn=forward_fn),
            donate_argnums=(2, 3),
            donate_argnames=("pool_k_scale", "pool_v_scale"))
        # The multi-token paged forward a speculative round verifies
        # with (the fused tick's forward under the fused tick's name).
        self._verify = jax.jit(_program(
            "paged_fused",
            verify_core, cfg=cfg, attn_impl=attn_impl,
            layers_hook=layers_hook, mlora_scale=mlora_scale,
            forward_fn=forward_fn),
            donate_argnums=(2, 3),
            donate_argnames=("pool_k_scale", "pool_v_scale"))
        # A speculative round's draft and verify programs run several
        # times over one table, so its growth is a program of its own
        # at the round's start (_spec_begin).
        self._grow = jax.jit(_program(
            "paged_grow", apply_growth, block_size=block_size))
        # Ticks whose program carried at least one new block, and the
        # blocks: /stats growth_ticks / blocks_grown.
        self.growth_ticks = 0
        self.blocks_grown = 0
        # What rides a tick that grows nothing, a width of the growth
        # array: the same all -1 argument already on the device, so
        # such a tick uploads nothing (an upload inside the call is
        # some 0.4 ms ahead of the launch on a v5e: PERF.md, PR 31).
        widths = {1} | ({growth_width(gamma * spec_horizon, block_size)}
                        if speculative_draft is not None else set())
        self._no_growth = {w: jnp.full((n_slots, w), -1, jnp.int32)
                           for w in widths}
        # Speculative decoding over the paged pools: a draft LM drafts
        # gamma tokens per slot, the target verifies the whole block in
        # ONE weight stream — and unlike the dense speculative loop
        # (models/speculative.py, lockstep min over the batch), paged
        # decode is ALREADY ragged, so acceptance is per-slot: fast
        # rows keep their full speedup while slow rows take 1 token.
        # The draft keeps its own KV pools indexed by the SAME block
        # table (shared prefix blocks carry draft KV written by their
        # publisher — identical values for identical tokens).
        # ``speculative`` is what the engine reads (a step returns a
        # list a slot, /stats has a ``speculative`` group); ``_draft_lm``
        # says the draft is a second model with pools of its own, which
        # the admission and tick paths below feed. A family whose draft
        # is a module of the target itself (latent.LatentSlotServer)
        # sets the first and not the second.
        self.speculative = self._draft_lm = speculative_draft is not None
        self.gamma = gamma
        self.spec_horizon = spec_horizon
        if self._draft_lm:
            # The shared seam owns the round driver, acceptance cores,
            # horizon semantics, and the gamma/horizon validation.
            self._spec_init(gamma=gamma, spec_horizon=spec_horizon,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p, cap=self.slot_capacity)
            draft_params, draft_cfg = speculative_draft
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError("draft and target must share a vocab")
            if self._ml.enabled:
                # The draft gets the SAME adapter bank: each slot's
                # proposals then come from its own fine-tune, keeping
                # acceptance high (for int8-self the draft is the
                # target's rounding WITH adapters). Correctness never
                # depends on this — verify is the adapted target — but
                # the bank's A/B shapes only apply to a draft sharing
                # the target's layer geometry.
                geom = ("d_model", "n_layers", "n_heads", "n_kv_heads",
                        "head_dim", "d_ff")   # d_ff: banks may adapt MLP
                if any(getattr(draft_cfg, a) != getattr(cfg, a)
                       for a in geom):
                    raise NotImplementedError(
                        "speculative + multi_lora needs a draft sharing "
                        "the target's layer geometry (int8-self or a "
                        "same-architecture draft) so the adapter bank "
                        "applies to both sides")
                from tpushare.models.lora import multi_lora_params
                draft_params = multi_lora_params(draft_params, multi_lora)
            self.draft_params = draft_params
            self.draft_cfg = draft_cfg
            dshape = (draft_cfg.n_layers, n_blocks, block_size,
                      draft_cfg.n_kv_heads * draft_cfg.head_dim)
            self._dpk = jnp.zeros(dshape, draft_cfg.dtype)
            self._dpv = jnp.zeros(dshape, draft_cfg.dtype)
            if self._placement is not None:
                # The draft places like the target: its own param spec
                # tree (int8-self drafts need the quant specs), its
                # pools on the same kv-head split — the shared block
                # table indexes both, so the draft's head count must
                # divide by tp too.
                dplace = make_placement(mesh, draft_cfg,
                                        draft_param_specs, role="draft")
                self.draft_params = dplace.place_params(draft_params)
                self._dpk = dplace.place_pool(self._dpk)
                self._dpv = dplace.place_pool(self._dpv)
            # draft_layers_hook: the quantized-self-speculation seam —
            # pass quant.dequant_hook(cfg) with an int8 quantize_params
            # tree of the TARGET as the draft: the draft is the
            # target's own rounding (acceptance near 100%) at half the
            # draft weight stream (speculative.py's dense loop has the
            # same hook).
            dfwd_fn = (forward_fn if draft_forward_fn is None
                       else draft_forward_fn)
            self._draft_decode = jax.jit(_program(
                "draft_paged_decode",
                decode_core, cfg=draft_cfg, block_size=block_size,
                attn_impl=attn_impl, layers_hook=draft_layers_hook,
                mlora_scale=mlora_scale, forward_fn=dfwd_fn),
                donate_argnums=(2, 3),
                donate_argnames=("pool_k_scale", "pool_v_scale"))
            self._draft_prefill = jax.jit(_program(
                "draft_paged_prefill",
                forward if dfwd_fn is None else dfwd_fn,
                cfg=draft_cfg, attn_impl=attn_impl,
                layers_hook=draft_layers_hook, mlora_scale=mlora_scale))
            # Draft-side fused tick dispatch: one multi-token draft
            # forward mirrors the decode tokens' draft KV AND writes
            # the admission chunk's draft KV (same batch as the
            # target's fused forward — logits discarded).
            self._draft_fused = jax.jit(_program(
                "draft_paged_fused",
                tick_fused, cfg=draft_cfg, attn_impl=attn_impl,
                layers_hook=draft_layers_hook, mlora_scale=mlora_scale,
                forward_fn=dfwd_fn),
                donate_argnums=(2, 3),
                donate_argnames=("pool_k_scale", "pool_v_scale"))
            # temperature > 0: proposals are SAMPLED from the draft's
            # filtered law and verified with the stochastic rejection
            # rule (spec.spec_accept_core) — every emitted token's
            # marginal is exactly the non-speculative sampler's law,
            # per slot, composing with top-k/top-p (both sides share
            # the sampler's filter_logits). temperature == 0 keeps the
            # bit-exact greedy match rule. Both core sets were built
            # by _spec_init above.

    #: the serial admission of a family whose weights and pools leave no
    #: room beside them (latent.LatentSlotServer turns it on and guards
    #: the call in its ``admit_step``): the block scatter donates the
    #: pools (``_prefill_chunk``) where the eager one copies a pool a
    #: leaf, and the dense row waits for the first SERIAL chunk
    #: (``admit_step`` builds it where ``row_stale``) where admit_start
    #: builds one a pending admission, which a fused admission never
    #: reads. Off here: the admission the dense and Mixtral cells were
    #: measured with; making it every family's is a perf_opt PR's, with
    #: their cells measured (PERF.md section 7).
    lean_admission = False

    @property
    def slot_capacity(self) -> int:
        return self.cache.max_blocks * self.cache.block_size

    def _pools_dispatch(self, fn, *args, **kw):
        """Every donating jitted dispatch goes through here: a call
        that raises AFTER consuming its donated pools (a transient
        XlaRuntimeError on chip — device OOM, interconnect hiccup)
        would otherwise leave self.cache.pool_k/_dpk permanently
        deleted, turning the engine's quarantine-and-replay recovery
        (PR 4 contract) into an unrecoverable 'Array has been
        deleted' loop. On failure the pools are rebuilt before the
        exception propagates, so recovery proceeds normally. So does
        every dispatch that carries block growth (a speculative
        round's growth program donates nothing): the failure leaves
        the device table behind the host mirror, and the same recovery
        uploads it again."""
        try:
            return fn(*args, **kw)
        except Exception:
            self._recover_donated_pools()
            raise

    def _recover_donated_pools(self) -> None:
        """Rebuild any donation-consumed pool as fresh zeros (same
        shape/dtype/placement). Correctness: the engine's tick failure
        domain quarantines EVERY in-flight slot and replays its
        request from the prompt, so all live KV is recomputed — the
        pools only need to exist. The prefix cache must be fully
        unpublished though: its indexed blocks' KV died with the old
        pools, and a later admit hitting a zeroed block would be
        silent corruption (zero-ref LRU blocks return to the free
        list; referenced published blocks lose their chain so release
        frees them instead of parking garbage on the LRU).

        The block table too: a tick's growth reaches the device table
        inside the tick's program, so a dispatch that raised after
        ``_grow_active``'s host half left the device table behind the
        host mirror. The mirror is the truth; the table is uploaded
        from it (by copy: the mirror is mutated in place)."""
        c = self.cache
        repl = {}
        for pf, _ in _row_pairs(c):
            arr = getattr(c, pf)
            if arr.is_deleted():
                new = jnp.zeros(arr.shape, arr.dtype)
                if self._placement is not None:
                    new = self._placement.place_pool(new)
                repl[pf] = new
        if repl:
            for blk in list(c.lru):
                c.free.append(blk)
            c.lru.clear()
            c.index.clear()
            c.chains.clear()
        self.cache = dataclasses.replace(
            c, block_table=upload_mirror(c.host_table()), **repl)
        if self._draft_lm:
            for attr in ("_dpk", "_dpv"):
                arr = getattr(self, attr)
                if arr.is_deleted():
                    new = jnp.zeros(arr.shape, arr.dtype)
                    if self._placement is not None:
                        new = self._placement.place_pool(new)
                    setattr(self, attr, new)

    def admit(self, prompt: jnp.ndarray, adapter: int = -1,
              tenant: Optional[str] = None) -> int:
        """Reserve blocks for ``prompt`` [S], prefill them, return the
        slot. Raises RuntimeError when slots or pool blocks run out.
        ``adapter``: this slot's multi-LoRA bank index (-1 = base).
        ``tenant``: the KV-quota accounting principal (None =
        "default" — only meaningful with ``kv_quota`` configured)."""
        slot = self.admit_start(prompt, adapter=adapter, tenant=tenant)
        while self.admit_step(slot) is None:
            pass
        return slot

    def admit_start(self, prompt: jnp.ndarray, adapter: int = -1,
                    chunk_tokens: Optional[int] = None,
                    tenant: Optional[str] = None) -> int:
        """Reserve a slot + all its blocks for ``prompt`` without
        prefilling anything yet; drive the prefill with admit_step().

        Chunked admission (vLLM-style chunked prefill): a 32k-token
        admit run whole blocks every co-located decode stream for the
        entire prefill; splitting it into ``chunk_tokens`` pieces lets
        the engine interleave decode steps between chunks, bounding
        the latency spike. Each chunk prefills positions
        [done, done+chunk) while attending over the already-written
        blocks — exactly prefill_suffix_into's contract, so chunked
        and whole admission produce bit-identical KV. Chunks stay
        block-aligned (compile keys are bounded by capacity/chunk and
        cached per process).

        Cost model: the admission holds ONE dense row cache across its
        chunks (_admission_row), so each chunk's attention reads the
        prefix KV already sitting in the row — there is no per-chunk
        pool re-gather (the old path paid ~S^2/(2*chunk) extra KV-row
        HBM copies; VERDICT r4 #4). A paged-prefill kernel reading
        prefix pages from the pool was the alternative considered and
        rejected: this admission COMPUTED the prefix KV moments ago,
        so keeping it costs nothing and is bit-identical by
        construction, while a kernel would re-stream the pages from
        HBM every chunk. Chunk size now trades only per-chunk dispatch
        overhead against the decode-latency bound — block-aligned
        chunks of a few hundred tokens are fine on real models. Memory:
        one [L, comp_len] KV row per in-flight admission (the same
        size the one-shot path allocates transiently)."""
        if prompt.ndim != 1:
            raise ValueError("admit takes a single unbatched prompt")
        self._ml.validate(adapter)
        with span("slot.admit.lookup"):
            candidates = [s for s in range(self.cache.n_slots)
                          if not self.active[s] and s not in self._admissions]
            if not candidates:
                # Slot pressure is the same transient class as pool
                # pressure for the engine's hold-and-retry path.
                raise PoolExhausted("no free slots")
            slot = candidates[0]
            if self._ml.enabled:
                self._ml.set(slot, adapter)
            prefill_fn = self._ml.wrap_prefill(self._prefill, adapter)
            # A slot that retired at capacity (deactivated in step()) still
            # owns its blocks so they stay readable; reclaim them before
            # reuse or they would leak — admit() wipes the table row
            # without touching the free list. release() degenerates to
            # evict() when no prefix bookkeeping exists, and plain evict()
            # on a cache with published blocks would free them while still
            # indexed (silent KV corruption) — so the server always
            # releases.
            if (self.cache.host_table()[slot] >= 0).any():
                self._refund_slot(slot)
                self.cache = release(self.cache, slot)
            prompt_np = np.asarray(prompt)
            S = int(prompt_np.shape[0])
            bs = self.cache.block_size
            tenant = tenant or "default"
            if self.prefix_cache:
                # Hash once: S//bs keys cover both the admit match
                # ((S-1)//bs of them) and the publish (S//bs). Salted by
                # adapter id: KV under different adapters must not share.
                salt = (b"adapter:%d" % adapter) if self._ml.enabled else b""
                keys = _chain_keys(prompt_np, bs, S // bs, salt=salt)
                self.cache, cached_len, blocks = admit_prefix(
                    self.cache, slot, prompt_np, keys=keys)
                self.last_cached_len = cached_len
                self.prefix_hit_tokens += cached_len
                self.prefix_prompt_tokens += S
            else:
                self.cache = admit(self.cache, slot, S)
                cached_len, keys, blocks = 0, None, None
            if self.kv_quota is not None:
                # Enforce on the FRESH allocation only (prefix hits share
                # blocks already paid for by their first writer). The
                # verdict runs after the alloc because only the alloc
                # knows how much of the prompt the prefix cache covered —
                # and the reserve-floor check must see the POST-admission
                # pool: a prefix hit pins zero-ref LRU blocks that a
                # pre-allocation snapshot still counts as claimable, which
                # would let a large-hit admission dig into other tenants'
                # guaranteed floors undetected. admit_verdict subtracts
                # ``need``, so handing it post-state + fresh makes its
                # comparison exactly "claimable after this admission".
                # A refusal rolls the host-side reservation back intact.
                # Promoted host-tier landings count as cached_len for
                # prefill purposes but are FRESH device allocations the
                # tenant pays for — only genuinely shared device-resident
                # hits are free (their first writer already paid).
                promoted = (self.cache.host_tier.last_promoted_n
                            if (self.prefix_cache
                                and self.cache.host_tier is not None)
                            else 0)
                fresh = blocks_needed(S + 1, bs) - cached_len // bs \
                    + promoted
                verdict = self.kv_quota.admit_verdict(
                    tenant, fresh, reclaimable_blocks(self.cache) + fresh)
                if verdict is not None:
                    kind, msg = verdict
                    self.cache = release(self.cache, slot)
                    if self.prefix_cache:
                        self.prefix_hit_tokens -= cached_len
                        self.prefix_prompt_tokens -= S
                    raise QuotaExceeded(msg, kind=kind, tenant=tenant,
                                        need=fresh)
                self.kv_quota.charge(tenant, fresh)
                self._slot_charge[slot] = fresh
            self._slot_tenant[slot] = tenant
            if self.prefix_cache and self.cache.host_tier is not None:
                # Record this tenant as the quota principal of every
                # freshly-allocated block — a later demotion charges the
                # host-tier byte ledger against it.
                n_matched = (cached_len // bs
                             - self.cache.host_tier.last_promoted_n)
                for b in blocks[n_matched:]:
                    self.cache.owners[int(b)] = tenant
        chunk = chunk_tokens if chunk_tokens else S
        # Round UP to block alignment: rounding down would split even a
        # whole-prompt admit of a non-aligned prompt into two dispatches
        # (and a second compile key) for no reason.
        chunk = max(bs, -(-chunk // bs) * bs)
        with span("slot.admit.row"):
            if self.lean_admission:
                row, comp_len, n_blk = None, 0, blocks_needed(S + 1, bs)
            else:
                row, comp_len, n_blk = _admission_row(
                    self.cfg, self.cache, slot, S, cached_len)
        st = {
            "prompt": prompt, "prompt_np": prompt_np, "done": cached_len,
            "chunk": chunk, "keys": keys, "blocks": blocks,
            "prefill_fn": prefill_fn,
            "row": row, "comp_len": comp_len, "n_blk": n_blk,
            # Fused chunks write straight to the pool through the
            # block table; the serial admission row then lags the
            # pool and must be re-gathered before the next serial
            # chunk (admit_step checks this flag).
            "row_stale": self.lean_admission,
        }
        if self._draft_lm:
            # The draft's admission row shares the block table; its
            # prefix gather (draft KV written by the publisher) also
            # happens once per admission. Its prefill pins the slot's
            # adapter too (the draft carries the same bank).
            # Host-tier note (r18): promoted blocks restore TARGET KV
            # only — the tier never demotes draft pools, so the
            # draft's gathered prefix over a promoted region is
            # zeros. Greedy speculation's output is provably the
            # target's law regardless of draft-KV content (acceptance
            # compares against the clean target verify), so this
            # degrades acceptance over the promoted span, never
            # correctness — the same tradeoff the donated-pool
            # recovery path already accepts.
            with span("slot.admit.row"):
                st["drow"], st["dcomp_len"], _ = _admission_row(
                    self.draft_cfg, self._draft_view(), slot, S,
                    cached_len)
            st["draft_prefill_fn"] = self._ml.wrap_prefill(
                self._draft_prefill, adapter)
        self._admissions[slot] = st
        return slot

    def _draft_view(self) -> PagedCache:
        """The draft pools behind the slot's own block table (shared
        prefix blocks carry draft KV written by their publisher —
        identical values for identical tokens)."""
        return dataclasses.replace(
            self.cache, pool_k=self._dpk, pool_v=self._dpv,
            pool_k_scale=None, pool_v_scale=None)

    def admit_step(self, slot: int,
                   max_chunk_tokens: Optional[int] = None
                   ) -> Optional[int]:
        """Prefill the next chunk of a started admission, optionally
        capped at ``max_chunk_tokens`` rounded down to block alignment
        (floor: one block — the engine's tick budget bounds serial
        chunks too). Returns None while chunks remain; on the final
        chunk, samples and returns the first generated token and
        activates the slot. Each chunk forwards against the
        admission's persistent row (no prefix re-gather) and scatters
        only its own block rows."""
        st = self._admissions[slot]
        S = int(st["prompt_np"].shape[0])
        chunk = st["chunk"]
        if max_chunk_tokens is not None:
            bs = self.cache.block_size
            chunk = max(bs, min(chunk,
                                (max_chunk_tokens // bs) * bs))
        if st["row_stale"]:
            # Fused chunks advanced this admission pool-side; rebuild
            # the serial row from the pool (one gather — exactly what
            # _admission_row does for a prefix hit of length `done`,
            # which fused chunks effectively are).
            with span("slot.admit.row"):
                st["row"], st["comp_len"], _ = _admission_row(
                    self.cfg, self.cache, slot, S, st["done"])
                if self._draft_lm:
                    st["drow"], st["dcomp_len"], _ = _admission_row(
                        self.draft_cfg, self._draft_view(), slot, S,
                        st["done"])
            st["row_stale"] = False
        end = min(S, st["done"] + chunk)
        done0 = st["done"]
        # Crossover-estimator feed (r18): the final chunk's span ends
        # at the blocking token fetch below (honest wall clock);
        # mid-chunk spans are dispatch-only and bias the measured
        # prefill rate HIGH — i.e. the transfer-vs-recompute policy
        # toward recompute, the conservative direction.
        t0 = time.perf_counter()
        last_logits, self.cache, st["row"] = _prefill_chunk(
            self.params, st["prompt"], self.cfg, self.cache, slot,
            st["row"], st["done"], end, st["n_blk"], st["comp_len"],
            chunk, prefill_fn=st["prefill_fn"],
            inplace=self.lean_admission)
        if self._draft_lm:
            # The draft needs prompt KV too, chunked the same way.
            _, dview, st["drow"] = _prefill_chunk(
                self.draft_params, st["prompt"], self.draft_cfg,
                self._draft_view(), slot, st["drow"], st["done"], end,
                st["n_blk"], st["dcomp_len"], chunk,
                prefill_fn=st["draft_prefill_fn"])
            self._dpk, self._dpv = dview.pool_k, dview.pool_v
        st["done"] = end
        tier = self.cache.host_tier
        if end < S:
            if tier is not None:
                tier.estimator.observe_prefill(
                    end - done0, time.perf_counter() - t0)
            return None
        del self._admissions[slot]
        if self.prefix_cache:
            publish_prefix(self.cache, st["blocks"], st["prompt_np"],
                           keys=st["keys"])
        with span("slot.sample"):
            nxt = self._sampler.pick(
                last_logits[None, :])[0].astype(jnp.int32)
            self.last_token = self.last_token.at[slot, 0].set(nxt)
        self.active[slot] = True
        self._active_dev = upload_mirror(self.active)
        self.device_fetches += 1
        with span("slot.admit.first_token"):
            tok = int(host_scalar(nxt))
        if tier is not None:
            tier.estimator.observe_prefill(
                end - done0, time.perf_counter() - t0)
        return tok

    def prefetch_prefix(self, prompt_np: np.ndarray,
                        adapter: int = -1) -> int:
        """Stage the host-tier portion of ``prompt_np``'s chain on
        device AHEAD of its admission — the engine calls this from
        the overlap window (_plan_next_pick) so the upload rides the
        in-flight dispatch and the later admit's promotion finds the
        blocks already device-resident (a prefetch HIT pays zero
        upload on the admission path). Host→device only
        (``jnp.asarray``): ZERO device fetches, pinned by
        test_sync_free. Returns the number of chain blocks staged.

        Mirrors admit_prefix's match walk exactly: the device-matched
        prefix needs no upload, the consecutive tier run after it
        stages, the first full miss (or an index hit after the tier
        run started) ends the chain. Stale stages from abandoned
        picks are dropped here — they were saved uploads, never
        state."""
        tier = self.cache.host_tier
        if tier is None or not self.prefix_cache:
            return 0
        bs = self.cache.block_size
        S = int(prompt_np.shape[0])
        salt = (b"adapter:%d" % adapter) if self._ml.enabled else b""
        keys = _chain_keys(prompt_np, bs, (S - 1) // bs, salt=salt)
        staged: List[bytes] = []
        for key in keys[:(S - 1) // bs]:
            if key in self.cache.index:
                if staged:
                    break           # admit_prefix stops its tier run
                continue            # here too — stay in lockstep
            data = tier.get(key)
            if data is None:
                break
            if key not in tier.staged:
                tier.stage(key, {pf: jnp.asarray(a)
                                 for pf, a in data.items()})
            staged.append(key)
        tier.clear_staged(keep=staged)
        return len(staged)

    def _grow_active(self, extra: int = 0):
        """Allocate next blocks for active slots whose current length
        crosses a block boundary — the HOST half of block growth:
        mirror reads only (no device sync), free-list pops, ``refs``,
        quota charges and the host table, all before anything is in
        flight, so a shortfall raises with nothing to undo on the
        device. Returns what rides the tick's program as an argument
        (``apply_growth`` is the device half, inside that program):
        [n_slots, growth_width(extra)] int32, a slot's new block ids
        from block ``length // block_size`` on, -1 where there is none;
        a numpy array that the call uploads, or where no slot grows the
        all -1 array the device already holds.
        ``extra``: additionally cover positions through length+extra
        (a speculative round writes gamma+1 tokens ahead), clamped at
        slot capacity — the acceptance clamp keeps lengths in range,
        and writes past the last allocated block land in the trash
        block by construction."""
        bs = self.cache.block_size
        lengths = self.cache.host_lengths()
        table = self.cache.host_table()
        slots, bis = [], []
        for slot in np.nonzero(self.active)[0]:
            lo = int(lengths[slot]) // bs
            if lo >= self.cache.max_blocks:
                raise SlotCapacityExceeded(
                    int(slot), f"slot {slot} exceeded max_blocks")
            hi = min((int(lengths[slot]) + extra) // bs,
                     self.cache.max_blocks - 1)
            for bi in range(lo, hi + 1):
                if table[slot, bi] >= 0:
                    continue
                slots.append(slot)
                bis.append(bi)
        # Check-then-pop so a shortfall raises with the free list
        # intact (a mid-loop raise after popping would leak blocks).
        # alloc_blocks has the same discipline and additionally
        # reclaims zero-ref cached blocks under pool pressure.
        ids = alloc_blocks(self.cache, len(slots))
        for b in ids:
            self.cache.refs[b] = 1
        if self.kv_quota is not None:
            # Growth is charged but not refused: a mid-stream refusal
            # would poison a whole batched tick over one tenant's
            # boundary crossing. Over-ceiling growth instead marks the
            # tenant (kv_quota.over_ceiling) and the ENGINE aims its
            # next preemption at that tenant's lowest tier — policy
            # belongs above the scatter path.
            for slot in slots:
                t = self._slot_tenant.get(int(slot), "default")
                self.kv_quota.charge(t, 1)
                self._slot_charge[int(slot)] = (
                    self._slot_charge.get(int(slot), 0) + 1)
        width = growth_width(extra, bs)
        if not slots:
            return self._no_growth[width]
        grow = np.full((self.cache.n_slots, width), -1, np.int32)
        rows, at = np.asarray(slots), np.asarray(bis)
        table[rows, at] = ids
        grow[rows, at - lengths[rows] // bs] = ids
        self.growth_ticks += 1
        self.blocks_grown += len(ids)
        return grow

    def step(self, prefill_work: Optional[int] = None,
             max_chunk_tokens: Optional[int] = None) -> Dict[int, int]:
        """One greedy decode step for every active slot; returns
        {slot: new_token}. Slots at capacity deactivate (their blocks
        stay readable until evict). Speculative servers return
        {slot: [tokens...]} — up to gamma+1 per slot per step.

        ``prefill_work``: a slot with an in-flight chunked admission —
        its next chunk (capped at ``max_chunk_tokens``, rounded down
        to block alignment) rides the SAME multi-token paged forward
        as the decode rows. A tick carrying a fused chunk is always a
        plain tick (spec rounds skip it; the draft mirrors decode
        tokens and its chunk in one draft forward). On the completing
        chunk the returned dict also carries the admitted slot's
        first sampled token."""
        return self.step_async(prefill_work, max_chunk_tokens).finalize()

    def step_async(self, prefill_work: Optional[int] = None,
                   max_chunk_tokens: Optional[int] = None):
        """step() with the token fetch deferred (serving.PendingStep
        contract): block growth, quota charges, forwards, pool/length
        rebinds, and capacity retirement all happen here — at
        dispatch — so pool-pressure errors (PoolExhausted,
        SlotCapacityExceeded) raise host-side before anything is in
        flight. finalize() performs the ONE device->host fetch and
        builds the out dict.

        Between this method's entry and the launch the host runs no
        device operation: the tick's new block ids ride the step's own
        program as a numpy argument (``_grow_active`` /
        ``apply_growth``), which hands back the grown table with the
        pools and the advanced lengths. The engine enters a tick with
        the previous one still owed (it fetches tick N only after it
        has launched N+1), so what runs here rides the device window of
        the program in flight, and a tick chains to the next on the
        device alone: ``last_token``, ``lengths``, the table and the
        pools are device arrays rebound here, growth and retirement
        read host mirrors that advance here, and each PendingStep
        closes over its own ``nxt`` and ``slots``. A row computed for a
        stream the owed tick turns out to have ended is the engine's to
        drop; it lands in the slot's private blocks, which ``evict``
        releases, ahead of anything a later admission launches."""
        from tpushare.models.serving import PendingStep
        if prefill_work is not None:
            if prefill_work not in self._admissions:
                raise ValueError(f"slot {prefill_work} has no "
                                 f"in-flight admission")
            return self._fused_tick_async(prefill_work, max_chunk_tokens)
        if self.speculative:
            return self._spec_step_async()
        if not self.active.any():
            return PendingStep.done({})
        with span("slot.grow"):
            grow = self._grow_active()
        with span("slot.launch"):
            mkw = ({"mlora_idx": self._ml.dev} if self._ml.enabled
                   else {})
            logits, pool_k, pool_v, pks, pvs, lengths, table = \
                self._pools_dispatch(
                    self._decode,
                    self.params, self.last_token, self.cache.pool_k,
                    self.cache.pool_v, self.cache.block_table,
                    self.cache.lengths, self._active_dev, grow,
                    pool_k_scale=self.cache.pool_k_scale,
                    pool_v_scale=self.cache.pool_v_scale, **mkw)
            # Rebind the donated pools IMMEDIATELY: between the
            # dispatch and this replace, self.cache.pool_k/pool_v name
            # deleted buffers (donate_argnums), and any raise in that
            # window would leave the server holding them. The grown
            # table comes back with them.
            self.cache = dataclasses.replace(
                self.cache, pool_k=pool_k, pool_v=pool_v,
                block_table=table, lengths=lengths,
                pool_k_scale=pks, pool_v_scale=pvs)
        with span("slot.sample"):
            nxt = self._sampler.pick(logits[:, 0]).astype(jnp.int32)
            self.last_token = jnp.where(self._active_dev[:, None],
                                        nxt[:, None], self.last_token)
        with span("slot.mirror"):
            # Host mirror advances by the same +1-per-active-slot the
            # device lengths just did — the tick's ONE transfer is the
            # token fetch itself.
            lnp = self.cache.host_lengths()
            lnp[self.active] += 1
            slots = [int(s) for s in np.nonzero(self.active)[0]]
            # Capacity retirement reads only the host mirror — decided
            # at dispatch, exactly the serial tick's criterion.
            hit_cap = False
            for slot in slots:
                if int(lnp[slot]) >= self.slot_capacity:
                    self.active[slot] = False
                    hit_cap = True
            if hit_cap:
                self._active_dev = upload_mirror(self.active)

        def _finalize(invalid):
            self.device_fetches += 1
            nxt_np = addressable_fetch(nxt)
            return {s: int(nxt_np[s]) for s in slots
                    if s not in invalid}

        return PendingStep(_finalize, slots=slots)

    def _fused_tick(self, slot: int,
                    max_chunk_tokens: Optional[int]) -> Dict[int, int]:
        """One fused engine tick over the pool: every active decode
        slot contributes 1 token and admission ``slot`` contributes
        its next (block-aligned) chunk — ONE multi-token paged forward
        per weight stream. The chunk attends its already-written
        prefix straight off the pool through the block table (the
        pool holds exactly what the serial chunks/prefix hits wrote,
        so fused and serial admission are bit-identical under greedy)
        and its KV scatters into the slot's reserved blocks exactly
        as admit_step writes it. Sync discipline unchanged: one
        device->host transfer (the token fetch; a completing
        admission's first token rides it)."""
        return self._fused_tick_async(slot, max_chunk_tokens).finalize()

    def _fused_tick_async(self, slot: int,
                          max_chunk_tokens: Optional[int]):
        from tpushare.models.serving import PendingStep, fused_chunk_span
        st = self._admissions[slot]
        if not self.active.any():
            # No decode batch to fuse into: serial admission is the
            # fast path (and the bit-exactness oracle); the tick
            # budget still caps its chunk. Its fetch cannot be
            # deferred (the chunk loop needs the completion signal).
            tok = self.admit_step(slot,
                                  max_chunk_tokens=max_chunk_tokens)
            return PendingStep.done({} if tok is None else {slot: tok})
        S = int(st["prompt_np"].shape[0])
        done = st["done"]
        end, width = fused_chunk_span(done, S, st["chunk"],
                                      max_chunk_tokens,
                                      gran=self.cache.block_size)
        if width == 0:
            return self.step_async()    # budget left no chunk room
        with span("slot.grow"):
            grow = self._grow_active()
        final = end >= S
        with span("slot.launch"):
            nxt_logits, first_logits = self._fused_forward(
                slot, st, done, end, width, final, grow)
        st["done"] = end
        st["row_stale"] = True
        with span("slot.sample"):
            if final:
                # Admission pick before the decode pick: matches the
                # serial engine order on the sampler's key stream.
                first = self._sampler.pick(first_logits).astype(jnp.int32)
            nxt = self._sampler.pick(nxt_logits).astype(jnp.int32)
            self.last_token = jnp.where(self._active_dev[:, None],
                                        nxt[:, None], self.last_token)
        with span("slot.mirror"):
            lnp = self.cache.host_lengths()
            lnp[self.active] += 1
            decode_slots = [int(s) for s in np.nonzero(self.active)[0]]
            for s in decode_slots:
                if int(lnp[s]) >= self.slot_capacity:
                    self.active[s] = False
            if final:
                # Activation is dispatch-side device work: the slot's
                # first token stays on device (first[0] indexes the
                # device array, no fetch) until finalize.
                del self._admissions[slot]
                if self.prefix_cache:
                    publish_prefix(self.cache, st["blocks"],
                                   st["prompt_np"], keys=st["keys"])
                self.last_token = self.last_token.at[slot, 0].set(
                    first[0])
                self.active[slot] = True
            self._active_dev = upload_mirror(self.active)
        out_slots = decode_slots + ([slot] if final else [])

        def _finalize(invalid):
            self.device_fetches += 1
            if final:
                nxt_np, first_np = addressable_fetch((nxt, first))
            else:
                nxt_np = addressable_fetch(nxt)
            out: Dict[int, int] = {}
            for s in decode_slots:
                if s not in invalid:
                    out[s] = int(nxt_np[s])
            if final and slot not in invalid:
                out[slot] = int(first_np[0])
            return out

        return PendingStep(_finalize, slots=out_slots)

    def _fused_forward(self, slot: int, st, done: int, end: int,
                       width: int, final: bool, grow):
        """The fused tick's one forward: the decode rows' pending tokens
        and prompt[done:end) of the admitting slot through the pools,
        which are rebound here with the grown table and the advanced
        lengths. Everything the program needs of this tick rides it as
        a host argument: ``grow`` (``_grow_active``), the chunk as a
        numpy row at the program's width, and ``slot``, ``done`` and
        the chunk's real length as numpy scalars; the token batch, the
        positions, the write mask and the length advance are computed
        inside it (``tick_fused``). Returns (the decode rows' logits
        [B, V], the logits after the prompt's last token [1, V] where
        the chunk completes it, else None). The seam a family with its
        own fused program overrides (latent.LatentSlotServer)."""
        chunk = np.zeros((width,), np.int32)
        chunk[:end - done] = st["prompt_np"][done:end]
        host = (chunk, np.int32(slot), np.int32(done), np.int32(end - done))
        mkw = ({"mlora_idx": self._ml.dev} if self._ml.enabled
               else {})
        lengths0 = self.cache.lengths
        nxt, first, pk, pv, pks, pvs, lengths, table = self._pools_dispatch(
            self._fused,
            self.params, self.last_token, self.cache.pool_k,
            self.cache.pool_v, self.cache.block_table, lengths0,
            self._active_dev, grow, *host,
            pool_k_scale=self.cache.pool_k_scale,
            pool_v_scale=self.cache.pool_v_scale, **mkw)
        # Rebind donated pools immediately (see step()).
        self.cache = dataclasses.replace(
            self.cache, pool_k=pk, pool_v=pv, block_table=table,
            lengths=lengths, pool_k_scale=pks, pool_v_scale=pvs)
        if self._draft_lm:
            # One draft forward: decode rows mirror their pending
            # token's draft KV (a skipped write would leave a hole
            # every later draft step attends), the admitting row
            # advances the draft chunk — same batch over the table the
            # target's program just grew, at the lengths it started
            # from; logits dropped.
            _, _, self._dpk, self._dpv, *_ = self._pools_dispatch(
                self._draft_fused,
                self.draft_params, self.last_token, self._dpk, self._dpv,
                table, lengths0, self._active_dev, None, *host, **mkw)
        return nxt, (first if final else None)

    # -- speculation hooks (models/spec.py SpecDecodeMixin owns the
    # round driver; these supply the paged mechanics) -----------------

    def _spec_begin(self, h: int):
        """Blocks through position length+h (the round's last write:
        both the verify block's final token and the extra draft write
        land at length+h), clamped at capacity. The round's draft and
        verify programs all read one table, so its growth is one
        program of fixed shape ahead of them (``apply_growth``, the
        function a plain tick runs inside its step)."""
        grow = self._grow_active(extra=h)
        self.cache = dataclasses.replace(
            self.cache, block_table=self._pools_dispatch(
                self._grow, self.cache.block_table, self.cache.lengths,
                grow))
        return self.cache.lengths

    def _spec_mkw(self):
        return ({"mlora_idx": self._ml.dev} if self._ml.enabled else {})

    def _spec_draft_step(self, tok, base, j: int):
        """One draft decode over the draft pools at position base+j.
        self._dpk/_dpv rebind EACH step: the draft pools are donated
        into the dispatch, so a local alias would leave the
        attributes naming deleted buffers mid-loop."""
        dl, self._dpk, self._dpv, _, _, _ = self._pools_dispatch(
            self._draft_decode,
            self.draft_params, tok, self._dpk, self._dpv,
            self.cache.block_table, base + j, self._active_dev,
            **self._spec_mkw())
        return dl[:, 0]

    def _spec_draft_catchup(self, block, tok, base, h: int):
        """The extra (h+1)-th draft step: the proposal loop wrote KV
        only for its INPUT tokens (last, d1..d_{h-1}) at
        base..base+h-1; this writes d_h's KV at base+h with its output
        discarded. Without it, a fully-accepted round (next base =
        base+h+1) would leave a PERMANENT draft-KV hole at base+h that
        every later draft step attends — output stays correct
        (acceptance compares against the clean target) but acceptance,
        i.e. the whole speedup, decays round over round. On partial
        acceptance the extra write is stale and the next round
        overwrites it (same rollback discipline as the rest)."""
        del block                       # the paged catch-up is a step,
        _, self._dpk, self._dpv, _, _, _ = self._pools_dispatch(
            self._draft_decode,         # not a multi-token rewrite
            self.draft_params, tok, self._dpk, self._dpv,
            self.cache.block_table, base + h, self._active_dev,
            **self._spec_mkw())
        return self._dpk

    def _spec_verify(self, block, base):
        """ONE multi-token target verify over the pools; donated
        pools rebind immediately (see step()); lengths join the
        replace in _spec_commit once acceptance is known."""
        tl, pk, pv, pks, pvs = self._pools_dispatch(
            self._verify,
            self.params, block, self.cache.pool_k, self.cache.pool_v,
            self.cache.block_table, base, self._active_dev,
            pool_k_scale=self.cache.pool_k_scale,
            pool_v_scale=self.cache.pool_v_scale, **self._spec_mkw())
        self.cache = dataclasses.replace(
            self.cache, pool_k=pk, pool_v=pv,
            pool_k_scale=pks, pool_v_scale=pvs)
        return tl

    def _spec_commit(self, a_b, correction, active) -> None:
        lengths = self.cache.lengths \
            + (a_b + 1) * active.astype(jnp.int32)
        self.last_token = jnp.where(active[:, None], correction,
                                    self.last_token)
        self.cache = dataclasses.replace(self.cache, lengths=lengths)

    def _spec_host_lengths(self):
        return self.cache.host_lengths()

    def _spec_capacity(self) -> int:
        return self.slot_capacity

    @property
    def admitting_count(self) -> int:
        """Chunked admissions in flight (their blocks free on evict,
        so pool pressure with admissions pending is transient)."""
        return len(self._admissions)

    @property
    def admission_slots(self):
        """Slots with an in-flight chunked admission — the engine's
        quarantine path evicts any of these it is not tracking (an
        admission orphaned by a mid-admit fault still owns blocks)."""
        return list(self._admissions)

    def _refund_slot(self, slot: int) -> None:
        """Return the slot's whole KV-quota charge to its tenant —
        the single refund point, paired with the admission/growth
        charges (release() itself stays quota-blind: the quota is a
        server-level policy over the cache's mechanics)."""
        charged = self._slot_charge.pop(slot, 0)
        tenant = self._slot_tenant.pop(slot, None)
        if self.kv_quota is not None and tenant is not None:
            self.kv_quota.refund(tenant, charged)

    def slot_tenants(self) -> Dict[int, str]:
        """Live slot -> tenant view (engine preemption targeting)."""
        return dict(self._slot_tenant)

    def evict(self, slot: int) -> None:
        """Free the slot's blocks back to the pool (refcounted and
        LRU-retained when published; identical to plain evict when no
        prefix bookkeeping exists). Safe mid-admission: the chunk
        state is dropped with the blocks."""
        self.active[slot] = False
        self._active_dev = upload_mirror(self.active)
        self._admissions.pop(slot, None)
        if self._ml.enabled:
            self._ml.reset(slot)
        self._refund_slot(slot)
        self.cache = release(self.cache, slot)
