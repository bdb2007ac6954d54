"""Automatic prefix caching over the paged pool (models/paged.py).

The cache-level checks: pool pressure eats a parked chain leaf first,
and release keeps the reference counts. What a slot server shows of
the prefix cache (bit-identical reuse, fewer unique blocks, retention
across eviction, reclaim of zero-ref blocks only) is held in
tests/test_slot_server.py::TestPrefixCache under both families.
"""

import numpy as np

from tpushare.models import paged
from tpushare.models import transformer as tf

CFG = tf.tiny(remat=False)
BS = 4


def test_reclaim_consumes_chains_leaf_first():
    """Pool pressure must eat a parked chain from its LEAF inward:
    root-first reclaim would orphan every surviving descendant (chain
    matching stops at the first miss) and zero the hit rate."""
    cache = paged.init_paged_cache(CFG, n_slots=2, n_blocks=9,
                                   block_size=BS, max_blocks_per_slot=8)
    prompt = np.arange(13, dtype=np.int32)      # 3 published + 1 tail
    cache, _, blocks = paged.admit_prefix(cache, 0, prompt)
    paged.publish_prefix(cache, blocks, prompt)
    cache = paged.release(cache, 0)
    assert len(cache.lru) == 3
    # Reclaim one block: must be the chain LEAF (last published).
    ids = paged.alloc_blocks(cache, len(cache.free) + 1)
    cache.free.extend(ids)      # borrower returns them unpublished
    cache2, cached_len, _ = paged.admit_prefix(cache, 1, prompt)
    assert cached_len == 2 * BS                 # root+middle still hit


def test_release_refcounts():
    cache = paged.init_paged_cache(CFG, n_slots=2, n_blocks=9,
                                   block_size=BS, max_blocks_per_slot=8)
    prompt = np.arange(9, dtype=np.int32)
    cache, c0, blocks = paged.admit_prefix(cache, 0, prompt)
    assert c0 == 0
    paged.publish_prefix(cache, blocks, prompt)
    cache, c1, _ = paged.admit_prefix(cache, 1, prompt)
    assert c1 == 8
    shared = [int(b) for b in np.asarray(cache.block_table[1, :2])]
    assert all(cache.refs[b] == 2 for b in shared)
    cache = paged.release(cache, 0)
    assert all(cache.refs[b] == 1 for b in shared)
    assert not cache.lru                # still referenced by slot 1
    cache = paged.release(cache, 1)
    assert all(b in cache.lru for b in shared)
    assert all(b not in cache.refs for b in shared)
