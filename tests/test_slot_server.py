"""The one slot server (PagedSlotServer) under both forward functions
it runs: ``family`` is the dense LM (transformer.forward) or the sparse
one (moe.paged_forward through the ``forward_fn`` seam), and the
reference is that family's own row-cache ``generate``. Held for both:
streams equal independent generation, slots recycle, a slot retires at
its capacity, sampled decode is reproducible, chunked admission equals
whole admission and interleaves with decode, an evict cancels an
admission and returns its blocks, prefix hits are block-granular,
bit-identical and survive eviction, and pool pressure reclaims only
what nobody references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe, quant
from tpushare.models import transformer as tf
from tpushare.models.generate import generate
from tpushare.models.paged import PagedSlotServer

TF_CFG = tf.tiny(remat=False)
MOE_CFG = moe.tiny(remat=False)
# family -> (cfg, params, the family's reference generate, server kwargs)
FAMILY = {
    "dense": (TF_CFG, tf.init_params(jax.random.PRNGKey(0), TF_CFG),
              generate, {}),
    "moe": (MOE_CFG, moe.init_params(jax.random.PRNGKey(0), MOE_CFG),
            moe.generate, {"forward_fn": moe.paged_forward}),
}
BS = 4

pytestmark = pytest.mark.parametrize("family", sorted(FAMILY))


def _mk(family, **kw):
    cfg, params, _, fkw = FAMILY[family]
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_blocks", 32)
    kw.setdefault("block_size", BS)
    return PagedSlotServer(params, cfg, **fkw, **kw)


def _prompt(family, seed, n):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, FAMILY[family][0].vocab_size, n), jnp.int32)


def _reference(family, prompt, n, **kw):
    cfg, params, gen, _ = FAMILY[family]
    out = gen(kw.pop("params", params), prompt[None, :], cfg,
              max_new_tokens=n, **kw)
    return [int(t) for t in np.asarray(out[0, prompt.shape[0]:])]


def _stream(srv, slot, n):
    out = [int(srv.last_token[slot, 0])]
    while len(out) < n:
        out.append(srv.step()[slot])
    return out


class TestStreamsAndSlots:
    def test_mixed_length_slots_match_independent_generation(self, family):
        srv = _mk(family, n_slots=4)
        p1, p2 = _prompt(family, 11, 6), _prompt(family, 12, 9)
        s1, s2 = srv.admit(p1), srv.admit(p2)
        assert s1 != s2
        got = {s1: [int(srv.last_token[s1, 0])],
               s2: [int(srv.last_token[s2, 0])]}
        for _ in range(4):
            for slot, tok in srv.step().items():
                got[slot].append(tok)
        for prompt, slot in ((p1, s1), (p2, s2)):
            assert got[slot] == _reference(family, prompt, 5), slot

    def test_evicted_slot_serves_the_next_prompt_exactly(self, family):
        srv = _mk(family, n_slots=1)
        s1 = srv.admit(_prompt(family, 13, 5))
        with pytest.raises(RuntimeError, match="no free slots"):
            srv.admit(_prompt(family, 14, 4))
        srv.step()
        srv.evict(s1)
        assert not srv.active.any()
        p2 = _prompt(family, 14, 4)
        s2 = srv.admit(p2)
        assert s2 == s1
        assert _stream(srv, s2, 5) == _reference(family, p2, 5)

    def test_step_with_no_active_slots_is_noop(self, family):
        assert _mk(family).step() == {}

    def test_sampled_decode_stays_reproducible(self, family):
        """Same (seed, admission order) -> the same sampled streams."""
        def run():
            srv = _mk(family, temperature=0.9, top_k=16, top_p=0.95,
                      seed=7)
            srv.admit(jnp.arange(5, dtype=jnp.int32))
            srv.admit(jnp.arange(3, dtype=jnp.int32))
            return [sorted(srv.step().items()) for _ in range(4)]

        a, b = run(), run()
        assert a == b
        assert any(tok for _, tok in a[0])          # produced real tokens

    def test_retires_at_capacity(self, family):
        srv = _mk(family, n_slots=1, n_blocks=8, max_blocks_per_slot=2)
        s = srv.admit(_prompt(family, 11, 6))   # length 6, capacity 8
        srv.step()                              # 7
        out = srv.step()                        # 8 == capacity -> retired
        assert s in out
        assert not srv.active[s]
        assert srv.step() == {}

    def test_prompt_over_slot_capacity_is_refused(self, family):
        srv = _mk(family, n_slots=1, n_blocks=8, max_blocks_per_slot=2)
        with pytest.raises(ValueError, match="capacity"):
            srv.admit(_prompt(family, 15, 8))   # 8+1 tokens > 2 blocks * 4
        assert len(srv.cache.free) == 7         # nothing leaked
        assert srv.admit(_prompt(family, 15, 7)) == 0

    def test_reuse_of_retired_slot_reclaims_blocks(self, family):
        # A slot that retired at capacity keeps its blocks (readable
        # until evict); admitting into it must return them to the pool,
        # not leak them (free + live == n_blocks - 1 trash block).
        srv = _mk(family, n_slots=1, n_blocks=8, max_blocks_per_slot=2)
        p1 = _prompt(family, 11, 6)
        for _ in range(3):
            srv.admit(p1)                       # reuses the retired slot
            while srv.active[0]:
                srv.step()
            assert len(srv.cache.free) + srv.cache.live_blocks() == 7

    def test_evict_reclaims_pool_blocks(self, family):
        srv = _mk(family, n_blocks=5, max_blocks_per_slot=4)
        p1, p2 = _prompt(family, 11, 6), _prompt(family, 12, 9)
        s1 = srv.admit(p1)                      # 6+1 tokens -> 2 of 4 usable
        used = srv.cache.live_blocks()
        with pytest.raises(RuntimeError, match="exhausted"):
            srv.admit(p2)                       # 9+1 -> 3 blocks, only 2 free
        srv.evict(s1)
        assert srv.cache.live_blocks() == 0
        assert srv.admit(p2) in (0, 1)
        assert srv.cache.live_blocks() >= used

    def test_grow_exhaustion_keeps_free_list_intact(self, family):
        # Two slots crossing a block boundary with one free block: the
        # shortfall must raise without popping (no leaked blocks).
        pa = _prompt(family, 11, 3)
        srv = _mk(family, n_blocks=4, max_blocks_per_slot=2)
        srv.admit(pa)
        srv.admit(pa)                           # 2 live, 1 free (1 trash)
        assert len(srv.cache.free) == 1
        srv.step()                              # lengths 3 -> 4 (block full)
        with pytest.raises(RuntimeError, match="exhausted"):
            srv.step()                          # both need block 1, one free
        assert len(srv.cache.free) == 1         # nothing leaked


class TestChunkedAdmission:
    """vLLM-style chunked prefill: admit_start/admit_step must produce
    bit-identical KV and tokens to a whole-prompt admit."""

    def test_chunked_matches_whole_admit(self, family):
        prompt = _prompt(family, 5, 19)
        whole = _mk(family)
        want = _stream(whole, whole.admit(prompt), 6)
        assert want == _reference(family, prompt, 6)

        chunked = _mk(family)
        slot = chunked.admit_start(prompt, chunk_tokens=8)
        assert chunked.admitting_count == 1
        steps, tok = 0, None
        while tok is None:
            tok = chunked.admit_step(slot)
            steps += 1
        assert steps == 3                   # 19 tokens / 8-aligned chunks
        assert chunked.admitting_count == 0
        assert [tok] + _stream(chunked, slot, 6)[1:] == want

    def test_decode_interleaves_with_admission(self, family):
        # An active stream keeps decoding between another slot's
        # chunks, and both final streams are the reference's.
        p0, p1 = _prompt(family, 22, 5), _prompt(family, 23, 11)
        srv = _mk(family)
        s0 = srv.admit(p0)
        s1 = srv.admit_start(p1, chunk_tokens=4)
        got0 = [int(srv.last_token[s0, 0])]
        first1 = None
        while first1 is None:
            got0.append(srv.step()[s0])     # decode between chunks
            first1 = srv.admit_step(s1)
        got1 = [first1]
        for _ in range(4):
            out = srv.step()
            got0.append(out[s0])
            got1.append(out[s1])
        assert got0 == _reference(family, p0, len(got0))
        assert got1 == _reference(family, p1, len(got1))

    def test_final_chunk_at_slot_capacity_is_exact(self, family):
        # The slot-capacity twin of the row server's max_len edge: a
        # 19-token prompt in a 20-token slot, whose last chunk pads to
        # the slot's last row, must keep parity with whole admission
        # and with the reference; one decode step then retires it.
        prompt = _prompt(family, 24, 19)
        kw = dict(n_slots=1, n_blocks=8, max_blocks_per_slot=5)
        whole = _mk(family, **kw)
        sw = whole.admit(prompt)
        chunked = _mk(family, **kw)
        sc = chunked.admit_start(prompt, chunk_tokens=16)
        while chunked.admit_step(sc) is None:
            pass
        want = _reference(family, prompt, 2)
        for srv, s in ((whole, sw), (chunked, sc)):
            assert [int(srv.last_token[s, 0]), srv.step()[s]] == want
            assert not srv.active[s]            # 20 == capacity

    def test_evict_cancels_an_admission_and_frees_its_blocks(self, family):
        srv = _mk(family, n_slots=1)
        free0 = len(srv.cache.free)
        slot = srv.admit_start(_prompt(family, 7, 16), chunk_tokens=4)
        assert srv.admitting_count == 1
        assert len(srv.cache.free) < free0
        with pytest.raises(RuntimeError, match="no free slots"):
            srv.admit(_prompt(family, 8, 2))    # admitting is not free
        srv.admit_step(slot)                    # one chunk in
        srv.evict(slot)
        assert srv.admitting_count == 0
        assert len(srv.cache.free) == free0
        assert not srv.active[slot]
        assert srv.admit(_prompt(family, 8, 2)) == slot


class TestPrefixCache:
    """A prefix hit is bit-identical KV reuse by whole blocks: sharing
    reduces unique pool blocks, retention survives eviction, and pool
    pressure reclaims only zero-ref published blocks."""

    @staticmethod
    def _unique_live(cache):
        ids = np.asarray(cache.block_table)
        return len({int(x) for x in ids.ravel() if int(x) >= 0})

    def test_prefix_sharing_matches_plain_server(self, family):
        shared = _prompt(family, 7, 8)
        a = jnp.concatenate([shared, _prompt(family, 8, 5)])
        b = jnp.concatenate([shared, _prompt(family, 9, 3)])
        streams = {}
        for pc in (False, True):
            srv = _mk(family, n_blocks=24, max_blocks_per_slot=8,
                      prefix_cache=pc)
            sa, sb = srv.admit(a), srv.admit(b)
            if pc:
                # b shares the two full 4-token prefix blocks of a.
                assert srv.last_cached_len == 8
                assert self._unique_live(srv.cache) == 5
            else:
                assert self._unique_live(srv.cache) == 7
            toks = {sa: [], sb: []}
            for _ in range(4):
                for slot, t in srv.step().items():
                    toks[slot].append(t)
            streams[pc] = (toks[sa], toks[sb])
        assert streams[False] == streams[True]
        assert streams[True][1] == _reference(family, b, 5)[1:]

    def test_chunked_admission_publishes_its_blocks(self, family):
        shared = _prompt(family, 6, 12)
        p1 = jnp.concatenate([shared, jnp.asarray([1, 2, 3], jnp.int32)])
        p2 = jnp.concatenate([shared,
                              jnp.asarray([4, 5, 6, 7], jnp.int32)])
        srv = _mk(family, prefix_cache=True)
        slot = srv.admit_start(p1, chunk_tokens=4)
        while srv.admit_step(slot) is None:
            pass
        assert srv.last_cached_len == 0
        s2 = srv.admit(p2)
        assert srv.last_cached_len == 12        # three whole blocks
        assert _stream(srv, s2, 4) == _reference(family, p2, 4)

    def test_identical_prompt_caps_at_recomputing_tail(self, family):
        prompt = _prompt(family, 3, 12)
        srv = _mk(family, prefix_cache=True)
        s0 = srv.admit(prompt)
        first = [srv.step()[s0] for _ in range(3)]
        s1 = srv.admit(prompt)
        # S=12, bs=4: full blocks 0..2 published, but matching stops at
        # (S-1)//bs = 2 blocks so the last token is always recomputed.
        assert srv.last_cached_len == 8
        assert [srv.step()[s1] for _ in range(3)] == first

    def test_retention_survives_eviction(self, family):
        prompt = _prompt(family, 5, 10)
        srv = _mk(family, prefix_cache=True)
        s0 = srv.admit(prompt)
        want = _stream(srv, s0, 3)
        srv.evict(s0)
        assert len(srv.cache.lru) > 0   # published blocks parked, not freed
        s1 = srv.admit(prompt)
        assert srv.last_cached_len == 8     # hit straight off the LRU
        assert _stream(srv, s1, 3) == want

    def test_pool_pressure_reclaims_only_zero_ref(self, family):
        # 8 usable blocks (9 - trash), prompts of 13 tokens need 4 each.
        srv = _mk(family, n_blocks=9, max_blocks_per_slot=8,
                  prefix_cache=True)
        p1, p2, p3 = (_prompt(family, s, 13) for s in (11, 12, 13))
        srv.evict(srv.admit(p1))
        assert set(srv.cache.lru)
        s1 = srv.admit(p2)                  # takes the 4 remaining free
        s2 = srv.admit(p1)                  # hits p1's parked blocks
        assert srv.last_cached_len == 12    # all 3 published blocks of p1
        srv.evict(s1)
        srv.evict(s2)
        s3 = srv.admit(p3)                  # reclaims under pressure
        live = {int(x) for x in np.asarray(srv.cache.block_table[s3])
                if int(x) >= 0}
        for blk in live:
            assert blk not in srv.cache.lru
            assert srv.cache.refs[blk] >= 1

    def test_shared_blocks_never_written_by_decode(self, family):
        # S = 8, a multiple of bs: the shareable blocks end exactly at
        # the slot's write frontier — the adversarial case for
        # copy-on-write.
        prompt = _prompt(family, 13, 8)
        srv = _mk(family, prefix_cache=True)
        s0 = srv.admit(prompt)
        s1 = srv.admit(prompt)
        assert srv.last_cached_len == 4     # (S-1)//bs = 1 full block
        shared = int(np.asarray(srv.cache.block_table[s1, 0]))
        assert shared == int(np.asarray(srv.cache.block_table[s0, 0]))
        before = np.asarray(srv.cache.pool_k[:, shared])
        for _ in range(6):                  # decode across a block boundary
            srv.step()
        np.testing.assert_array_equal(
            before, np.asarray(srv.cache.pool_k[:, shared]))


class TestInt8Weights:
    def test_int8_weights_match_int8_generate(self, family):
        # The server must be bit-exact vs generate ON THE SAME int8
        # params: the serving path itself adds zero error.
        cfg, params, _, _ = FAMILY[family]
        qp = quant.quantize_params(params, cfg)
        hook = quant.dequant_hook(cfg)
        srv = PagedSlotServer(qp, cfg, n_slots=3, n_blocks=32,
                              block_size=BS, layers_hook=hook,
                              **FAMILY[family][3])
        p0, p1 = _prompt(family, 13, 9), _prompt(family, 14, 5)
        s0, s1 = srv.admit(p0), srv.admit(p1)
        got = {s0: [int(srv.last_token[s0, 0])],
               s1: [int(srv.last_token[s1, 0])]}
        for _ in range(6):
            for s, t in srv.step().items():
                got[s].append(t)
        for s, p in ((s0, p0), (s1, p1)):
            assert got[s] == _reference(family, p, 7, params=qp,
                                        layers_hook=hook), s
