"""The one slot server (PagedSlotServer) under both forward functions
it runs: ``family`` is the dense LM (transformer.forward) or the sparse
one (moe.paged_forward through the ``forward_fn`` seam), and the
reference is that family's own row-cache ``generate``. The retention
family (its subclass RetentionSlotServer: a recurrent state a slot, the
blocks a token budget) is a third ``family`` of the two classes whose
cases have meaning without blocks, against the greedy argmax of its
plain reference. Held for both:
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

from tpubench.references import retention as retention_reference
from tpushare.models import moe, quant, retention
from tpushare.models import transformer as tf
from tpushare.models.generate import generate
from tpushare.models.paged import PagedSlotServer
from tests.launch_trace import Session, tables_agree

TF_CFG = tf.tiny(remat=False)
MOE_CFG = moe.tiny(remat=False)
RET_CFG = retention.tiny()


def _retention_generate(params, prompts, cfg, max_new_tokens):
    """Greedy continuation by the plain reference's full forward: the
    quadratic form is causal, so every step runs at one padded length
    and reads the logits of the last real position."""
    config = {"num_attention_heads": cfg.n_heads,
              "num_key_value_heads": cfg.n_kv_heads,
              "head_dim": cfg.head_dim, "rms_norm_eps": cfg.norm_eps,
              "rope_theta": cfg.rope_base}
    toks = [int(t) for t in prompts[0]]
    for _ in range(max_new_tokens):
        padded = toks + [0] * (48 - len(toks))
        logits = retention_reference.forward(params, padded, config)
        toks.append(int(jnp.argmax(logits[len(toks) - 1])))
    return jnp.asarray([toks])


# family -> (cfg, params, the family's reference generate, server kwargs,
# the server)
FAMILY = {
    "dense": (TF_CFG, tf.init_params(jax.random.PRNGKey(0), TF_CFG),
              generate, {}, PagedSlotServer),
    "moe": (MOE_CFG, moe.init_params(jax.random.PRNGKey(0), MOE_CFG),
            moe.generate, {"forward_fn": moe.paged_forward},
            PagedSlotServer),
    "retention": (RET_CFG,
                  retention.init_params(jax.random.PRNGKey(0), RET_CFG),
                  _retention_generate, {}, retention.RetentionSlotServer),
}
BS = 4

#: the families that keep blocks of keys and values: every class; the
#: retention family joins where a case has meaning without them
paged_families = pytest.mark.parametrize("family", ["dense", "moe"])
every_family = pytest.mark.parametrize("family", sorted(FAMILY))


def _mk(family, **kw):
    cfg, params, _, fkw, server = FAMILY[family]
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_blocks", 32)
    kw.setdefault("block_size", BS)
    return server(params, cfg, **fkw, **kw)


def _prompt(family, seed, n):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, FAMILY[family][0].vocab_size, n), jnp.int32)


def _reference(family, prompt, n, **kw):
    cfg, params, gen = FAMILY[family][:3]
    out = gen(kw.pop("params", params), prompt[None, :], cfg,
              max_new_tokens=n, **kw)
    return [int(t) for t in np.asarray(out[0, prompt.shape[0]:])]


def _stream(srv, slot, n):
    out = [int(srv.last_token[slot, 0])]
    while len(out) < n:
        out.append(srv.step()[slot])
    return out


@every_family
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


@every_family
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


@paged_families
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


@paged_families
class TestInt8Weights:
    def test_int8_weights_match_int8_generate(self, family):
        # The server must be bit-exact vs generate ON THE SAME int8
        # params: the serving path itself adds zero error.
        cfg, params = FAMILY[family][:2]
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


@paged_families
class TestATickLaunchesOneProgram:
    """Block growth and the fused tick's batch ride the step's own
    program (ISSUE 31): between a tick's entry and its launch the host
    runs no device operation, the device table follows the host mirror
    through admissions, evictions and re-admissions, and a failure on
    either side of the host half leaves both consistent."""

    @staticmethod
    def _scenario(srv, family, tick):
        """Lengths 7, 6, 5 on blocks of 4: a tick where no slot crosses
        a block boundary, one where one does, a fused tick beside a
        crossing, then 7, 7, 7 for a tick where every slot crosses."""
        slots = [srv.admit(_prompt(family, 20 + n, n)) for n in (7, 6, 5)]
        tick("none")                            # 7 6 5: blocks all there
        tick("one")                             # 8 7 6: the first crosses
        a = srv.admit_start(_prompt(family, 30, 21), chunk_tokens=8)
        tick("fused", a)                        # 9 8 7: the second, fused
        for s in slots + [a]:
            srv.evict(s)
        for seed in (41, 42, 43):
            srv.admit(_prompt(family, seed, 7))
        tick("full")                            # 7 7 7
        tick("every")                           # 8 8 8: all three cross
        for s in range(srv.cache.n_slots):
            srv.evict(s)

    _READINGS = {}          # family -> (ticks, grown): one session each

    @classmethod
    def _readings(cls, family):
        if family not in cls._READINGS:
            cls._READINGS[family] = cls._traced(family)
        return cls._READINGS[family]

    @classmethod
    def _traced(cls, family):
        srv = _mk(family, n_slots=4, n_blocks=64)
        cls._scenario(srv, family,          # every shape once, untraced
                      lambda label, work=None: srv.step(prefill_work=work))
        grown = {}
        with Session() as ticks:
            def tick(label, work=None):
                before = (srv.growth_ticks, srv.blocks_grown)
                with ticks.tick(label):
                    srv.step(prefill_work=work)
                grown[label] = (srv.growth_ticks - before[0],
                                srv.blocks_grown - before[1])
                tables_agree(srv)
            cls._scenario(srv, family, tick)
        return ticks, grown

    @pytest.mark.parametrize("label,program,blocks", [
        ("none", "paged_decode", 0), ("one", "paged_decode", 1),
        ("every", "paged_decode", 3), ("fused", "paged_fused", 1)])
    def test_no_eager_operation_ahead_of_the_launch(
            self, family, label, program, blocks):
        ticks, grown = self._readings(family)
        # a tick that grows nothing uploads nothing; the others their
        # growth array, and a fused tick its chunk and three scalars too
        arguments = {"none": 0, "fused": 5}.get(label, 1)
        assert ticks[label] == {"programs": [program], "uploads": 0,
                                "arguments": arguments}
        assert grown[label] == (int(blocks > 0), blocks)

    def test_the_trace_reader_sees_an_eager_operation(self, family):
        """The control of the test above: the eager scatter the tick
        used to run is programs the reader counts."""
        from tpushare.utils.profiling import span
        table = jnp.full((4, 8), -1, jnp.int32)
        for _ in range(2):                      # the second run is warm
            with Session() as ticks:
                with ticks.tick("eager"):
                    with span("slot.launch"):
                        table.at[np.asarray([0, 1]), np.asarray([2, 3])].set(
                            jnp.asarray(np.asarray([5, 6], np.int32)))
        assert "scatter" in ticks["eager"]["programs"]
        assert len(ticks["eager"]["programs"]) > 1
        assert ticks["eager"]["uploads"] == 1
        assert ticks["eager"]["arguments"] > 1  # its index arrays, a call each

    @pytest.mark.parametrize("variant", ("plain", "int8-pool", "speculative"))
    def test_the_device_table_is_the_host_mirror_after_every_tick(
            self, family, variant):
        """Admissions (whole, chunked and fused), evictions and
        re-admissions between ticks; greedy streams are the family's
        reference whatever rode the program."""
        if variant == "int8-pool" and family == "moe":
            pytest.skip("kv_quant lives in the dense LM's forward")
        cfg, params = FAMILY[family][:2]
        kw = {"plain": {}, "int8-pool": {"kv_quant": True},
              "speculative": {"speculative_draft": (params, cfg),
                              "gamma": 3}}[variant]
        srv = _mk(family, n_slots=3, n_blocks=48, **kw)
        prompts, got = {}, {}
        pending = None

        def admit(seed, n, chunked=False):
            nonlocal pending
            p = _prompt(family, seed, n)
            if chunked:
                slot = pending = srv.admit_start(p, chunk_tokens=8)
            else:
                slot = srv.admit(p)
                got[slot] = [int(srv.last_token[slot, 0])]
            prompts[slot] = p
            return slot

        def tick():
            nonlocal pending
            out = srv.step(prefill_work=pending)
            tables_agree(srv)
            for slot, toks in out.items():
                got.setdefault(slot, []).extend(
                    toks if isinstance(toks, list) else [toks])
            if pending in out:
                pending = None

        def close(slot):
            if variant != "int8-pool":          # int8 KV is not the
                n = len(got[slot])              # reference's arithmetic
                assert got.pop(slot) == _reference(
                    family, prompts[slot], n), slot
            srv.evict(slot)
            tables_agree(srv)

        a, b = admit(1, 7), admit(2, 5)
        for _ in range(3):
            tick()
        c = admit(3, 19, chunked=True)          # fused beside a and b
        while pending is not None:
            tick()
        close(a)
        d = admit(4, 6)                         # a's slot again
        assert d == a
        for _ in range(6):
            tick()
        close(b), close(c)
        e = admit(5, 11, chunked=True)          # fused beside d alone
        while pending is not None:
            tick()
        for _ in range(4):
            tick()
        close(d), close(e)
        assert srv.cache.live_blocks() == 0
        assert srv.blocks_grown > 0 and srv.growth_ticks > 0

    def test_a_slot_grows_into_its_last_block_and_retires(self, family):
        srv = _mk(family, n_slots=1, n_blocks=8, max_blocks_per_slot=2)
        p = _prompt(family, 51, 3)              # one block; capacity 8
        s = srv.admit(p)
        got = [int(srv.last_token[s, 0])]
        while srv.active[s]:
            got.append(srv.step()[s])
            tables_agree(srv)
        assert int(srv.cache.host_lengths()[s]) == 8
        assert (srv.cache.host_table()[s] >= 0).all()
        assert srv.blocks_grown == 1
        assert got == _reference(family, p, len(got))

    def test_capacity_is_refused_with_the_host_state_intact(self, family):
        """A slot past its last block raises before anything is popped,
        charged or launched, though another slot wanted a block in the
        same tick."""
        from tpushare.models.paged import SlotCapacityExceeded
        from tpushare.slo.quota import KvQuota
        quota = KvQuota()
        srv = _mk(family, n_slots=2, n_blocks=16, max_blocks_per_slot=2,
                  kv_quota=quota)
        full = srv.admit(_prompt(family, 52, 6), tenant="acme")
        srv.step()                              # 7 of 8
        p = _prompt(family, 53, 3)
        other = srv.admit(p, tenant="beta")
        got = [int(srv.last_token[other, 0]), srv.step()[other]]
        assert not srv.active[full]             # 8 of 8: retired
        srv.active[full] = True                 # what retirement prevents
        before = (list(srv.cache.free), dict(srv.cache.refs),
                  dict(quota.used), srv.cache.host_table().copy(),
                  srv.growth_ticks)
        with pytest.raises(SlotCapacityExceeded):
            srv.step()                          # `other` is at 4: crossing
        assert (list(srv.cache.free), dict(srv.cache.refs),
                dict(quota.used)) == before[:3]
        np.testing.assert_array_equal(srv.cache.host_table(), before[3])
        assert srv.growth_ticks == before[4]
        tables_agree(srv)
        srv.active[full] = False
        got += [srv.step()[other] for _ in range(2)]
        assert quota.used["beta"] == 2          # the growth, charged once
        assert got == _reference(family, p, 4)

    def test_an_exhausted_pool_is_refused_with_the_host_state_intact(
            self, family):
        from tpushare.models.paged import PoolExhausted
        srv = _mk(family, n_slots=2, n_blocks=5)    # four usable blocks
        a, b = srv.admit(_prompt(family, 54, 7)), srv.admit(
            _prompt(family, 55, 7))
        srv.step()                                  # 8 and 8: both cross
        before = (list(srv.cache.free), srv.cache.host_table().copy())
        assert before[0] == []
        with pytest.raises(PoolExhausted):
            srv.step()
        assert list(srv.cache.free) == before[0]
        np.testing.assert_array_equal(srv.cache.host_table(), before[1])
        tables_agree(srv)
        srv.evict(b)
        assert a in srv.step()
        tables_agree(srv)

    @pytest.mark.parametrize("fused", (False, True), ids=("plain", "fused"))
    def test_a_failed_dispatch_rebuilds_the_table_from_the_mirror(
            self, family, fused):
        """The host half ran (block popped, mirror written) and the
        launch raised: the device table is uploaded from the mirror and
        the stream goes on as if the tick had only been late."""
        srv = _mk(family, n_slots=2)
        p = _prompt(family, 56, 7)
        s = srv.admit(p)
        got = [int(srv.last_token[s, 0]), srv.step()[s]]    # length 8
        work = (srv.admit_start(_prompt(family, 57, 13), chunk_tokens=8)
                if fused else None)
        name = "_fused" if fused else "_decode"
        real = getattr(srv, name)

        def broken(*a, **kw):
            raise RuntimeError("injected after the host half")

        setattr(srv, name, broken)
        free0 = len(srv.cache.free)
        with pytest.raises(RuntimeError, match="injected"):
            srv.step(prefill_work=work)
        setattr(srv, name, real)
        assert len(srv.cache.free) == free0 - 1     # the host half stands
        assert srv.cache.host_table()[s, 2] >= 0
        tables_agree(srv)                          # and the device has it
        for _ in range(4):
            got.append(srv.step(prefill_work=work
                                if work in srv.admission_slots
                                else None)[s])
            tables_agree(srv)
        assert got == _reference(family, p, len(got))

    def test_a_speculative_round_grows_across_two_blocks(self, family):
        """gamma 6 on blocks of 4: a round at length 7 writes through
        position 13, blocks 2 and 3 of the slot, in one growth program
        of fixed width ahead of the round's own."""
        from tpushare.models.paged import growth_width
        cfg, params = FAMILY[family][:2]
        assert growth_width(6, BS) == 3 and growth_width(0, BS) == 1
        srv = _mk(family, n_slots=2, n_blocks=48, gamma=6,
                  speculative_draft=(params, cfg))
        p = _prompt(family, 58, 7)
        s = srv.admit(p)
        got = [int(srv.last_token[s, 0])]
        assert (srv.cache.host_table()[s] >= 0).sum() == 2
        got += srv.step()[s]                        # the draft is the target
        assert srv.blocks_grown == 2 and srv.growth_ticks == 1
        assert (srv.cache.host_table()[s] >= 0).sum() == 4
        tables_agree(srv)
        while len(got) < 24:
            got += srv.step()[s]
            tables_agree(srv)
        assert got == _reference(family, p, len(got))
