"""Host-RAM KV offload tier + cross-replica migration (r18).

The KV economy's correctness bar: a demoted block that promotes back
must reproduce BIT-IDENTICAL tokens to a never-evicted oracle (KV
promotion is a restore, not an approximation), a failed or refused
promotion must degrade to token-exact recompute, migration must land
only validated contiguous chain prefixes (gossip staleness = clean
miss, never corrupt KV), and the measured crossover policy must cite
real rates — or admit it ran blind.
"""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe
from tpushare.models import transformer as tf
from tpushare.models.kvtier import CHANNELS, CrossoverEstimator, HostKvTier
from tpushare.models.paged import PagedSlotServer
from tpushare.slo.quota import KvQuota, parse_quota_spec

CFG = tf.tiny(remat=False)
PARAMS = tf.init_params(jax.random.PRNGKey(0), CFG)
MOE_CFG = moe.tiny(remat=False)
MOE_PARAMS = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)
BS = 4
FAMILIES = ("dense", "moe")


def _prompt(seed, n, family="dense"):
    vocab = (MOE_CFG if family == "moe" else CFG).vocab_size
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, n), jnp.int32)


def _mk(tier=None, n_blocks=16, family="dense", **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("block_size", BS)
    kw.setdefault("max_blocks_per_slot", 8)
    kw.setdefault("prefix_cache", True)
    if family == "moe":
        srv = PagedSlotServer(MOE_PARAMS, MOE_CFG, n_blocks=n_blocks,
                              forward_fn=moe.paged_forward, **kw)
    else:
        srv = PagedSlotServer(PARAMS, CFG, n_blocks=n_blocks, **kw)
    if tier is not None:
        srv.cache.host_tier = tier
    return srv


def _decode(srv, slot, n):
    """Flattened greedy stream. Speculative servers return BURSTS per
    step and acceptance boundaries shift when the draft's own KV is
    rebuilt — but the accepted token SEQUENCE is target-law and must
    not."""
    out = [int(srv.last_token[slot, 0])]
    while len(out) < n:
        tok = srv.step()[slot]
        out.extend(tok if isinstance(tok, list) else [tok])
    return out[:n]


def _block(i=0.0):
    """One fake pool-block payload ([L, bs, Hkv, Dh]-shaped stand-in)."""
    return {"pool_k": np.full((2, 4, 2, 8), i, np.float32),
            "pool_v": np.full((2, 4, 2, 8), -i, np.float32)}


_BLOCK_NBYTES = sum(a.nbytes for a in _block().values())


# ---------------------------------------------------------------------
# CrossoverEstimator: the measured policy
# ---------------------------------------------------------------------

class TestCrossoverEstimator:
    def test_unmeasured_defaults_to_transfer_and_is_counted(self):
        est = CrossoverEstimator()
        assert est.rate("h2d") is None
        assert est.prefill_rate() is None
        assert est.decide("h2d", 1 << 20, 64) == "transfer"
        snap = est.snapshot()
        assert snap["decisions"]["unmeasured"] == 1
        assert snap["decisions"]["transfer"] == 1
        # Null-not-0: a channel never observed cites null rates.
        assert snap["channels"]["h2d"]["bytes_per_s"] is None
        assert snap["prefill"]["tokens_per_s"] is None

    def test_measured_rates_decide_the_crossover(self):
        est = CrossoverEstimator()
        est.observe_transfer("h2d", 1000, 1.0)      # 1000 B/s
        est.observe_prefill(100, 1.0)               # 100 tok/s
        # Moving 500 B (0.5 s) beats recomputing 100 tok (1.0 s).
        assert est.decide("h2d", 500, 100) == "transfer"
        # Moving 10 kB (10 s) loses to recomputing 100 tok (1.0 s).
        assert est.decide("h2d", 10_000, 100) == "recompute"
        # Exact tie goes to transfer (it also saves pool pressure).
        assert est.decide("h2d", 1000, 100) == "transfer"

    def test_channels_are_independent(self):
        est = CrossoverEstimator()
        est.observe_prefill(100, 1.0)
        est.observe_transfer("net", 10, 1.0)        # terrible network
        est.observe_transfer("h2d", 1_000_000, 1.0)  # fast local bus
        assert est.decide("net", 1000, 100) == "recompute"
        assert est.decide("h2d", 1000, 100) == "transfer"
        # The d2h channel is still unmeasured: optimistic transfer.
        assert est.decide("d2h", 1000, 100) == "transfer"

    def test_snapshot_cites_every_channel(self):
        snap = CrossoverEstimator().snapshot()
        assert set(snap["channels"]) == set(CHANNELS)
        for row in snap["channels"].values():
            assert set(row) == {"bytes_per_s", "bytes_total",
                                "seconds", "transfers"}

    def test_garbage_observations_are_ignored(self):
        est = CrossoverEstimator()
        est.observe_transfer("h2d", 0, 1.0)
        est.observe_transfer("h2d", 100, 0.0)
        est.observe_transfer("bogus", 100, 1.0)
        est.observe_prefill(0, 1.0)
        assert est.rate("h2d") is None
        assert est.prefill_rate() is None


# ---------------------------------------------------------------------
# HostKvTier: budget, LRU, tenant spill isolation
# ---------------------------------------------------------------------

class TestHostKvTier:
    def test_budget_must_be_positive(self):
        with pytest.raises(ValueError):
            HostKvTier(0)

    def test_put_get_roundtrip_and_inclusive_promote(self):
        tier = HostKvTier(10 * _BLOCK_NBYTES)
        data = _block(3.0)
        assert tier.put(b"k1", data, tokens=BS)
        got = tier.get(b"k1")
        assert got is data
        assert tier.begin_promote(b"k1", tokens=BS)
        taken, staged = tier.take_promote(b"k1")
        assert taken is data and not staged
        # Inclusive: the entry SURVIVES promotion (the next donation
        # wipe of the device prefix cache must not cost the host copy).
        assert tier.has(b"k1")
        assert tier.snapshot()["promotions"] == 1

    def test_global_budget_evicts_oldest_first(self):
        tier = HostKvTier(2 * _BLOCK_NBYTES)
        for i in range(3):
            assert tier.put(b"k%d" % i, _block(float(i)), tokens=BS)
        snap = tier.snapshot()
        assert snap["blocks_resident"] == 2
        assert snap["evictions"] == 1
        assert not tier.has(b"k0") and tier.has(b"k2")

    def test_oversized_block_is_refused_not_thrashed(self):
        tier = HostKvTier(_BLOCK_NBYTES // 2)
        tier.put(b"keep", {"pool_k": np.zeros(4, np.float32)})
        assert not tier.put(b"big", _block())
        assert tier.has(b"keep")        # refusal evicted nothing
        assert tier.snapshot()["put_refused"] == 1

    def test_tenant_spill_isolation(self):
        """A tenant past its host budget sheds ITS OWN oldest entries;
        a neighbor's warm state is untouchable through that path."""
        quota = KvQuota(parse_quota_spec(
            "acme=0::%d" % (2 * _BLOCK_NBYTES)))
        tier = HostKvTier(100 * _BLOCK_NBYTES, quota=quota)
        assert tier.put(b"bg", _block(), tenant="internal", tokens=BS)
        for i in range(4):
            assert tier.put(b"a%d" % i, _block(float(i)),
                            tenant="acme", tokens=BS)
        assert tier.has(b"bg")                      # neighbor intact
        assert not tier.has(b"a0") and not tier.has(b"a1")
        assert tier.has(b"a2") and tier.has(b"a3")
        assert quota.host_used["acme"] <= 2 * _BLOCK_NBYTES

    def test_eviction_refunds_the_quota_ledger(self):
        quota = KvQuota()
        tier = HostKvTier(2 * _BLOCK_NBYTES, quota=quota)
        for i in range(3):
            tier.put(b"k%d" % i, _block(), tenant="t", tokens=BS)
        assert quota.host_used["t"] == 2 * _BLOCK_NBYTES
        tier.pop(b"k1")
        tier.pop(b"k2")
        assert "t" not in quota.host_used       # clamped-out at zero

    def test_chaos_promote_fault_breaks_cleanly(self):
        tier = HostKvTier(10 * _BLOCK_NBYTES)
        tier.put(b"k", _block(), tokens=BS)

        def boom():
            raise RuntimeError("injected")
        tier.fault_promote = boom
        assert not tier.begin_promote(b"k", tokens=BS)
        assert tier.snapshot()["promote_failures"] == 1
        assert tier.has(b"k")           # failure never corrupts state

    def test_prefetch_stage_hit_and_stale_clear(self):
        tier = HostKvTier(10 * _BLOCK_NBYTES)
        tier.put(b"k", _block(), tokens=BS)
        tier.stage(b"k", {"pool_k": "devcopy"})
        taken, staged = tier.take_promote(b"k")
        assert staged and taken == {"pool_k": "devcopy"}
        tier.stage(b"stale", {"pool_k": "x"})
        tier.stage(b"keep", {"pool_k": "y"})
        tier.clear_staged(keep=(b"keep",))
        assert set(tier.staged) == {b"keep"}
        assert tier.snapshot()["prefetch_hit_rate"] == 1.0

    def test_snapshot_schema(self):
        snap = HostKvTier(1 << 20).snapshot()
        for k in ("blocks_resident", "bytes_resident", "budget_bytes",
                  "staged", "demotions", "promotions", "migrations_in",
                  "evictions", "demote_failures", "promote_failures",
                  "put_refused", "prefetch_hit_rate", "crossover"):
            assert k in snap, k
        assert snap["prefetch_hit_rate"] is None    # null-not-0


# ---------------------------------------------------------------------
# Quota spellings: the host_bytes third segment
# ---------------------------------------------------------------------

class TestQuotaHostBytes:
    def test_two_segment_spelling_unchanged(self):
        spec = parse_quota_spec("acme=16:64")["acme"]
        assert (spec.reserve, spec.ceiling, spec.host_bytes) \
            == (16, 64, None)

    def test_third_segment_parses(self):
        spec = parse_quota_spec("acme=16:64:1048576")["acme"]
        assert spec.host_bytes == 1048576
        assert parse_quota_spec("acme=16:64:")["acme"].host_bytes is None

    def test_negative_host_bytes_rejected(self):
        with pytest.raises(ValueError):
            parse_quota_spec("acme=0::-1")

    def test_snapshot_includes_host_rows(self):
        q = KvQuota(parse_quota_spec("acme=1:4:1000"))
        q.host_charge("acme", 600)
        row = q.snapshot()["acme"]
        assert row["host_bytes_used"] == 600
        assert row["host_bytes"] == 1000
        assert not q.host_over("acme")
        q.host_charge("acme", 600)
        assert q.host_over("acme")


# ---------------------------------------------------------------------
# Demote -> promote roundtrip: bit-exact vs never-evicted oracle
# ---------------------------------------------------------------------

def _force_transfer(tier):
    """Pin the crossover policy to "transfer". The roundtrip tests
    assert the MECHANISM (demote -> promote, bit-exact); whether the
    measured policy would bother is environment timing (a warm XLA
    cache makes recompute win) and is pinned separately."""
    tier.estimator.observe_transfer("d2h", 1 << 40, 1.0)
    tier.estimator.observe_transfer("h2d", 1 << 40, 1.0)
    return tier


def _roundtrip(tier, n_decode=6, family="dense", **server_kw):
    """Warm prompt A, evict, thrash the pool with fillers until A's
    blocks demote, re-admit A. Returns (oracle tokens, tier tokens,
    the tier, the server)."""
    a = _prompt(1, 13, family)
    server_kw["family"] = family
    # Oracle: pool big enough that nothing is ever reclaimed.
    big = _mk(None, n_blocks=64, **server_kw)
    slot = big.admit(a)
    want = _decode(big, slot, n_decode)

    srv = _mk(tier, n_blocks=10, **server_kw)
    slot = srv.admit(a)
    _decode(srv, slot, n_decode)
    srv.evict(slot)                     # A's chain parks on the LRU
    for seed in range(3, 7):            # thrash: reclaim demotes A
        f = srv.admit(_prompt(seed, 13, family))
        srv.evict(f)
    slot = srv.admit(a)                 # promote from the host tier
    got = _decode(srv, slot, n_decode)
    return want, got, srv


class TestDemotePromoteRoundtrip:
    @pytest.mark.parametrize("family", FAMILIES)
    def test_roundtrip_bit_exact(self, family):
        tier = _force_transfer(HostKvTier(32 << 20))
        want, got, srv = _roundtrip(tier, family=family)
        assert got == want
        snap = tier.snapshot()
        assert snap["demotions"] > 0, "thrash never demoted"
        assert snap["promotions"] > 0, "re-admit never promoted"
        # The promoted chain counted as cached prefix: the re-admit
        # prefilled less than the full prompt.
        assert srv.last_cached_len > 0
        # The estimator measured REAL transfers both ways, on top of
        # the one seeded observation per channel.
        cx = snap["crossover"]
        assert cx["channels"]["d2h"]["transfers"] > 1
        assert cx["channels"]["h2d"]["transfers"] > 1

    def test_speculative_roundtrip_bit_exact(self):
        """Promotion restores TARGET KV only (the draft prefix over a
        promoted region is zeros) — greedy speculation must stay
        target-law: identical tokens, whatever the acceptance rate."""
        tier = _force_transfer(HostKvTier(32 << 20))
        draft = (tf.init_params(jax.random.PRNGKey(9), CFG), CFG)
        want, got, srv = _roundtrip(tier, speculative_draft=draft,
                                    gamma=2)
        assert got == want
        assert tier.snapshot()["promotions"] > 0

    def test_kv_quant_roundtrip_bit_exact(self):
        """int8 pools demote all four rows (k, v, and both scale
        rows); a missing scale row would dequantize garbage."""
        tier = _force_transfer(HostKvTier(32 << 20))
        want, got, srv = _roundtrip(tier, kv_quant=True)
        assert got == want
        assert tier.snapshot()["promotions"] > 0

    @pytest.mark.parametrize("family", FAMILIES)
    def test_failed_promotion_recomputes_token_exact(self, family):
        tier = _force_transfer(HostKvTier(32 << 20))

        def boom():
            raise RuntimeError("injected promote fault")
        tier.fault_promote = boom
        want, got, srv = _roundtrip(tier, family=family)
        assert got == want              # recompute fallback, bit-exact
        snap = tier.snapshot()
        assert snap["promotions"] == 0
        assert snap["promote_failures"] > 0

    @pytest.mark.parametrize("family", FAMILIES)
    def test_chaos_demote_fault_degrades_to_eviction(self, family):
        tier = _force_transfer(HostKvTier(32 << 20))

        def boom():
            raise RuntimeError("injected demote fault")
        tier.fault_demote = boom
        want, got, srv = _roundtrip(tier, family=family)
        assert got == want              # plain eviction + recompute
        snap = tier.snapshot()
        assert snap["demotions"] == 0
        assert snap["demote_failures"] > 0

    def test_recompute_policy_skips_demotion(self):
        """A measured d2h rate so bad the crossover policy refuses to
        demote: blocks are destroyed (pre-r18 behavior), tokens stay
        exact."""
        tier = HostKvTier(32 << 20)
        tier.estimator.observe_transfer("d2h", 1, 10.0)  # 0.1 B/s
        tier.estimator.observe_prefill(10_000, 0.001)    # very fast
        want, got, srv = _roundtrip(tier)
        assert got == want
        snap = tier.snapshot()
        assert snap["demotions"] == 0
        assert snap["crossover"]["decisions"]["recompute"] > 0


# ---------------------------------------------------------------------
# Spill-before-429: the host tier absorbs what eviction destroyed
# ---------------------------------------------------------------------

class TestSpillBefore429:
    def test_pool_pressure_spills_to_host_not_destroys(self):
        """Under pool pressure the published chains a burst tenant
        forces out are DEMOTED (reusable) instead of destroyed —
        admissions keep succeeding exactly as before, and the spilled
        chains are charged to their first-writer tenants."""
        quota = KvQuota(parse_quota_spec("acme=0::%d" % (64 << 20)))
        tier = _force_transfer(HostKvTier(64 << 20, quota=quota))
        srv = _mk(tier, n_blocks=10, kv_quota=quota)
        srv.cache.host_tier = tier
        a = _prompt(1, 13)
        slot = srv.admit(a, tenant="acme")
        srv.evict(slot)
        for seed in range(3, 7):        # the burst that forces spill
            srv.evict(srv.admit(_prompt(seed, 13), tenant="acme"))
        assert tier.snapshot()["demotions"] > 0
        assert quota.host_used.get("acme", 0) > 0
        row = quota.snapshot()["acme"]
        assert row["host_bytes_used"] > 0
        assert row["host_bytes"] == 64 << 20


# ---------------------------------------------------------------------
# Engine + HTTP surface: /kv/blocks, /kv/migrate, /stats, gossip
# ---------------------------------------------------------------------

def _engine(**kw):
    from tpushare.chaos.smoke import build_engine
    eng, cfg = build_engine("dense", **kw)
    return eng, cfg


def _run_one(eng, prompt, max_tokens=4):
    from tpushare.cli.serve import _Request
    req = _Request(list(prompt), max_tokens, None)
    assert eng.submit(req)
    assert req.done.wait(60)
    assert req.error is None, req.error
    return req.tokens


class TestEngineSurface:
    def test_stats_null_without_tier(self):
        eng, _ = _engine()
        try:
            eng.start()
            st = eng.stats()
            assert st["host_tier"] is None
            assert st["host_prefetch_errors"] is None
        finally:
            eng.stop()

    def test_stats_schema_with_tier(self):
        eng, _ = _engine(host_kv_bytes=8 << 20)
        try:
            eng.start()
            st = eng.stats()
            ht = st["host_tier"]
            assert ht is not None
            assert ht["budget_bytes"] == 8 << 20
            assert set(ht["crossover"]["channels"]) == set(CHANNELS)
            assert st["host_prefetch_errors"] == 0
            json.dumps(st)              # the whole surface serializes
        finally:
            eng.stop()

    def test_host_tier_needs_prefix_cache(self):
        from tpushare.cli.serve import ServeEngine
        with pytest.raises(ValueError, match="prefix_cache"):
            ServeEngine(PARAMS, CFG, n_slots=2, n_blocks=16,
                        block_size=BS, prefix_cache=False,
                        host_kv_bytes=1 << 20)

    def test_gossip_includes_tier_resident_chains(self):
        eng, cfg = _engine(host_kv_bytes=8 << 20)
        try:
            eng.start()
            prompt = np.random.default_rng(0).integers(
                0, cfg.vocab_size, 20)
            _run_one(eng, [int(t) for t in prompt])
            dev_keys = set(eng.prefix_keys()["keys"])
            # Plant a tier-only chain: it must gossip too.
            eng._host_tier.put(b"\x01" * 32, _block(), tokens=BS)
            keys = eng.prefix_keys()["keys"]
            assert ("01" * 32) in keys
            assert dev_keys <= set(keys)
        finally:
            eng.stop()

    def test_kv_blocks_serves_device_and_tier_omits_unknown(self):
        eng, cfg = _engine(host_kv_bytes=8 << 20)
        try:
            eng.start()
            prompt = np.random.default_rng(1).integers(
                0, cfg.vocab_size, 20)
            _run_one(eng, [int(t) for t in prompt])
            keys = eng.prefix_keys()["keys"]
            assert keys
            out = eng.kv_blocks(keys + ["ff" * 32, "zz-not-hex"])
            assert out["block_size"] == 8
            assert set(out["blocks"]) == set(keys)  # unknown OMITTED
            for rec in out["blocks"].values():
                assert set(rec) == {"pool_k", "pool_v"}
                for leaf in rec.values():
                    assert {"dtype", "shape", "b64"} <= set(leaf)
        finally:
            eng.stop()

    def test_migrate_e2e_token_exact_and_staleness_clean(self):
        """Two engines over real HTTP: B pulls A's published chain,
        serves the shared-prefix prompt token-exact — and a pull
        naming chains A no longer holds (gossip staleness) lands only
        the valid contiguous prefix, never corrupt KV."""
        from tpushare.cli import serve as serve_mod
        eng_a, cfg = _engine(host_kv_bytes=8 << 20)
        eng_b, _ = _engine(host_kv_bytes=8 << 20)
        httpd_a = serve_mod.serve(eng_a, host="127.0.0.1", port=0)
        httpd_b = serve_mod.serve(eng_b, host="127.0.0.1", port=0)
        try:
            rng = np.random.default_rng(5)
            prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 20)]
            want = _run_one(eng_a, prompt)
            keys = eng_a.prefix_keys()["keys"]
            assert len(keys) >= 2
            a_url = "http://127.0.0.1:%d" % httpd_a.server_address[1]
            # Staleness first: a bogus key mid-chain breaks the
            # landing there (contiguous prefix only).
            out = eng_b.kv_migrate(a_url, [keys[0], "ee" * 32, keys[1]])
            assert out["migrated"] == 1
            # Then the full valid chain (re-landing the block the
            # staleness pull already holds is an idempotent overwrite).
            out = eng_b.kv_migrate(a_url, keys, tenant="acme")
            assert out["migrated"] == len(keys)
            ht = eng_b._host_tier.snapshot()
            assert ht["migrations_in"] == len(keys) + 1
            assert ht["crossover"]["channels"]["net"]["bytes_per_s"] \
                is not None
            got = _run_one(eng_b, prompt)
            assert got == want          # promoted chain, bit-exact
            assert eng_b._host_tier.snapshot()["promotions"] > 0
        finally:
            httpd_a.shutdown()
            httpd_b.shutdown()
            eng_a.stop()
            eng_b.stop()

    def test_migrate_unreachable_source_is_clean(self):
        eng, _ = _engine(host_kv_bytes=8 << 20)
        try:
            eng.start()
            out = eng.kv_migrate("http://127.0.0.1:9", ["aa" * 32])
            assert out["migrated"] == 0
            assert "error" in out
        finally:
            eng.stop()


# ---------------------------------------------------------------------
# Router: migration planning + host-tier load signal
# ---------------------------------------------------------------------

class TestRouterMigration:
    def _router(self, **kw):
        from tpushare.router.core import Router
        kw.setdefault("migrate_min_blocks", 2)
        return Router(["http://a:1", "http://b:2"],
                      poll_interval_s=9999, **kw)

    def test_plan_migration_finds_the_longer_holder(self):
        r = self._router()
        a, b = r.replicas
        a.block_size = b.block_size = 8
        keys = ["k0", "k1", "k2", "k3"]
        b.prefix_keys = {"k0", "k1", "k2"}
        plan = r.plan_migration(keys, a)
        assert plan is not None
        src, pull = plan
        assert src is b and pull == ["k0", "k1", "k2"]

    def test_plan_migration_respects_threshold(self):
        r = self._router()
        a, b = r.replicas
        a.block_size = b.block_size = 8
        a.prefix_keys = {"k0", "k1"}
        b.prefix_keys = {"k0", "k1", "k2"}      # only +1 block better
        assert r.plan_migration(["k0", "k1", "k2"], a) is None

    def test_plan_migration_disabled_and_no_gossip(self):
        r = self._router(migrate_min_blocks=0)
        a, b = r.replicas
        b.prefix_keys = {"k0", "k1", "k2"}
        assert r.plan_migration(["k0", "k1"], a) is None
        r2 = self._router()
        r2.replicas[1].prefix_keys = {"k0", "k1", "k2"}
        # chosen has no gossiped block size yet -> no plan
        assert r2.plan_migration(["k0", "k1", "k2"],
                                 r2.replicas[0]) is None

    def test_block_fetch_chaos_counts_failed_never_blocks(self):
        r = self._router(chaos_spec="block_fetch:raise@p=1.0;seed=1")
        a, b = r.replicas
        a.block_size = b.block_size = 8
        b.prefix_keys = {"k0", "k1"}
        r._maybe_migrate(a, ["k0", "k1"], None)
        st = r.stats()
        assert st["migrations_instructed"] == 1
        assert st["migrations_failed"] == 1
        assert st["migrated_blocks"] == 0

    def test_load_host_tier_pressure_neutral_on_null(self):
        r = self._router()
        a, b = r.replicas
        base = {"n_slots": 2, "queue_depth": 0, "active_slots": 0,
                "pool_free_frac": 0.5}
        a.stats = dict(base, host_tier=None)
        b.stats = dict(base, host_tier={"budget_bytes": 100,
                                        "bytes_resident": 100})
        la, lb = r._load(a), r._load(b)
        assert lb > la                  # a full tier adds pressure
        c = self._router().replicas[0]
        c.stats = dict(base)            # field absent entirely
        assert r._load(a) == pytest.approx(
            self._router()._load(c))    # null == absent == neutral
