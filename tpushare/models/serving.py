"""Tensor-parallel serving: sharded prefill + decode for multi-chip pods.

The BASELINE.md mixed bin-pack config runs a Llama-3-8B serving pod on
a multi-chip ICI sub-mesh the plugin allocated (GetPreferredAllocation
hands out contiguous sub-meshes; the pod sees them via
TPU_VISIBLE_CHIPS). This module is the tenant-side serving path over
that sub-mesh: params and KV cache shard heads over ``tp``, every
decode step runs fully SPMD with exactly one psum per block half, and
the scanned generation loop from models/generate.py applies unchanged
because forward() derives head counts from the (sharded) param shapes.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from tpushare.models.generate import sample_logits
from tpushare.utils.profiling import span
from tpushare.models.transformer import (
    ParallelCtx, TransformerConfig, forward, init_cache, param_specs,
)


def cache_specs() -> Dict[str, P]:
    """KV cache PartitionSpec: [L, B, S, Hkv, Dh], kv heads over tp."""
    spec = P(None, None, None, "tp", None)
    return {"k": spec, "v": spec}


def _params_contract(cfg: TransformerConfig, quantized: bool):
    """(param specs, layers_hook) for full-precision or int8 params —
    the one place the quantized placement contract lives for the
    DENSE serving factories (the MoE analog is
    quant.quant_moe_param_specs, used by make_moe_decoder)."""
    if not quantized:
        return param_specs(cfg), None
    from tpushare.models.quant import dequant_hook, quant_param_specs
    return quant_param_specs(cfg), dequant_hook(cfg)


def _decoder_fns(step_fn, mesh: Mesh, pspecs, cspecs):
    """Shared tail of the decoder factories: shard_map the step over
    (params, tokens, cache, offset), jit, and wrap as the
    (prefill_fn, decode_fn) pair. ``offset`` may be a scalar
    (lockstep batch) or a per-sequence [B] array (ragged continuous
    batching) — jit specializes on the offset's rank, so each
    compiles once."""
    fn = shard_map(
        step_fn, mesh=mesh,
        in_specs=(pspecs, P(), cspecs, P()),
        out_specs=(P(), cspecs),
    )
    jfn = jax.jit(fn)

    def prefill_fn(params, tokens, cache):
        return jfn(params, tokens, cache, jnp.asarray(0, jnp.int32))

    def decode_fn(params, token, cache, offset):
        return jfn(params, token, cache, jnp.asarray(offset, jnp.int32))

    return prefill_fn, decode_fn


def make_tp_decoder(cfg: TransformerConfig, mesh: Mesh, *,
                    quantized: bool = False):
    """Build (prefill_fn, decode_fn) sharded over mesh's tp axis.

    prefill_fn(params, tokens, cache) -> (logits, cache)
    decode_fn(params, token, cache, offset) -> (logits, cache)

    Params must be placed per param_specs(cfg) — or, with
    ``quantized``, per quant.quant_param_specs(cfg); caches per
    cache_specs() (init via sharded_cache below). tp must divide
    n_kv_heads.
    ``offset`` may be a scalar or a per-sequence [B] array (ragged
    continuous-batching decode) — both are replicated across the mesh.

    ``quantized``: params are a quant.quantize_params tree — int8
    weight storage shards over tp exactly like the bf16 weights (the
    per-output-channel scales keep the output-axis sharding), and each
    rank dequantizes its local slice per layer inside the scan
    (layers_hook), so the tp weight stream stays int8 in HBM.
    """
    tp = mesh.shape["tp"]
    if cfg.n_kv_heads % tp:
        raise ValueError(f"tp={tp} must divide n_kv_heads={cfg.n_kv_heads}")
    pctx = ParallelCtx(tp="tp")
    pspecs, hook = _params_contract(cfg, quantized)
    cspecs = cache_specs()

    def _step(params, tokens, cache, offset):
        logits, cache = forward(params, tokens, cfg, pctx=pctx,
                                cache=cache, pos_offset=offset,
                                layers_hook=hook)
        # No reduction needed here: inputs are replicated and the tp
        # psums inside forward already made the logits tp-unvarying.
        return logits, cache

    return _decoder_fns(_step, mesh, pspecs, cspecs)


def sharded_cache(cfg: TransformerConfig, mesh: Mesh, batch: int,
                  max_len: int):
    """A tp-sharded KV cache placed on ``mesh``. Accepts MoEConfig
    too — the MoE KV cache is deliberately the same [L, B, S, Hkv,
    Dh] layout (moe.init_cache docstring), so one placement helper
    serves both decoder families."""
    from tpushare.parallel.sharding import shard_tree
    cache = init_cache(cfg, batch, max_len)
    return shard_tree(cache, mesh, cache_specs())


def make_moe_decoder(cfg, mesh: Mesh, *, quantized: bool = False):
    """Build (prefill_fn, decode_fn) for the MoE LM over mesh's
    ep x tp axes — the make_tp_decoder contract (same signatures,
    same cache_specs head split) with experts sharded over ep.

    prefill_fn(params, tokens, cache) -> (logits, cache)
    decode_fn(params, token, cache, offset) -> (logits, cache)

    Params must be placed per moe.param_specs(cfg) — or, with
    ``quantized``, per quant.quant_moe_param_specs(cfg) (the int8
    expert stacks shard over ep/tp exactly like bf16; scales keep
    every non-reduced axis's sharding); caches per cache_specs()
    (init via sharded_cache — the MoE cache layout is identical).
    ep must divide n_experts and tp must divide n_kv_heads. Routing
    follows cfg.routing under ep_axis="ep" (experts hold no decode
    state, so every dispatch strategy decodes unchanged).
    """
    from tpushare.models import moe as _moe
    missing = {"ep", "tp"} - set(mesh.shape)
    if missing:
        # The step body binds both axis names unconditionally; a
        # missing axis must fail here, not as an unbound-axis error
        # deep inside shard_map (size-1 axes are fine — make_mesh
        # materializes every canonical axis).
        raise ValueError(f"make_moe_decoder needs mesh axes ep and tp "
                         f"(missing {sorted(missing)})")
    ep = mesh.shape["ep"]
    tp = mesh.shape["tp"]
    if cfg.n_experts % ep:
        raise ValueError(f"ep={ep} must divide n_experts="
                         f"{cfg.n_experts}")
    if cfg.n_kv_heads % tp:
        raise ValueError(f"tp={tp} must divide n_kv_heads="
                         f"{cfg.n_kv_heads}")
    pctx = ParallelCtx(tp="tp")
    hook = None
    if quantized:
        # Deliberately the dequant hook, not fused_expert_hook: this
        # shard_map path is the dryrun parity oracle whose banked
        # MULTICHIP rows were measured against it, and the fused
        # kernel's per-shard dispatch is validated on the placement
        # (jit-SPMD) serving path (PagedSlotServer mesh= + quant specs).
        from tpushare.models.quant import (
            dequant_hook, quant_moe_param_specs,
        )
        pspecs = quant_moe_param_specs(cfg)
        hook = dequant_hook(cfg)
    else:
        pspecs = _moe.param_specs(cfg)
    cspecs = cache_specs()

    def _step(params, tokens, cache, offset):
        logits, _aux, cache = _moe.forward(
            params, tokens, cfg, pctx=pctx, ep_axis="ep",
            cache=cache, pos_offset=offset, layers_hook=hook)
        return logits, cache

    return _decoder_fns(_step, mesh, pspecs, cspecs)


def paged_pool_specs() -> P:
    """Paged KV pool PartitionSpec: [L, n_blocks, bs, Hkv*Dh], kv
    heads over tp: a page holds its heads merged, head-major, so
    splitting the merged axis tp ways is the same head split as
    cache_specs (tp divides Hkv); block tables and lengths stay
    replicated — they are tiny int32 control state."""
    return P(None, None, None, "tp")


def make_tp_paged_decoder(cfg: TransformerConfig, mesh: Mesh, *,
                          block_size: int, attn_impl: str = "auto",
                          quantized: bool = False):
    """Tensor-parallel paged decode step over ``mesh``.

    decode_fn(params, tokens, pool_k, pool_v, table, lengths, active)
      -> (logits, pool_k, pool_v, lengths)

    Pools must be placed per paged_pool_specs(); params per
    param_specs(cfg) — or quant.quant_param_specs(cfg) with
    ``quantized`` (int8 weight stream, per-rank per-layer dequant, as
    make_tp_decoder). The block-table gather happens per shard on the
    tp-local head slice, so paged storage composes with the Megatron
    psums unchanged (models/paged.decode_core with pctx=tp).
    """
    from tpushare.models.paged import decode_core

    tp = mesh.shape["tp"]
    if cfg.n_kv_heads % tp:
        raise ValueError(f"tp={tp} must divide n_kv_heads={cfg.n_kv_heads}")
    pctx = ParallelCtx(tp="tp")
    pool_spec = paged_pool_specs()
    pspecs, hook = _params_contract(cfg, quantized)

    def _step(params, tokens, pool_k, pool_v, table, lengths, active):
        # decode_core's fixed 6-arity carries None scale slots for the
        # full-precision pools; drop them here (the tp factory's int8
        # composition is the weight stream via ``quantized``, not the
        # KV pools — kv_quant sharded pools are a documented seam).
        logits, pk, pv, _, _, new_len = decode_core(
            params, tokens, pool_k, pool_v, table, lengths,
            active, cfg=cfg, block_size=block_size,
            attn_impl=attn_impl, pctx=pctx, layers_hook=hook)
        return logits, pk, pv, new_len

    fn = shard_map(
        _step, mesh=mesh,
        in_specs=(pspecs, P(), pool_spec, pool_spec, P(), P(), P()),
        out_specs=(P(), pool_spec, pool_spec, P()),
    )
    return jax.jit(fn)


def mesh_axes(mesh) -> Optional[Dict[str, int]]:
    """Mesh axis sizes with 1-sized axes elided — THE spelling /stats
    and the bench rows report ({} = a 1-device mesh, None = no mesh);
    one home so the observability surfaces cannot drift."""
    if mesh is None:
        return None
    return {ax: int(s) for ax, s in mesh.shape.items() if s > 1}


def make_placement(mesh, cfg, param_specs=None, *, role: str = "target"):
    """Build-and-validate a MeshPlacement (None mesh → None) — the one
    constructor every slot-server family (and its draft side) calls,
    so the spec-default/validation contract cannot drift between
    them."""
    if mesh is None:
        return None
    place = MeshPlacement(mesh, param_specs or default_param_specs(cfg))
    place.check(cfg, role=role)
    return place


def mesh_attn_impl(mesh, attn_impl: str) -> str:
    """The attention implementation a slot server spanning ``mesh`` may
    dispatch to. The sharded forwards are plain jit over
    NamedSharding-placed arrays (MeshPlacement), and JAX refuses to
    lower a Mosaic kernel there: "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map."
    The CPU lane never met this because the kernels are off on the
    CPU; on the chip every eligible prefill and decode would raise it.
    So over more than one device "auto" resolves to the XLA reference
    paths, which GSPMD partitions like any other op. Running the
    kernels per tp shard of the head axis (a shard_map round each
    call, threaded through every copy of the block) is ROADMAP S7's."""
    if mesh is not None and mesh.size > 1 and attn_impl == "auto":
        return "reference"
    return attn_impl


def default_param_specs(cfg):
    """The family's full-precision PartitionSpec tree, resolved off the
    config shape (MoEConfig carries n_experts). Quantized params trees
    (quant.quantize_params) have a different leaf structure — callers
    serving int8 weights pass quant.quant_param_specs(cfg) /
    quant.quant_moe_param_specs(cfg) explicitly."""
    if hasattr(cfg, "n_experts"):
        from tpushare.models import moe as _moe
        return _moe.param_specs(cfg)
    return param_specs(cfg)


class MeshPlacement:
    """The ONE home of the sharded slot servers' placement contract.

    Weights place per the family's param_specs (tensor-parallel dense
    attention/MLP; expert x tensor-parallel MoE — experts over ``ep``,
    per-expert GEMMs over ``tp``). KV storage — dense rows
    [L, B, S, Hkv, Dh] AND paged pools [L, nb, bs, Hkv*Dh] (heads
    merged, head-major) — splits the kv heads over ``tp``
    (cache_specs / paged_pool_specs, the same head split the shard_map
    decoder factories use). Control state (block tables, lengths,
    token buffers, active masks) stays replicated: every mutation is
    host-decided, so block ids are HOST-GLOBAL by construction — the
    pool's block axis is never sharded — and admission / eviction /
    prefix-sharing logic in models/paged.py runs placement-blind.

    The servers' jitted forwards are NOT shard_mapped: placement alone
    makes jit compile them SPMD over the mesh (GSPMD inserts the
    collectives), so step/_spec_step/_fused_tick, chunked admission,
    and speculation run the exact same code sharded and unsharded —
    which is what makes the single-chip engine a usable correctness
    oracle. The sync-free invariant generalizes to ONE FETCH PER HOST:
    the token fetch reads a replicated array, so each process's
    device_get gathers from its own addressable shard — still exactly
    one transfer per tick per host."""

    def __init__(self, mesh, param_specs_tree):
        self.mesh = mesh
        self._pspecs = param_specs_tree
        # THE kv-head split, not a copy of it: cache_specs() is the
        # one home of the dense rows' layout ([L, B, S, Hkv, Dh]) and
        # paged_pool_specs() of the pool's ([L, nb, bs, Hkv*Dh]) — a
        # layout change there must move this placement with it.
        self.kv = NamedSharding(mesh, cache_specs()["k"])
        self.pool = NamedSharding(mesh, paged_pool_specs())

    @property
    def shape(self) -> Dict[str, int]:
        """Mesh axis sizes, 1-sized axes elided (the /stats spelling)."""
        return mesh_axes(self.mesh)

    def check(self, cfg, *, role: str = "target") -> None:
        """Fail loudly before any placement: a non-dividing axis would
        either error deep inside XLA or silently pad."""
        tp = self.mesh.shape.get("tp", 1)
        ep = self.mesh.shape.get("ep", 1)
        if cfg.n_kv_heads % tp:
            raise ValueError(f"tp={tp} must divide the {role} model's "
                             f"n_kv_heads={cfg.n_kv_heads}")
        n_experts = getattr(cfg, "n_experts", None)
        if n_experts is None:
            if ep > 1:
                raise ValueError(
                    f"ep={ep} is an expert-parallel axis; the {role} "
                    f"model is dense (use tp, or serve an MoE family)")
        elif n_experts % ep:
            raise ValueError(f"ep={ep} must divide the {role} model's "
                             f"n_experts={n_experts}")
        unused = [ax for ax, s in self.mesh.shape.items()
                  if s > 1 and ax not in ("tp", "ep")]
        if unused:
            raise ValueError(
                f"serving shards over tp/ep only; axes {unused} would "
                f"silently replicate every weight and pool shard")

    def place_params(self, params):
        from tpushare.parallel.sharding import shard_tree
        return shard_tree(params, self.mesh, self._pspecs)

    def place_kv(self, tree):
        """Place dense-row KV leaves on the kv-head split."""
        return jax.device_put(tree, self.kv)

    def place_pool(self, pool):
        """Place a paged KV pool on the same head split."""
        return jax.device_put(pool, self.pool)


def upload_mirror(mirror: np.ndarray) -> jnp.ndarray:
    """The device copy of a host mirror that is mutated in place
    afterwards (the active mask, the block table, the adapter ids).
    An upload reads its argument when the transfer RUNS, not when it
    is called: the CPU client aliases the numpy buffer and copies it
    in a program of its own, a chip's may read it until the transfer
    completes. So a mutation right after the call reached programs
    launched BEFORE it, and since the engine fetches tick N only after
    it has launched N+1 the next mutation (an evict, an activation)
    is microseconds away. The mirror is copied on the host first; the
    copy is nobody else's to mutate."""
    return jnp.asarray(mirror.copy())


def bucket_len(n: int, floor: int = 16) -> int:
    """Next power of two >= n (floor 16): admits compile once per
    bucket, not once per distinct prompt length — the ONE bucketing
    policy every slot server shares."""
    b = floor
    while b < n:
        b *= 2
    return b


def fused_chunk_span(done: int, S: int, chunk: int,
                     max_chunk_tokens=None, gran: int = 1):
    """This tick's fused-admission span [done, end) and the padded
    batch width — the ONE chunk-scheduling policy every fused tick
    shares. Mid chunks run at the fixed ``chunk`` width (one compile
    per chunk size); the final chunk bucket-pads, capped at ``chunk``
    (compile variants stay O(log chunk)). ``max_chunk_tokens`` is the
    engine's per-tick token budget for the chunk, rounded down to
    ``gran`` (the paged pool's block size; 1 for dense rows). Returns
    (end, width); width == 0 means the budget leaves no room for even
    one granule and the caller should run a plain tick."""
    eff = chunk
    if max_chunk_tokens is not None:
        eff = min(eff, (max_chunk_tokens // gran) * gran)
    if eff < max(1, gran):
        return done, 0
    end = min(S, done + eff)
    width = min(bucket_len(end - done), eff) if end >= S else eff
    return end, width


def fused_token_batch(last_token: jnp.ndarray, prompt: jnp.ndarray,
                      done: int, end: int, width: int,
                      slot: int) -> jnp.ndarray:
    """The fused engine tick's [B, width] token batch: every row's
    column 0 is its pending last token (decode rows consume exactly
    that; their columns >= 1 are junk whose KV the length masks keep
    unattended until real writes overwrite it), and the admitting row
    carries prompt[done:end] zero-padded to ``width``. One batch, one
    forward, one weight stream for decode AND admission.

    Traced inside the fused tick's program (paged.tick_fused), never
    run eagerly ahead of it: there ``prompt`` is the chunk the host
    padded to ``width`` (done 0, end ``width``) and ``slot`` a traced
    scalar; ``done``, ``end`` and ``width`` are static."""
    B = last_token.shape[0]
    toks = jnp.zeros((B, width), jnp.int32).at[:, 0].set(last_token[:, 0])
    row = jnp.zeros((width,), jnp.int32).at[:end - done].set(
        jnp.asarray(prompt[done:end], jnp.int32))
    return toks.at[slot].set(row)


class TokenSampler:
    """The per-server sampling state both slot servers share: one
    jitted sample_logits dispatch plus a (seed, draw-counter) key
    stream, so slot streams are reproducible for a given (seed,
    admission order)."""

    def __init__(self, temperature: float = 0.0, top_k=None, top_p=None,
                 seed: int = 0):
        self._rng = jax.random.PRNGKey(seed)
        self._draws = 0
        base = functools.partial(sample_logits, temperature=temperature,
                                 top_k=top_k, top_p=top_p)

        def _sample_guarded(logits, key):
            # A NaN logits row must surface as the INVALID token -1:
            # bare argmax/categorical LAUNDERS a poisoned row into a
            # plausible in-vocab id and the stream corrupts silently.
            # The engine's token validation quarantines the -1 slot
            # (cli/serve.py failure domains). Fused into the one
            # jitted sampler dispatch and riding the existing token
            # fetch — no extra transfer, no extra dispatch.
            tok = base(logits, key)
            bad = jnp.isnan(logits).any(axis=-1)
            return jnp.where(bad, jnp.asarray(-1, tok.dtype), tok)

        self._sample = jax.jit(_sample_guarded)

    def next_key(self) -> jax.Array:
        """One key off the (seed, draw-counter) stream — for consumers
        that sample outside pick() (speculative accept/resample) but
        must stay on the server's reproducible stream."""
        key = jax.random.fold_in(self._rng, self._draws)
        self._draws += 1
        return key

    def pick(self, logits: jnp.ndarray) -> jnp.ndarray:
        """[B, V] logits -> [B] token ids under the sampling config
        (greedy when temperature == 0); jitted once at construction —
        the per-token decode hot path must not dispatch a full-vocab
        sort/cumsum op-by-op. A NaN logits row picks -1 (invalid by
        construction), which the serving engine quarantines. The
        speculative paths apply the SAME discipline at their one home
        (models/spec.py): greedy verify through
        spec.greedy_verify_tokens, and stochastic acceptance through
        spec.spec_accept_core — a poisoned verify row can never
        accept and a cut on one emits the -1 sentinel instead of
        resampling through a NaN softmax (the laundering residual
        documented since the chaos PR, closed by the seam)."""
        return self._sample(logits, self.next_key())


def validate_adapter(adapter: int, enabled: bool, bank_size: int) -> None:
    """Host-side multi-LoRA index check shared by both slot servers: a
    jit gather CLAMPS an out-of-range index, which would silently
    serve another tenant's adapter — fail loud instead. Bools are
    rejected too (bool subclasses int: {"adapter": true} from JSON
    would silently select adapter 1)."""
    if isinstance(adapter, bool) or not isinstance(adapter, int):
        raise ValueError(f"adapter must be an int, got {adapter!r}")
    if adapter != -1 and not (enabled and 0 <= adapter < bank_size):
        raise ValueError(
            f"adapter {adapter} out of range for a bank of "
            f"{bank_size} (multi_lora "
            f"{'set' if enabled else 'not set'}) — a clamped device "
            f"gather would silently serve another tenant's adapter")


class MultiLoraSlots:
    """Per-slot adapter bookkeeping shared by both slot servers: the
    bank size, the host-truth adapter array, its device mirror, and
    the prefill wrapper that pins a single row's adapter. One copy so
    validation and bookkeeping cannot drift between servers."""

    def __init__(self, multi_lora, n_slots: int):
        self.enabled = multi_lora is not None
        self.bank_size = (jax.tree.leaves(multi_lora)[0].shape[1]
                          if self.enabled else 0)
        self._host = np.full(n_slots, -1, np.int32)
        self.dev = jnp.full((n_slots,), -1, jnp.int32)

    def validate(self, adapter: int) -> None:
        validate_adapter(adapter, self.enabled, self.bank_size)

    def adapter_of(self, slot: int) -> int:
        return int(self._host[slot])

    def set(self, slot: int, adapter: int) -> None:
        self._host[slot] = adapter
        self.dev = upload_mirror(self._host)

    def reset(self, slot: int) -> None:
        self.set(slot, -1)

    def wrap_prefill(self, prefill_fn, adapter: int):
        """Single-row prefill with this adapter pinned (mlora_idx [1])."""
        if not self.enabled:
            return prefill_fn
        idx1 = jnp.asarray([adapter], jnp.int32)
        return lambda p, t, **kw: prefill_fn(p, t, mlora_idx=idx1, **kw)


class PendingStep:
    """A dispatched tick whose one device->host token fetch is still
    owed. ``step_async`` returns one: all device work for the tick is
    already enqueued (forwards, cache/length rebinds, activations),
    and ``finalize()`` performs the deferred fetch and builds the
    ``{slot: token}`` dict. ``step() == step_async().finalize()`` —
    the serial engine keeps exact one-transfer-per-tick semantics,
    while the overlapped engine holds the PendingStep until it has
    dispatched the NEXT tick, so at most two are owed and the fetch
    returns when this tick's program ends with the next one already
    queued behind it.

    ``finalize(invalid=...)`` skips slots whose request changed while
    the tick was in flight (evicted, or evicted-and-readmitted): their
    in-flight tokens are dropped and the replay machinery regenerates
    them token-exactly. Finalize is one-shot; a pipeline flush simply
    abandons the object without calling it (no fetch happens).
    """

    __slots__ = ("_fn", "_ready", "slots")

    def __init__(self, finalize_fn=None, *, ready=None,
                 slots: Tuple[int, ...] = ()):
        self._fn = finalize_fn
        self._ready = ready
        #: slots whose tokens this tick will produce (dispatch-time
        #: snapshot; the engine's identity guard is keyed on these)
        self.slots = tuple(slots)

    @classmethod
    def done(cls, out: Dict[int, Any]) -> "PendingStep":
        """An already-finalized tick (empty batch, or a path whose
        fetch could not be deferred) — finalize() is a no-op lookup."""
        return cls(ready=out, slots=tuple(out))

    def finalize(self, invalid=frozenset()) -> Dict[int, Any]:
        if self._fn is None:
            out = self._ready
            if invalid:
                out = {s: t for s, t in out.items() if s not in invalid}
            return out
        fn, self._fn = self._fn, None
        # The tick's one device->host transfer: the host waits here
        # for the device (every slot server's deferred fetch).
        with span("slot.fetch"):
            return fn(frozenset(invalid))
