"""Power retention decoder (Brumby): the Mistral/Qwen3 block with its
attention replaced by a gated recurrent state, the fourth family beside
transformer.py, moe.py and latent.py, served through the same slot
server interface (models/paged.py).

A layer, for its normed input x (``tpubench/references/retention.py`` is
the plain form of the same equations):

    q = x W_q [H, D]   k = x W_k [Hkv, D]   v = x W_v [Hkv, D]
    q, k <- RMSNorm over each head's D (w_qn, w_kn), then rotary, then
            each scaled by D^-1/2 (so q.k carries the 1/D that keeps
            (q.k)^2 in range; any constant cancels in o but for eps)
    log g = logsigmoid(x W_g + b_g) [Hkv]      float32, one gate a kv head
    S_t = g_t S_{t-1} + phi(k_t) v_t^T         z_t = g_t z_{t-1} + phi(k_t)
    o_i[t] = phi(q_i[t])^T S_t / (phi(q_i[t]) . z_t + eps),   kv head i // G
    h <- h + concat_i(o_i) W_o ;   h <- h + SwiGLU(RMSNorm(h))

``phi`` (ops/retention.py) makes ``phi(q).phi(k) = (q.k)^2``. A stream's
whole past is one row of ``state [layers, slots, Hkv, D, F]`` and ``z
[layers, slots, Hkv, F]`` in float32: no key or value is kept, nothing
grows with the context, and a tick reads AND writes every active slot's
row. Three programs, under the names the trace readers know:

  paged_decode   one token a slot: ``ops.retention.retention_step`` a
                 layer (the Pallas kernel on a TPU)
  paged_prefill  a chunk of one slot's prompt: a scan over inner chunks
                 of ``inner_chunk`` tokens, quadratic inside one
                 ((q.k)^2 under the cumulative gate), through the state
                 between them; the slot's row is zeroed where the chunk
                 is the prompt's first
  paged_fused    slots + chunk tokens through the weights once: the
                 decode rows take the step, the chunk the scan

A layer's weights are arrays of their own (``params["layers"][i]``), as
latent.py's are: nothing is sliced out of a stack.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpushare.models.paged import PagedSlotServer, _program
from tpushare.models.serving import bucket_len, upload_mirror
from tpushare.ops.norms import rms_norm
from tpushare.ops.retention import n_features, phi, retention_step
from tpushare.ops.rotary import apply_rotary, rotary_embedding
from tpushare.parallel.multihost import host_scalar
from tpushare.utils.profiling import span

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class RetentionConfig:
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_base: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    #: tokens of the prefill scan's quadratic part
    inner_chunk: int = 128
    #: the normaliser's eps, at the scale of (q.k / D)^2
    eps: float = 1e-6
    #: a serial admission's chunk where the caller names none
    prefill_chunk: int = 1024
    #: ``ops.retention.retention_step``'s ``impl``
    step_impl: str = "auto"

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def features(self) -> int:
        return n_features(self.head_dim)

    def state_shapes(self, n_slots: int):
        """(state, z): one row a slot a layer, float32."""
        row = (self.n_layers, n_slots, self.n_kv_heads)
        return (row + (self.head_dim, self.features), row + (self.features,))

    def state_bytes(self, n_slots: int = 1) -> int:
        return 4 * sum(math.prod(s) for s in self.state_shapes(n_slots))

    def pool_shapes(self, n_blocks: int, block_size: int):
        """What ``paged.init_paged_cache`` asks a family for: no row a
        token is cached, so the pools hold nothing (the block axis keeps
        its length: the token budget's bookkeeping reads it)."""
        empty = (0, n_blocks, block_size, 0)
        return empty, empty, None


def tiny(vocab_size: int = 256, **kw) -> RetentionConfig:
    """Toy widths: two kv heads of two query heads, an inner chunk
    shorter than a test's prompts."""
    base = dict(vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=2, head_dim=16, d_ff=96, rope_base=1e4,
                dtype=jnp.float32, inner_chunk=8, prefill_chunk=32)
    base.update(kw)
    return RetentionConfig(**base)


def init_params(rng: jax.Array, cfg: RetentionConfig) -> Dict[str, Any]:
    Dm, H, Hkv, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(rng, 16 * cfg.n_layers + 8))

    def dense(shape, fan_in, scale=1.0):
        return (jax.random.truncated_normal(next(keys), -2, 2, shape, _F32)
                * (scale / math.sqrt(fan_in))).astype(cfg.dtype)

    layers = []
    for _ in range(cfg.n_layers):
        layers.append({
            "ln1": jnp.ones((Dm,), cfg.dtype),
            "wq": dense((Dm, H * D), Dm),
            "wk": dense((Dm, Hkv * D), Dm),
            "wv": dense((Dm, Hkv * D), Dm),
            "q_norm": jnp.ones((D,), cfg.dtype),
            "k_norm": jnp.ones((D,), cfg.dtype),
            # a trained gate remembers: the bias puts g = sigmoid(b) in
            # 0.9 .. 0.999 (a stream forgets over 10 to 1,000 tokens, a
            # head each its own), and the projection moves the logit by
            # a quarter around it
            "wg": dense((Dm, Hkv), Dm, 0.25),
            "bg": jax.random.uniform(next(keys), (Hkv,), _F32,
                                     math.log(9.0), math.log(999.0)),
            "wo": dense((H * D, Dm), H * D),
            "ln2": jnp.ones((Dm,), cfg.dtype),
            "w_gate": dense((Dm, cfg.d_ff), Dm),
            "w_up": dense((Dm, cfg.d_ff), Dm),
            "w_down": dense((cfg.d_ff, Dm), cfg.d_ff)})
    return {"embed": dense((cfg.vocab_size, Dm), Dm),
            "unembed": dense((Dm, cfg.vocab_size), Dm),
            "final_norm": jnp.ones((Dm,), cfg.dtype),
            "layers": layers}


# ---------------------------------------------------------------------------
# One layer's arithmetic on flat tokens [N, Dm] at positions [N].
# ---------------------------------------------------------------------------


def _project(h, pos, w, cfg: RetentionConfig):
    """q [N, H, D], k, v [N, Hkv, D] float32 (q, k normed, rotated and
    scaled), log g [N, Hkv]. The projections leave the MXU in float32
    and stay there: (q.k)^2 doubles whatever rounding q and k carry."""
    N, H, Hkv, D = h.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def heads(name, n):
        return jnp.dot(h, w[name], preferred_element_type=_F32).reshape(
            N, n, D)

    cos, sin = rotary_embedding(pos, D, base=cfg.rope_base)

    def normed(x, weight):
        x = rms_norm(x, weight, eps=cfg.norm_eps)
        return apply_rotary(x[None], cos[None], sin[None])[0] * D ** -0.5

    log_g = jax.nn.log_sigmoid(
        jnp.dot(h, w["wg"], preferred_element_type=_F32) + w["bg"])
    return (normed(heads("wq", H), w["q_norm"]),
            normed(heads("wk", Hkv), w["k_norm"]), heads("wv", Hkv), log_g)


def chunk_scan(s0, z0, q, k, v, log_g, live, cfg: RetentionConfig):
    """A chunk of one stream through one layer's state.

    s0 [Hkv, D, F], z0 [Hkv, F]; q [P, H, D], k, v [P, Hkv, D], log_g
    [P, Hkv], live [P] (False: padding, which neither decays nor writes).
    P is a whole number of ``cfg.inner_chunk``. Returns (o [P, H, D],
    s, z). Inside an inner chunk the quadratic form, a[t, u] = (q_t.k_u)^2
    exp(G_t - G_u) with G the running sum of log g; between chunks the
    state: phi(Q) S for what came before, phi(K)^T V into it."""
    P, C, Hkv, G = q.shape[0], cfg.inner_chunk, cfg.n_kv_heads, cfg.group
    D = cfg.head_dim
    k = jnp.where(live[:, None, None], k, 0.0)
    log_g = jnp.where(live[:, None], log_g, 0.0)
    xs = (q.reshape(P // C, C, Hkv, G, D), k.reshape(P // C, C, Hkv, D),
          v.reshape(P // C, C, Hkv, D), log_g.reshape(P // C, C, Hkv))
    causal = jnp.tril(jnp.ones((C, C), bool))

    def inner(carry, x):
        s, z = carry
        qc, kc, vc, lg = x
        run = jnp.cumsum(lg, axis=0)                    # [C, Hkv], G_t
        # what came before the chunk, read through the state
        pq = phi(qc)                                    # [C, Hkv, G, F]
        reach = jnp.exp(run)[..., None]                 # [C, Hkv, 1]
        num = reach[..., None] * jnp.einsum(
            "chgf,hvf->chgv", pq, s, precision=_HI)
        den = reach * jnp.einsum("chgf,hf->chg", pq, z, precision=_HI)
        # the chunk's own tokens, quadratic
        decay = jnp.exp(jnp.where(
            causal[:, :, None], run[:, None] - run[None, :], -jnp.inf))
        a = (jnp.einsum("chgd,uhd->chgu", qc, kc, precision=_HI) ** 2
             * jnp.moveaxis(decay, 2, 1)[:, :, None, :])
        num = num + jnp.einsum("chgu,uhv->chgv", a, vc, precision=_HI)
        den = den + a.sum(-1)
        o = num / (den[..., None] + cfg.eps)
        # the chunk into the state
        left = jnp.exp(run[-1][None] - run)             # [C, Hkv]
        pk = phi(kc)                                    # [C, Hkv, F]
        total = jnp.exp(run[-1])
        s = total[:, None, None] * s + jnp.einsum(
            "uhv,uhf->hvf", vc * left[..., None], pk, precision=_HI)
        z = total[:, None] * z + jnp.einsum("uh,uhf->hf", left, pk,
                                            precision=_HI)
        return (s, z), o

    (s, z), o = jax.lax.scan(inner, (s0, z0), xs)
    return o.reshape(P, Hkv * G, D), s, z


def _run_layers(params, cfg: RetentionConfig, tokens, pos, attend):
    """The residual stream of flat ``tokens`` [N] at ``pos`` [N] through
    every layer; ``attend(layer, q, k, v, log_g) -> o [N, H, D]`` owns
    the state."""
    x = params["embed"][tokens].astype(cfg.dtype)
    N = x.shape[0]
    for li, w in enumerate(params["layers"]):
        h = rms_norm(x, w["ln1"], eps=cfg.norm_eps)
        o = attend(li, *_project(h, pos, w, cfg))
        x = x + o.astype(cfg.dtype).reshape(N, -1) @ w["wo"]
        h = rms_norm(x, w["ln2"], eps=cfg.norm_eps)
        x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x


def _logits(params, cfg: RetentionConfig, x):
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    return (x @ params["unembed"]).astype(_F32)


class _State:
    """The state through one program's layers: the decode rows' step and
    (``chunk``: slot, first, live) the admitting slot's scan."""

    def __init__(self, cfg, state, z, active, chunk=None):
        self.cfg, self.state, self.z = cfg, state, z
        self.active, self.chunk = active, chunk

    def __call__(self, li, q, k, v, log_g):
        cfg, B = self.cfg, 0 if self.active is None else self.active.shape[0]
        out = []
        if B:
            o, self.state, self.z = retention_step(
                self.state, self.z, li, q[:B], k[:B], v[:B], log_g[:B],
                self.active, eps=cfg.eps, impl=cfg.step_impl)
            out.append(o)
        if self.chunk is not None:
            slot, first, live = self.chunk
            s0 = jax.lax.dynamic_index_in_dim(self.state[li], slot, 0, False)
            z0 = jax.lax.dynamic_index_in_dim(self.z[li], slot, 0, False)
            # a prompt's first chunk starts the slot's row from nothing:
            # whatever the last stream left there is never read
            s0, z0 = (jnp.where(first, 0.0, a) for a in (s0, z0))
            o, s, zz = chunk_scan(s0, z0, q[B:], k[B:], v[B:], log_g[B:],
                                  live, cfg)
            self.state = jax.lax.dynamic_update_slice(
                self.state, s[None, None], (li, slot, 0, 0, 0))
            self.z = jax.lax.dynamic_update_slice(
                self.z, zz[None, None], (li, slot, 0, 0))
            out.append(o)
        return jnp.concatenate(out) if len(out) > 1 else out[0]


# ---------------------------------------------------------------------------
# The three programs of the slot server.
# ---------------------------------------------------------------------------


def decode_tick(params, tokens, state, z, lengths, active, *,
                cfg: RetentionConfig):
    """One token a slot. tokens [B, 1]. Returns (logits [B, 1, V], state,
    z, lengths advanced for the active slots)."""
    att = _State(cfg, state, z, active)
    x = _run_layers(params, cfg, tokens[:, 0], lengths, att)
    return (_logits(params, cfg, x)[:, None], att.state, att.z,
            lengths + active.astype(jnp.int32))


def _chunk_args(chunk_tokens, done, n_valid):
    P = chunk_tokens.shape[0]
    return done + jnp.arange(P), jnp.arange(P) < n_valid


def prefill_chunk(params, chunk_tokens, state, z, slot, done, n_valid, *,
                  cfg: RetentionConfig):
    """``chunk_tokens`` [P] (the first ``n_valid`` real) of slot
    ``slot``'s prompt at positions ``done``.. into its row of the state.
    Returns (the logits after the last real token [V], state, z)."""
    cpos, live = _chunk_args(chunk_tokens, done, n_valid)
    att = _State(cfg, state, z, None, chunk=(slot, done == 0, live))
    x = _run_layers(params, cfg, chunk_tokens, cpos, att)
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=0)
    return _logits(params, cfg, last)[0], att.state, att.z


def fused_tick(params, last_token, chunk_tokens, state, z, lengths, active,
               slot, done, n_valid, *, cfg: RetentionConfig):
    """A decode step for the active slots and a chunk of slot ``slot``'s
    prompt in ONE pass over the weights: B + P tokens. Returns (decode
    logits [B, V], the logits after the chunk's last real token [1, V],
    state, z, lengths advanced for the decode rows)."""
    B = last_token.shape[0]
    cpos, live = _chunk_args(chunk_tokens, done, n_valid)
    att = _State(cfg, state, z, active, chunk=(slot, done == 0, live))
    x = _run_layers(params, cfg,
                    jnp.concatenate([last_token[:, 0], chunk_tokens]),
                    jnp.concatenate([lengths, cpos]), att)
    last = jax.lax.dynamic_slice_in_dim(x, B + n_valid - 1, 1, axis=0)
    lg = _logits(params, cfg, jnp.concatenate([x[:B], last]))
    return (lg[:B], lg[B:], att.state, att.z,
            lengths + active.astype(jnp.int32))


def _no_forward(*a, **kw):
    raise NotImplementedError(
        "the retention family runs its own programs (retention.decode_tick, "
        "prefill_chunk, fused_tick), not the paged pool's forward")


class RetentionSlotServer(PagedSlotServer):
    """PagedSlotServer with a recurrent state where the pool was: the
    same admission (``admit_start`` / ``admit_step``), tick interface
    (``step_async``, the fused tick), sampler, quotas and capacity, over
    ``state`` and ``z`` donated through every program. The block table,
    the free list and the quota ledger stay as the TOKEN budget's
    bookkeeping (``block_size`` x ``max_blocks_per_slot`` tokens a slot,
    ``live_blocks`` in ``/stats``): the pools are empty, no program
    reads the table, and a tick's growth is its host half alone.
    Admission writes the slot's row in place chunk by chunk (no
    admission row, no block scatter); eviction frees the slot and its
    budget and touches no state."""

    #: no admission row, ever (``PagedSlotServer.admit_start``)
    lean_admission = True

    def __init__(self, params, cfg: RetentionConfig, **kw):
        for flag in ("kv_quant", "multi_lora", "speculative_draft", "mesh",
                     "layers_hook", "prefix_cache"):
            if kw.get(flag):
                raise ValueError(
                    f"the retention family does not support {flag}: a "
                    f"stream's past is one recurrent state, not blocks of "
                    f"keys and values to share, quantize, shard or adapt")
        super().__init__(params, cfg, forward_fn=_no_forward, **kw)
        n_slots = self.cache.n_slots
        self.state, self.z = (jnp.zeros(s, _F32)
                              for s in cfg.state_shapes(n_slots))
        self._row_bytes = cfg.state_bytes()
        #: state bytes read + written by decode and fused ticks, and the
        #: admission chunks run: counted on the host, off the active mask
        self.state_bytes_moved = 0
        self.ticks = 0
        self.chunks = 0
        self._decode_prog = jax.jit(
            _program("paged_decode", decode_tick, cfg=cfg),
            donate_argnums=(2, 3))
        self._prefill_prog = jax.jit(
            _program("paged_prefill", prefill_chunk, cfg=cfg),
            donate_argnums=(2, 3))
        self._fused_prog = jax.jit(
            _program("paged_fused", fused_tick, cfg=cfg),
            donate_argnums=(3, 4))
        self._decode = self._decode_state

    def family_stats(self) -> Dict[str, Any]:
        """What ``/stats`` adds for this family, all host arithmetic."""
        return {
            "retention_state_bytes": self._row_bytes * self.cache.n_slots,
            "retention_state_bytes_live":
                self._row_bytes * int(self.active.sum()),
            "retention_state_bytes_moved": self.state_bytes_moved,
            "retention_ticks": self.ticks,
            "retention_chunks": self.chunks,
        }

    def _recover_donated_pools(self) -> None:
        """The state died with a dispatch that raised after consuming it:
        fresh zeros, as the parent's pools (every in-flight request is
        replayed from its prompt)."""
        super()._recover_donated_pools()
        for name in ("state", "z"):
            arr = getattr(self, name)
            if arr.is_deleted():
                setattr(self, name, jnp.zeros(arr.shape, arr.dtype))

    def _moved(self) -> None:
        """A decode or fused tick ran: every active slot's state was read
        and written once."""
        self.ticks += 1
        self.state_bytes_moved += 2 * self._row_bytes * int(self.active.sum())

    # -- programs -----------------------------------------------------

    def _decode_state(self, params, tokens, pool_k, pool_v, table, lengths,
                      active, grow=None, pool_k_scale=None,
                      pool_v_scale=None):
        """``PagedSlotServer.step_async``'s one dispatch: the parent
        rebinds the (empty) pools and the table it is handed back."""
        logits, self.state, self.z, lengths = self._decode_prog(
            params, tokens, self.state, self.z, lengths, active)
        self._moved()
        return logits, pool_k, pool_v, None, None, lengths, table

    def _chunk(self, st, done: int, end: int, chunk: int):
        """prompt[done:end) as a numpy row at its program's width, with
        the three scalars. Widths double from one inner chunk up to the
        admission's chunk: a program a width, not a prompt length."""
        c = self.cfg.inner_chunk
        width = min(bucket_len(end - done, c), -(-chunk // c) * c)
        row = np.zeros((width,), np.int32)
        row[:end - done] = st["prompt_np"][done:end]
        self.chunks += 1
        return row, np.int32(done), np.int32(end - done)

    def _fused_forward(self, slot, st, done, end, width, final, grow):
        row, d, n = self._chunk(st, done, end, st["chunk"])
        nxt, first, self.state, self.z, lengths = self._pools_dispatch(
            self._fused_prog, self.params, self.last_token, row, self.state,
            self.z, self.cache.lengths, self._active_dev, np.int32(slot),
            d, n)
        self._moved()
        self.cache = dataclasses.replace(self.cache, lengths=lengths)
        return nxt, (first if final else None)

    def admit_start(self, prompt, adapter: int = -1,
                    chunk_tokens: Optional[int] = None,
                    tenant: Optional[str] = None) -> int:
        return super().admit_start(
            prompt, adapter=adapter, tenant=tenant,
            chunk_tokens=chunk_tokens or self.cfg.prefill_chunk)

    def admit_step(self, slot: int, max_chunk_tokens: Optional[int] = None):
        """The next serial chunk of a started admission straight into the
        slot's row; on the last, the first token (``PagedSlotServer.
        admit_step``'s contract)."""
        st = self._admissions[slot]
        S = int(st["prompt_np"].shape[0])
        chunk = st["chunk"]
        if max_chunk_tokens is not None:
            bs = self.cache.block_size
            chunk = max(bs, min(chunk, (max_chunk_tokens // bs) * bs))
        end = min(S, st["done"] + chunk)
        row, d, n = self._chunk(st, st["done"], end, chunk)
        with span("slot.admit.prefill"):
            last_logits, self.state, self.z = self._pools_dispatch(
                self._prefill_prog, self.params, row, self.state, self.z,
                np.int32(slot), d, n)
        st["done"] = end
        if end < S:
            return None
        del self._admissions[slot]
        with span("slot.sample"):
            nxt = self._sampler.pick(last_logits[None, :])[0].astype(jnp.int32)
            self.last_token = self.last_token.at[slot, 0].set(nxt)
        self.active[slot] = True
        self._active_dev = upload_mirror(self.active)
        self.device_fetches += 1
        with span("slot.admit.first_token"):
            # the admission's one fetch, as PagedSlotServer.admit_step's
            # (the baseline's note on host_scalar): a local read, counted
            return int(host_scalar(nxt))  # tpushare: ignore[TS103,TS104]
