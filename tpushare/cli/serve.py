"""tpushare-serve: HTTP serving daemon over the paged slot server.

The tenant-side integration of the whole serving stack: continuous
batching (PagedSlotServer), automatic prefix caching, optional int8 KV
pools and multi-LoRA — behind one stdlib HTTP endpoint a pod can run
as its container command under the plugin's injected env.

Design: one ENGINE thread owns the model and the slot server (JAX
state is mutated from exactly one thread); HTTP handlers only enqueue
requests and wait on a per-request event. The engine loop admits
pending prompts into free slots, advances every active slot one token
per iteration (one jitted step — batching across requests is the
whole point), and completes requests at max_tokens or EOS.

API (token ids in, token ids out — tokenization is the caller's;
this framework is model-plumbing, not a tokenizer registry):

  POST /v1/completions  {"prompt": [int, ...], "max_tokens": N,
                         "eos": int (optional),
                         "adapter": i (optional multi-LoRA bank index,
                                       -1 = base model),
                         "stream": bool (optional)}
      -> {"id": rid, "tokens": [int, ...], "cached_prefix": C}
      -> stream=true: text/event-stream of `id: N` + `data:
         {"token": t}` events as tokens decode (the monotonic event
         id N = tokens delivered so far — the resume cursor), closing
         with `data: {"done": true, "cached_prefix": C}` (or `data:
         {"error": ...}`); the request id rides the `X-Request-Id`
         response header; client disconnect cancels the generation
         and frees the slot.
         An `Idempotency-Key` request header makes the admission
         EXACTLY-ONCE (r15): a retried POST with the same key
         re-attaches to the live request or returns the completed
         result — never double-executes; the same key with a
         DIFFERENT prompt is a 409 (a client bug, not a retry). The
         dedupe window is journal-backed (--journal-dir), so it
         survives process death.
  GET /v1/completions/{id}?from=N
                        -> resume a stream mid-generation (r15):
                           text/event-stream of the request's events
                           from cursor N (`Last-Event-ID` is honored
                           when ?from= is absent), byte-identical to
                           the uninterrupted stream's token events —
                           after either side drops, reconnect and
                           continue; 404 for an unknown (or
                           dedupe-window-evicted) id
  GET /healthz          -> LIVENESS: the engine thread is alive or
                           restartable (a draining/restarting replica
                           is still live — kubelet must not kill it)
  GET /readyz           -> READINESS: accepting new work (503 while
                           draining/restarting — the router and the
                           k8s readiness probe stop sending, nothing
                           kills the pod). The old single /healthz bit
                           conflated "kill me" with "stop routing to
                           me"; the split is the contract now
  GET /prefixes         -> prefix-cache gossip: the hex chain keys
                           this replica's pool currently holds (the
                           router's affinity key)
  GET /stats            -> slots / pool / prefix-cache / recovery counters
  POST /drain           -> stop accepting new work (the co-located
                           plugin's device-health churn hook POSTs
                           this when a chip goes unhealthy); accepted
                           work runs to completion
  POST /mesh/host       -> whole-host health churn {"rank": r,
                           "healthy": bool}: a process-aware engine
                           (gang-granted multi-host mesh) shrinks
                           across the process boundary / grows back —
                           the failure ladder's last rung
  POST /mesh/chip       -> per-chip health churn {"device"|"chip": i,
                           "healthy": bool}: a SHARDED engine degrades
                           onto its surviving chips (quarantine +
                           token-exact replay + re-carve + rebuild —
                           the mesh failure domain) or grows back once
                           all chips recover; an unsharded engine
                           falls back to drain/undrain (one chip IS
                           its whole domain)

Failure domains (docs/OPERATIONS.md "Failure domains & recovery"): a
NaN token quarantines its slot; an exception out of a tick quarantines
every in-flight slot; quarantined requests replay from the queue front
carrying their already-generated tokens (token-exact under greedy),
bounded by --max-replays before a clean 503; a crashed engine thread
is restarted by the loop supervisor with backoff before /healthz goes
red — re-placing weights on the CURRENT healthy mesh, never the
boot-time one; a tick stuck past --tick-wedge-ms is ESCALATED by the
supervisor to a hard engine restart through the same bounded path
(the wedged thread is superseded and aborts at its next seam — the
PR-4 tick_in_flight_ms wedge *signal* finally has an actor). The
PROCESS domain (ISSUE 14) sits above them all: with --journal-dir
set, every accepted request is journaled (tpushare.durable WAL:
ACCEPT -> per-tick TOKENS batches -> DONE/CANCEL/FAILED), and a
kill -9'd daemon restarts, replays the journal, and finishes every
accepted stream token-exact through the same fold-watermark replay
path — recovered requests keep their tier and their deadline clocks.
A SHARDED engine adds the MESH domain (ISSUE 13): a
chip-health event or an XlaRuntimeError out of a sharded dispatch
triggers degrade-and-replay (models/reshard) — every in-flight
request replays token-exact onto the largest healthy sub-mesh,
bounded by --max-reshards before the replica goes drained-sticky;
recovery grows the full mesh back at the next idle tick. The
tpushare.chaos injector exercises every one of these paths
deterministically (--chaos-spec / TPUSHARE_CHAOS).

No reference analog (SURVEY.md §2: the reference schedules workloads
but contains none); this is the workload the plugin schedules.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import queue
import signal
import sys
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple

from tpushare.chaos import (ENV_CHAOS, InjectedFault, Injector,
                            InjectedXlaRuntimeError)
from tpushare.durable import journal as durable_journal
# jax-free by design (tpushare/slo): the SLO policy layer must be
# importable by the router's device-runtime-free process, and every
# decision it makes for the engine is host arithmetic — tiering adds
# zero device syncs to the tick (test_sync_free pins it).
from tpushare.slo import (DEFAULT_TIER, KvQuota, TickScheduler,
                          TierStats, choose_victim, parse_tier,
                          tier_rank)
from tpushare.utils import ownership as _ownership

# Machine-readable cross-class ownership contracts (read by
# tpushare/analysis/threads.py alongside the inline
# `# tpushare: owner[...]` declarations). The engine/supervisor pair
# is SERIALIZED, not concurrent: the supervisor only touches
# engine-owned state after _join_or_watchdog observes the engine
# thread dead (or abandons a wedged generation whose zombie aborts at
# its next generation-check seam) — a happens-before edge, so its
# writes to owned fields are sanctioned. KvQuota/TierStats are owned
# by the engine that charges them; their snapshot() methods are the
# one sanctioned cross-thread reader each, held to the one-site
# atomic-copy discipline by TO902.
TPUSHARE_OWNERSHIP = {
    "owners": {"KvQuota.used": "engine"},
    "readers": ["KvQuota.snapshot", "TierStats.snapshot"],
    "serialized": [["engine", "supervisor"]],
}

# Measured break-even for chunked admission (SERVING_TPU.jsonl, r5):
# 256-token chunks ran at 0.49x of whole-admit, 512 at 0.58x, because
# every standalone chunk paid its own full weight stream. The fused
# tick removes the second stream, but per-chunk dispatch overhead
# still argues for chunks of at least this many tokens; the daemon
# clamps smaller values unless --prefill-chunk-force is passed.
PREFILL_CHUNK_FLOOR = 512

#: The engine thread's loop as stages, in the overlapped tick's order;
#: a moment of the thread is in at most one, and what is in none is the
#: remainder against its wall clock. Each is a ``tpushare.engine.<name>``
#: span in a profiler session and a clock in /stats ``engine_thread_ms``.
#:   preamble  chaos points, gang poll, capacity pre-reap
#:   admit     one admission that popped a request (prefill included);
#:             it waits for the device too: the eager pool scatter
#:             returns when the prefill has run, the lookup reads the
#:             prompt back, and the first token is fetched here
#:   finalize  the deferred token fetch: the host waits for the device
#:             (overlapped tick only)
#:   apply     NaN scan, emission, completion, reap
#:   schedule  admission pick, cancelled reap, budget alternation
#:   dispatch  step_async / admit_step; in the serial tick srv.step(), so
#:             there it contains the fetch, as an admit_step that
#:             completes a prompt contains the first token's
#:   plan      the next tick's pick, inside this tick's device window
#:   journal   TOKENS records and the flush policy (journaled engines)
#:   idle      the idle sleep
ENGINE_STAGES = ("preamble", "admit", "finalize", "apply", "schedule",
                 "dispatch", "plan", "journal", "idle")


def _np_dtype(name: str):
    """Resolve a wire dtype name to numpy, falling through to
    ml_dtypes for the accelerator-only names (``bfloat16``,
    ``float8_*``) numpy itself refuses — jax guarantees ml_dtypes is
    importable. Migration payloads carry dtype by NAME so a bf16 pool
    round-trips bit-exact through the block-fetch endpoint."""
    import numpy as np
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


class _EngineSuperseded(Exception):
    """Raised inside a tick whose engine generation was escalated away
    (the wedge watchdog's hard restart): the zombie thread must abort
    WITHOUT touching the slot server or emitting tokens — its requests
    were already quarantined and replayed by the new generation."""


class _Request:
    def __init__(self, prompt, max_tokens: int,
                 eos: Optional[int], adapter: int = -1,
                 tier: str = DEFAULT_TIER, tenant: str = "default"):
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.eos = eos
        self.adapter = adapter
        # Durable identity (ISSUE 14): the request id every response
        # carries (the stream-resume handle), the client's
        # Idempotency-Key (None = no dedupe asked), the original
        # prompt snapshot (self.prompt mutates through fold/replay;
        # the journal's ACCEPT and the key-reuse check need the
        # admission-time truth), and whether this request's ACCEPT
        # already hit the journal (replays and recovered requests
        # must never re-ACCEPT).
        self.request_id = uuid.uuid4().hex
        self.idem_key: Optional[str] = None
        self.prompt0 = list(prompt)
        self.journaled = False
        self._terminal_cb = None        # engine-installed journal hook
        # SLO identity (ISSUE 9): the priority tier the scheduler
        # orders by and the tenant the KV-block quota charges. Both
        # survive preemption and quarantine/replay — the request
        # object is the same across re-admissions, so the deadline
        # clock (t_submit) and the tier contract ride through.
        self.tier = tier
        self.tenant = tenant
        self.t_submit = time.monotonic()
        # When the admission that first PLACED the request began (a
        # held or re-queued pop does not stamp it; a replay keeps the
        # first life's): t_submit..t_admit is queue wait, t_admit..
        # t_first the admission itself.
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None    # first pushed token
        self.t_last: Optional[float] = None     # newest pushed token
        self.tokens: List[int] = []
        self.cached_prefix = 0
        self.error: Optional[str] = None
        self.status = 503               # error class when error is set
        self.cancelled = False          # set by a timed-out handler;
        self.done = threading.Event()   # the engine frees the slot
        self.replays = 0                # quarantine re-admissions spent
        # Generated tokens already folded into self.prompt by a
        # replay/preemption re-queue. A second re-queue must fold only
        # tokens[folded:] — re-appending the whole list would
        # duplicate the earlier tokens in the prompt and silently
        # corrupt the continuation (a latent bug in the original
        # preemption path, caught by the chaos fault storm).
        self.folded = 0
        self.seq = 0                    # admit order (preemption victim
                                        # choice: newest loses least)
        # Streaming handlers block on this instead of polling: the
        # engine notifies on every push() and on finish(), so a token
        # reaches the wire with no poll-quantum latency floor and an
        # idle stream costs zero wakeups (VERDICT r4 #5).
        self.cond = threading.Condition()

    def push(self, tok: int) -> None:
        """Engine-side token append + wake streaming waiters."""
        now = time.monotonic()
        if self.t_first is None:
            self.t_first = now          # TTFT clock stops ONCE — a
        self.t_last = now               # replay never restarts it
        self.tokens.append(tok)
        with self.cond:
            self.cond.notify_all()

    def fold_into_prompt(self) -> None:
        """Fold the not-yet-folded generated tokens into the prompt
        for a re-admission (preemption or quarantine replay). The ONE
        home of the fold-watermark arithmetic — two hand-synced
        copies is exactly how the duplicate-prefix corruption this
        fixes crept in."""
        self.prompt = list(self.prompt) + list(self.tokens[self.folded:])
        self.folded = len(self.tokens)

    @property
    def prompt_hash(self) -> str:
        return durable_journal.prompt_hash(self.prompt0)

    def finish(self) -> None:
        """Engine-side terminal transition (done/error/cancel-reaped).
        The terminal callback (journal DONE/CANCEL/FAILED + dedupe-
        window rotation) runs BEFORE done fires — a waiter that wakes
        on done must find the terminal record already appended — and
        exactly once (finish is re-entered on some shutdown paths)."""
        cb, self._terminal_cb = self._terminal_cb, None
        if cb is not None:
            try:
                cb(self)
            except Exception:       # noqa: BLE001 — a degraded journal
                pass                # must never block the completion
        self.done.set()
        with self.cond:
            self.cond.notify_all()


class _PendingTick:
    """One in-flight overlapped dispatch: the PendingStep whose fetch
    is still owed, stamped with the engine generation and tick id it
    was dispatched under so a fault in the overlap window quarantines
    exactly the dispatched tick's slots, plus the slot->request
    identity map at dispatch time with each request's placement stamp
    (``_Request.seq``, new at every admission): a slot recycled while
    the tick was in flight must not receive the old dispatch's token,
    not even where a replay put the SAME request object back into the
    SAME slot. ``dispatch_fetches`` is the device-fetch delta the
    dispatch itself paid (normally zero; the eager monkeypatch
    fallback pays its fetch up front), so /stats fetch accounting
    stays exact either way. ``ahead``: dispatched while an older tick
    was still owed, so some of its rows may belong to streams that
    tick ended."""

    __slots__ = ("step", "engine_gen", "tick_id", "slot_reqs", "seqs",
                 "work", "landed", "dispatch_fetches", "ahead", "retired")

    def __init__(self, step, *, engine_gen, tick_id, slot_reqs, work,
                 dispatch_fetches, ahead=False):
        self.step = step
        self.engine_gen = engine_gen
        self.tick_id = tick_id
        self.slot_reqs = dict(slot_reqs)
        self.seqs = {s: r.seq for s, r in self.slot_reqs.items()}
        self.work = work
        # The slot whose admission this tick's fused chunk completed
        # at dispatch (the slot server activated it there); the engine
        # moves its request to _active only when the tick is applied.
        self.landed = (work if work is not None and work in step.slots
                       else None)
        self.dispatch_fetches = int(dispatch_fetches)
        self.ahead = bool(ahead)
        # {slot: request} capacity-retired rows pre-reaped out of the
        # engine's _active while this tick was in flight (their final
        # tokens are emitted at finalize).
        self.retired: Dict[int, "_Request"] = {}

    def carries(self, slot: int, req: Optional["_Request"]) -> bool:
        """Is the row this tick computed for ``slot`` still owed to
        ``req``, in the placement it was dispatched for?"""
        return (req is not None and self.slot_reqs.get(slot) is req
                and self.seqs[slot] == req.seq)


class ServeEngine:
    """Single-threaded engine loop around the one slot server,
    PagedSlotServer: ``model_family="dense"`` over
    transformer.forward, ``"moe"`` over the SAME block pool via
    moe.paged_forward, ``"latent"`` through its subclass
    LatentSlotServer, ``"retention"`` through RetentionSlotServer (a
    recurrent state a slot where the others keep blocks of keys and
    values: no prefix sharing, the pool a token budget). Every other
    family gets block-granular admission, chain-keyed prefix sharing
    and a real free_blocks pressure signal. Features with no MoE analog — kv_quant, multi-LoRA — are
    rejected loudly rather than silently ignored; int8 EXPERT weights
    ride ``layers_hook``."""

    def __init__(self, params, cfg, *, n_slots: int = 8,
                 n_blocks: int = 256, block_size: int = 16,
                 max_blocks_per_slot: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 kv_quant: bool = False,
                 multi_lora=None, mlora_scale: float = 1.0,
                 temperature: float = 0.0, top_k=None, top_p=None,
                 seed: int = 0, idle_sleep_s: float = 0.005,
                 max_queue: int = 64,
                 prefill_chunk: Optional[int] = None,
                 tick_token_budget: Optional[int] = None,
                 speculative_draft=None, gamma: int = 4,
                 spec_horizon: int = 1,
                 draft_layers_hook=None,
                 model_family: str = "dense",
                 kv: Optional[str] = None,
                 layers_hook=None,
                 chaos_spec: Optional[str] = None,
                 tick_deadline_ms: Optional[float] = None,
                 max_replays: int = 3,
                 max_engine_restarts: int = 3,
                 restart_backoff_s: float = 0.05,
                 mesh=None, param_specs=None, draft_param_specs=None,
                 default_tier: str = DEFAULT_TIER, tier_specs=None,
                 tenant_quotas=None,
                 reshard_checkpoint: Optional[str] = None,
                 max_reshards: int = 3,
                 journal_dir: Optional[str] = None,
                 journal_fsync: str = "tick",
                 dedup_window: int = 1024,
                 tick_wedge_ms: Optional[float] = None,
                 overlap_tick: bool = True,
                 host_kv_bytes: int = 0,
                 num_processes: int = 1,
                 process_index: int = 0,
                 gang=None):
        # mesh: span a jax.sharding Mesh (parallel.serving_mesh builds
        # one over the plugin's TPU_VISIBLE_CHIPS/TPU_PROCESS_BOUNDS
        # sub-mesh grant): tensor-parallel dense, expert x tensor-
        # parallel MoE, KV pools/rows split on the kv-head axis —
        # every tick path (fused, chunked, speculative) runs the same
        # code SPMD, and the sync-free invariant generalizes to one
        # fetch per host per tick. ``param_specs``/``draft_param_specs``
        # override the family default for int8 weight trees
        # (quant.quant_param_specs / quant_moe_param_specs).
        # ``kv`` has one legal layout since PR 30 (the dense-row slot
        # servers are gone); the keyword stays for the callers that
        # still name it.
        if kv not in (None, "paged"):
            raise ValueError(
                f"kv={kv!r}: 'paged' is the one KV layout ('rows' was "
                f"removed in PR 30 with the dense-row slot servers; "
                f"drop kv= or pass kv='paged')")
        # Spec-round granule math vs the tick budget: a speculative
        # round is UNSPLITTABLE — acceptance is decided on device, so
        # one slot's round emits up to gamma×horizon+1 tokens in its
        # tick no matter what the budget says. A budget below that
        # single-slot granule is therefore a self-contradictory
        # config: every spec round would breach the per-tick token
        # bound the budget promises (silently, tick after tick).
        # Rejected loudly instead — and checked BEFORE any server
        # construction: it is pure int arithmetic, and failing after
        # the KV pools and draft pools were already placed on device
        # would tear down a half-built engine over a flag typo.
        if (speculative_draft is not None and tick_token_budget
                and tick_token_budget < gamma * spec_horizon + 1):
            raise ValueError(
                f"tick_token_budget={tick_token_budget} is below the "
                f"speculative round granule gamma*spec_horizon+1 = "
                f"{gamma * spec_horizon + 1}: a spec round cannot be "
                f"split (acceptance is decided on device), so every "
                f"round would emit past this budget and breach the "
                f"per-tick bound it promises. Raise the budget or "
                f"lower --gamma/--spec-horizon")
        # Per-tenant KV-block quotas (tpushare.slo.quota) layer on the
        # paged pool's counters.
        self._kv_quota = KvQuota(tenant_quotas) if tenant_quotas else None
        # The server construction is a FACTORY, not inline: the mesh
        # failure domain (ISSUE 13) rebuilds the slot server on a
        # degraded (or regrown) mesh mid-life, and two hand-synced
        # copies of this kwargs block is exactly how placement
        # contracts drift. The factory closes over every build-time
        # flag; only (params, draft, mesh, kv_quota) vary per rebuild.
        use_prefix = True if prefix_cache is None else prefix_cache
        family_kw: Dict[str, Any] = {}
        if model_family == "retention":
            # Power retention (models/retention.py): a stream's past is
            # one recurrent state, so there are no blocks to share:
            # prefix_cache=None means off here, True is refused by the
            # server by name, as are kv_quant, multi_lora, layers_hook,
            # a draft and a mesh; the host KV tier below refuses itself
            # (it needs the prefix cache). A preempted stream is
            # replayed from its tokens, as wherever blocks are gone.
            from tpushare.models.retention import \
                RetentionSlotServer as server
            use_prefix = bool(prefix_cache)
        elif model_family == "latent":
            # Latent attention with a key selector and windowed layers
            # (models/latent.py): the paged pool, block tables, prefix
            # cache and tick of the dense family, its own two pools and
            # programs underneath. kv_quant, multi_lora, layers_hook,
            # a second model as draft and a mesh are refused there,
            # loudly. A configuration that carries a multi-token-
            # prediction module drafts with it (no flag: the server
            # sets ``speculative`` itself).
            from tpushare.models.latent import LatentSlotServer as server
        elif model_family == "moe":
            from tpushare.models.moe import paged_forward
            from tpushare.models.paged import PagedSlotServer as server
            if kv_quant or multi_lora is not None:
                raise ValueError(
                    "model_family='moe' does not support kv_quant/"
                    "multi_lora (dense-LM features; pass layers_hook="
                    "quant.dequant_hook(cfg) for int8 expert weights)")
            family_kw["forward_fn"] = paged_forward
        elif model_family == "dense":
            from tpushare.models.paged import PagedSlotServer as server
        else:
            raise ValueError(f"unknown model_family {model_family!r}")

        def factory(f_params, f_draft, f_mesh, f_quota):
            return server(
                f_params, cfg, n_slots=n_slots, n_blocks=n_blocks,
                block_size=block_size,
                max_blocks_per_slot=max_blocks_per_slot,
                prefix_cache=use_prefix,
                kv_quant=kv_quant,
                multi_lora=multi_lora, mlora_scale=mlora_scale,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=seed, layers_hook=layers_hook,
                speculative_draft=f_draft, gamma=gamma,
                spec_horizon=spec_horizon,
                draft_layers_hook=draft_layers_hook,
                mesh=f_mesh, param_specs=param_specs,
                draft_param_specs=draft_param_specs,
                kv_quota=f_quota, **family_kw)
        self._server_factory = factory
        # Mesh failure domain (ISSUE 13): the configured mesh is the
        # operator's sized shape; the CURRENT mesh lives on srv (it
        # shrinks on chip loss and grows back on recovery). Chip
        # health is engine-side truth, fed by POST /mesh/chip (the
        # plugin's per-chip churn hook), /undrain (all-healthy), the
        # mesh.chip_failure chaos point, and classified dispatch
        # failures. The ParamStore is built BEFORE placement, off the
        # unplaced trees: a dead chip takes its weight shards with
        # it, so rebuilds must come from host (or disk) copies.
        self._mesh_configured = mesh
        self._max_reshards = max(0, int(max_reshards))
        self._degraded = False
        self._mesh_fault: Optional[str] = None
        self._chip_health = ([True] * mesh.size
                             if mesh is not None else None)
        self._reshard_ms: List[float] = []
        self._draft_cfg = (speculative_draft[1]
                           if speculative_draft is not None else None)
        self._tenant_quotas = tenant_quotas
        self._param_store = None
        if mesh is not None:
            from tpushare.models.reshard import ParamStore
            self._param_store = ParamStore(
                params,
                (speculative_draft[0] if speculative_draft is not None
                 else None),
                path=reshard_checkpoint)
        elif reshard_checkpoint is not None:
            raise ValueError(
                "reshard_checkpoint is a mesh feature (the reshard "
                "path rebuilds weights after chip loss); pass mesh= "
                "or drop it")
        # Process axis (ISSUE 19): a multi-process mesh partitions its
        # flat device list into num_processes contiguous ranks — on a
        # real multi-host slice every process runs this same engine
        # SPMD (gang env -> multihost.initialize -> serving_mesh); on
        # the CPU CI lane one process carries a forced process view so
        # host-loss recovery exercises the identical
        # rank->device-range->shrink path. HOST health rides the
        # existing chip-health machinery: a dead host is its whole
        # device range going unhealthy at once.
        if num_processes < 1:
            raise ValueError("num_processes must be >= 1")
        if num_processes > 1 and mesh is None:
            raise ValueError(
                "num_processes > 1 is a mesh feature (the process "
                "axis partitions a mesh's devices); pass mesh=")
        self._topo = None
        if mesh is not None and num_processes > 1:
            # num_processes == 1 stays topo-less on purpose: a
            # single-process sharded engine has no host domain to
            # churn (null-not-zero in /stats, 400 on /mesh/host).
            from tpushare.parallel.multihost import ProcessTopology
            if mesh.size % int(num_processes) != 0:
                raise ValueError(
                    f"mesh of {mesh.size} devices does not divide "
                    f"into {num_processes} processes")
            self._topo = ProcessTopology(
                num_processes=int(num_processes),
                process_index=int(process_index),
                local_device_count=mesh.size // int(num_processes))
        self._host_health = ([True] * int(num_processes)
                             if self._topo is not None else None)
        # Gang liaison (parallel.gang.GangLeader): rank 0 owns the
        # heartbeat verdicts; followers just drip. poll()ed in the
        # tick preamble so host loss is detected on the engine thread
        # with bounded lag (one heartbeat timeout + one tick).
        self._gang = gang
        if gang is not None and (self._topo is None
                                 or self._topo.num_processes < 2):
            raise ValueError(
                "a gang liaison needs num_processes >= 2 on a mesh")
        self.srv = factory(params, speculative_draft, mesh,
                           self._kv_quota)
        self.model_family = model_family
        self.kv = "paged"
        # Bounded queue: a request flood gets an immediate 429 instead
        # of an unbounded queue + one parked handler thread per request.
        self._max_queue = max(1, max_queue)
        self._pending: "queue.Queue[_Request]" = queue.Queue(
            maxsize=self._max_queue)
        # Tier-aware admission order (ISSUE 9): the intake queue above
        # stays a flat FIFO (handlers only enqueue); the engine drains
        # it into the scheduler's per-tier queues, which decide who
        # admits next — weighted fairness across tiers, strict
        # priority when an interactive deadline is at risk. Intake is
        # BOUNDED (scheduler backlog stops draining at max_queue, so
        # the flood backstop stays the Queue's 429 — accepted-not-
        # admitted work never exceeds 2x max_queue). The old ordered
        # `_held` list lives on as push_front into the request's OWN
        # tier (pool-pressure re-admits, preempted victims and
        # quarantine replays keep their place in-tier while the tier
        # rotation still ranks across tiers).
        self._sched = TickScheduler(tier_specs, default_tier)
        self._tier_stats = TierStats(self._sched.specs)
        # Quota-ceiling holds wait OUT of the tier rotation (only
        # their own tenant's refunds can cure them; at a tier front
        # they would head-of-line-block every other tenant) —
        # engine-thread-owned, re-queued by _unpark_tenant.
        self._quota_parked: List[_Request] = []     # tpushare: owner[engine]
        self._active: Dict[int, _Request] = {}      # tpushare: owner[engine]
        # Chunked prefill (vLLM-style): a long prompt's admission is
        # split into block-aligned chunks FUSED into the decode batch
        # (srv.step(prefill_work=...): one model forward serves both),
        # so one 32k admit cannot stall every in-flight stream for its
        # whole prefill AND no tick pays a second weight stream for
        # the chunk. None = whole-prompt admits.
        self._prefill_chunk = prefill_chunk
        # Per-tick token budget (decode rows + fused chunk tokens):
        # bounds fused-tick latency. 0/None = unbounded (full chunk).
        # When the budget leaves no room for even one chunk granule
        # beside the decode batch, the engine alternates decode-only
        # and admission-only ticks so neither side starves.
        self._tick_token_budget = int(tick_token_budget or 0)
        # Positions a stream runs through the weights in a plain tick: a
        # latent server that drafts with its own module verifies two
        # (the last token and the draft), and its round cannot be split
        # either, so the budget counts both and must hold one round.
        self._stream_positions = 2 if getattr(self.srv, "drafting",
                                              False) else 1
        if 0 < self._tick_token_budget < self._stream_positions:
            raise ValueError(
                f"tick_token_budget={tick_token_budget} is below the two "
                f"positions a stream's self-drafting round verifies "
                f"(the configuration carries a multi-token-prediction "
                f"module); a round cannot be split: raise the budget")
        self._admit_turn = False
        self._chunk_gran = getattr(self.srv.cache, "block_size", 1)
        self._admitting: Dict[int, _Request] = {}   # tpushare: owner[engine]
        self._idle_sleep_s = idle_sleep_s
        # what one request may ask for: never under the 4,096 it always
        # was, and as much as a slot holds where slots are longer
        self.max_tokens_cap = max(4096, getattr(self.srv, "slot_capacity", 0))
        self._seq = 0
        self._stats = {"requests": 0, "completed": 0, "rejected": 0,
                       "preempted": 0, "chunked_admits": 0, "steps": 0,
                       "fused_ticks": 0, "model_forwards": 0,
                       "work_ticks": 0, "device_fetches": 0,
                       # Work ticks dispatched while an older tick's
                       # fetch was still owed, and the rows such a
                       # tick computed for a request that no longer
                       # held the slot when they came home (a stream
                       # the older tick ended): never emitted.
                       "ahead_ticks": 0, "ahead_dropped_tokens": 0,
                       "tokens_out": 0, "slot_rounds": 0,
                       "engine_errors": 0, "last_error": None,
                       "quarantines": 0, "replays": 0,
                       "engine_restarts": 0, "deadline_breaches": 0,
                       "evict_errors": 0,
                       # Mesh failure domain (ISSUE 13): shrink-and-
                       # replay events, grow-backs, and the in-flight
                       # requests each reshard replayed.
                       "reshards": 0, "grow_backs": 0,
                       "replayed_on_reshard": 0,
                       # Host failure domain (ISSUE 19): whole-host
                       # (process rank) losses and rejoins, from the
                       # gang liaison, POST /mesh/host, or host.loss
                       # chaos.
                       "host_losses": 0, "host_rejoins": 0,
                       # Process failure domain (ISSUE 14): journal-
                       # recovered replays at boot, idempotency-key
                       # dedupe hits, mid-generation stream resumes,
                       # and wedge-watchdog hard restarts.
                       "recovered_requests": 0, "dedup_hits": 0,
                       "resumed_streams": 0, "wedge_escalations": 0,
                       # The client's first-token time split on the
                       # engine's own clock: submit -> the admission
                       # that placed the request (queue wait), and
                       # that -> its first token (whole prompt) or
                       # completing chunk (chunked). Once a request,
                       # at its first life.
                       "queue_wait_ms_sum": 0.0, "queue_wait_n": 0,
                       "admit_ms_sum": 0.0, "admit_n": 0,
                       # Monotonic engine-loop iterations (idle ticks
                       # included): the router's liveness-of-the-loop
                       # signal — a wedged engine's ticks stop
                       # climbing while work_ticks alone could just
                       # mean "idle".
                       "ticks": 0}
        self._engine_t0 = time.monotonic()
        # The engine thread's loop cut into stages (ENGINE_STAGES): a
        # span each when someone traces, a cumulative clock each
        # always. /stats engine_thread_ms / engine_thread_n.
        from tpushare.utils.profiling import StageClock
        self._clock = StageClock("engine", ENGINE_STAGES)
        # Typed transient-pressure exception (lazy-bound like every
        # other jax-adjacent import in this module): the admission and
        # preemption paths catch EXACTLY this — any other runtime
        # error is a device/engine failure and must reach the
        # quarantine path, never be mistaken for pool pressure.
        from tpushare.models.paged import (PoolExhausted,
                                           QuotaExceeded,
                                           SlotCapacityExceeded)
        self._pool_exhausted = PoolExhausted
        self._quota_exceeded = QuotaExceeded
        self._slot_cap_exceeded = SlotCapacityExceeded
        # Fault injection (tpushare.chaos): fault points resolve ONCE
        # here — an unarmed point is the shared no-op, so a chaos-free
        # deployment pays one no-op call per point per tick and
        # nothing else.
        if chaos_spec is None:
            chaos_spec = os.environ.get(ENV_CHAOS, "")
        self._chaos = Injector.from_spec(chaos_spec,
                                         deadline_ms=tick_deadline_ms)
        self._fault_forward = self._chaos.point("engine.tick.forward")
        self._fault_token_fetch = self._chaos.point("engine.token_fetch")
        self._fault_admit = self._chaos.point("engine.admit")
        self._fault_chip = self._chaos.point("mesh.chip_failure")
        self._fault_kill = self._chaos.point("process.kill")
        self._fault_host = self._chaos.point("host.loss")
        # Host KV offload tier (ISSUE 18): cold paged blocks demote
        # to host RAM under this byte budget instead of being
        # destroyed, admissions promote tier-resident chains back
        # (prefetched in the overlap window) instead of recomputing
        # them, and sibling replicas land migrated chains here via
        # POST /kv/migrate. 0 = no tier (exactly the pre-r18 engine).
        self._host_tier = None
        if host_kv_bytes:
            if not use_prefix:
                raise ValueError(
                    "host_kv_bytes needs prefix_cache: demoted "
                    "blocks are keyed (and promoted) by their chain "
                    "digests, which only the prefix cache computes")
            if mesh is not None:
                raise ValueError(
                    "host_kv_bytes does not compose with mesh "
                    "sharding yet (a sharded pool's block rows are "
                    "split across devices; the host copy/restore "
                    "contract here is single-device — documented "
                    "seam, like kv_quant-on-mesh)")
            from tpushare.models.kvtier import HostKvTier
            self._host_tier = HostKvTier(int(host_kv_bytes),
                                         quota=self._kv_quota)
            self._host_tier.fault_demote = self._chaos.point("kv.demote")
            self._host_tier.fault_promote = \
                self._chaos.point("kv.promote")
            self.srv.cache.host_tier = self._host_tier
        # Overlap-window prefetch failures (best-effort by contract —
        # the admission pays its own upload instead): counted, never
        # raised past the tick. tpushare: owner[engine]
        self._prefetch_errors = 0
        # Per-tick deadline (ms): a tick running longer counts a
        # breach (the hang-detection signal operators alert on).
        self._tick_deadline_ms = tick_deadline_ms or None
        # Bounded recovery: per-request replay budget, engine-thread
        # restart budget, supervisor backoff base.
        self._max_replays = max(0, int(max_replays))
        self._max_engine_restarts = max(0, int(max_engine_restarts))
        self._restart_backoff_s = restart_backoff_s
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._drain_sticky = False      # shutdown drain: no undrain
        # Request popped from the queue but not yet placed into
        # _active/_admitting/_held: drain()'s idle check must see it,
        # or a SIGTERM landing mid-prefill would let drain() declare
        # idle and stop() would 503 an accepted request. _pop_lock
        # makes the pop->_popped handoff atomic against that check.
        self._popped: Optional[_Request] = None     # tpushare: lock[_pop_lock]
        self._pop_lock = threading.Lock()
        self._tick_started: Optional[float] = None  # in-flight tick t0
        # -- process failure domain (ISSUE 14) ------------------------
        # The durable request registry: every HTTP-submitted request
        # by id (the resume handle), the Idempotency-Key -> id map
        # (the dedupe window), and a bounded FIFO of completed ids so
        # the window never grows without bound. Handler threads and
        # the engine both touch these — every mutation holds
        # _durable_lock.
        self._durable_lock = threading.Lock()
        self._requests: Dict[str, _Request] = {}    # tpushare: lock[_durable_lock]
        self._dedup: Dict[str, str] = {}            # tpushare: lock[_durable_lock]
        self._dedup_window = max(8, int(dedup_window))
        self._completed_order = collections.deque()  # tpushare: lock[_durable_lock]
        # Journal (engine-thread-owned batching; appends are locked
        # inside the Journal so terminal records from shutdown paths
        # on other threads stay safe). _jrnl_tick batches this tick's
        # per-request emissions into ONE TOKENS record each, written
        # at tick end off the tick's one existing device fetch.
        self._journal: Optional[durable_journal.Journal] = None
        self._jrnl_tick: Dict[_Request, List[int]] = {}  # tpushare: owner[engine]
        self._jrnl_open = 0             # journaled, not yet terminal
        self._jrnl_dirty = False        # real records since checkpoint
        if journal_dir:
            recovered = durable_journal.scan(journal_dir)
            self._journal = durable_journal.Journal(
                journal_dir, fsync=journal_fsync,
                fault_write=self._chaos.point("journal.write"),
                fault_fsync=self._chaos.point("journal.fsync"))
            self._recover_journal(recovered)
        # Wedge watchdog (ISSUE 14): the engine GENERATION the current
        # loop thread belongs to. The supervisor escalates a tick
        # stuck past tick_wedge_ms by bumping the generation — the
        # wedged thread aborts at its next seam instead of ever
        # touching the (already quarantined-and-replayed) state again.
        self._tick_wedge_ms = tick_wedge_ms or None
        # Overlapped tick pipeline (ISSUE 17, 33): the engine keeps up
        # to TWO ticks in flight. With tick N's fetch still owed a
        # pass dispatches tick N+1 first and only then fetches N, so
        # the device has its next program queued before a token comes
        # home and the host's whole pass (fetch latency, emission,
        # scheduling, the launch) hides behind it. _pending_ticks
        # holds the dispatches whose fetch is owed, oldest first
        # (empty = pipeline empty; one between passes, two only inside
        # a pass); every abandon path counts a pipeline_flush a tick.
        # Engine-thread-owned, like _active.
        self._overlap_tick = bool(overlap_tick)
        self._pending_ticks: collections.deque = collections.deque()  # tpushare: owner[engine]
        self._pipeline_flushes = 0
        # Host-gap ring (overlap mode only): wall-clock from the end
        # of one dispatch to the end of the next, less the wait inside
        # the finalize between them: the host's own part of a tick.
        # Bounded like the tier-stats rings.
        self._host_gap_ms: List[float] = []     # tpushare: owner[engine]
        self._gap_anchor: Optional[float] = None
        self._gap_waited = 0.0          # s inside finalize since anchor
        self._dispatch_seq = 0          # tick-generation stamp source
        # Next-tick pick plan, precomputed in the overlap window off a
        # quota-ledger snapshot (pure host work; committed or
        # recomputed at the next schedule stage).
        self._next_pick_plan = None
        self._engine_gen = 0
        self._thread = threading.Thread(target=self._loop, args=(0,),
                                        daemon=True)
        # The loop supervisor owns the engine thread's lifecycle: it
        # (re)starts _loop with backoff when a lethal error kills the
        # thread (today a dead thread was only detected by /healthz,
        # never restarted) and gives up — /healthz goes red — after
        # max_engine_restarts.
        self._supervisor = threading.Thread(target=self._supervise,
                                            daemon=True)
        self._started = False
        # Opt-in runtime counterpart of the static TO901 contract
        # (TPUSHARE_OWNERSHIP_CHECKS=1; the chaos storm and SLO smoke
        # arm it): declared-owner fields assert their writer thread.
        # install() is a no-op when the env var is off — no subclass
        # swap, no container wrapper, nothing on the tick path.
        _ownership.install(self, "engine",
                           ("_quota_parked", "_active", "_admitting",
                            "_jrnl_tick"))
        _ownership.install(self._tier_stats, "engine",
                           ("_c", "_ttft", "_per_tok"))
        if self._kv_quota is not None:
            _ownership.install(self._kv_quota, "engine", ("used",))

    def _adopt_ownership(self) -> None:
        """Bind the engine-owned state to the calling thread: the loop
        thread at its top, the supervisor after joining a dead engine,
        stop() after joining the supervisor — the same serialized
        handover TPUSHARE_OWNERSHIP declares statically."""
        _ownership.adopt(self)
        _ownership.adopt(self._tier_stats)
        if self._kv_quota is not None:
            _ownership.adopt(self._kv_quota)

    # -- client side -------------------------------------------------
    def submit(self, req: _Request) -> bool:
        """Enqueue; False when the queue is full (caller answers 429).
        A draining engine refuses new work with a 503 (clients retry
        another replica) while everything already accepted — queued,
        held, admitting, active — still runs to completion."""
        if self._draining.is_set():
            req.error = "server draining; retry another replica"
            req.status = 503
            req.finish()
            return True
        try:
            self._pending.put_nowait(req)
        except queue.Full:
            return False
        if self._stop.is_set():
            # Check-then-enqueue race against shutdown: _stop is set
            # BEFORE stop()'s final queue drain, so seeing it here
            # means our enqueue may have landed after the last drain —
            # no engine will ever serve this queue again. Fail the
            # stragglers ourselves or their handlers would sit on
            # done.wait() until the HTTP timeout (and server_close's
            # handler join would block that long too).
            while True:
                try:
                    r = self._pending.get_nowait()
                except queue.Empty:
                    break
                r.error = "server shutting down"
                r.finish()
        return True

    # -- durable requests (ISSUE 14) ---------------------------------
    def register_or_attach(self, req: "_Request"
                           ) -> Tuple["_Request", bool, bool]:
        """Register a fresh HTTP request — or, when its
        Idempotency-Key already names one, RE-ATTACH to it. Returns
        (request-to-serve, attached, conflict): ``attached`` means the
        caller must serve the returned (live or completed) request and
        NOT submit; ``conflict`` means the key was reused with a
        different prompt (a client bug — 409, never a silent
        re-attach). Atomic under the durable lock, so two concurrent
        retries with the same key admit exactly one request."""
        with self._durable_lock:
            if req.idem_key is not None:
                rid = self._dedup.get(req.idem_key)
                if rid is not None:
                    existing = self._requests.get(rid)
                    # A CANCELLED request is not a result: exactly-
                    # once binds completions, so a retry after a
                    # client-side abandon re-executes (once) — the
                    # key rebinds to the fresh request below instead
                    # of returning a truncated token list as a 200.
                    if existing is not None and not existing.cancelled:
                        if existing.prompt_hash != req.prompt_hash:
                            return req, False, True
                        self._stats["dedup_hits"] += 1
                        return existing, True, False
                self._dedup[req.idem_key] = req.request_id
            self._requests[req.request_id] = req
            req._terminal_cb = self._request_terminal
        return req, False, False

    def deregister(self, req: "_Request") -> None:
        """Undo a registration whose submit never landed (queue-full
        429): the key must not pin a request that will never run."""
        with self._durable_lock:
            self._requests.pop(req.request_id, None)
            if req.idem_key is not None and \
                    self._dedup.get(req.idem_key) == req.request_id:
                del self._dedup[req.idem_key]
        req._terminal_cb = None

    def request_by_id(self, request_id: str) -> Optional["_Request"]:
        """The stream-resume lookup (GET /v1/completions/{id})."""
        with self._durable_lock:
            return self._requests.get(request_id)

    def note_resumed(self) -> None:
        self._stats["resumed_streams"] += 1

    def _request_terminal(self, req: "_Request") -> None:
        """req.finish() hook: append the terminal journal record and
        rotate the request into the bounded completed window. Runs on
        whatever thread finishes the request (engine, supervisor,
        shutdown) — the journal locks internally, the window under
        the durable lock."""
        if self._journal is not None and req.journaled:
            if req.cancelled and req.error is None:
                rec = {"k": "CANCEL", "id": req.request_id}
            elif req.error is not None:
                rec = {"k": "FAILED", "id": req.request_id,
                       "err": req.error, "status": req.status}
            else:
                rec = {"k": "DONE", "id": req.request_id,
                       "n": len(req.tokens)}
            self._journal.append(rec)
            self._jrnl_dirty = True
            with self._durable_lock:
                self._jrnl_open = max(0, self._jrnl_open - 1)
        self._retain_completed(req)

    def _retain_completed(self, req: "_Request") -> None:
        """Keep the finished request inside the dedupe/resume window;
        evict the oldest completed entries past the bound (live
        requests are never evicted — they hold slots)."""
        with self._durable_lock:
            if req.request_id not in self._requests:
                return                  # never registered (direct
            self._completed_order.append(req.request_id)  # submits)
            if (req.error is not None or req.cancelled) \
                    and req.idem_key is not None \
                    and self._dedup.get(req.idem_key) == req.request_id:
                # A FAILED or CANCELLED terminal is not a result to
                # dedupe-return: the request never completed, so a
                # retry SHOULD re-execute (once) — exactly-once binds
                # completions, not refusals or abandons. The request
                # itself stays resumable by id.
                del self._dedup[req.idem_key]
            while len(self._completed_order) > self._dedup_window:
                old = self._completed_order.popleft()
                dead = self._requests.pop(old, None)
                if dead is not None and dead.idem_key is not None \
                        and self._dedup.get(dead.idem_key) == old:
                    del self._dedup[dead.idem_key]

    def _journal_accept(self, req: "_Request") -> None:
        """ACCEPT — written when the engine first drains the request
        into its tier queue (the accepted-durably point; a crash
        before this leaves the client's retry to re-execute from
        scratch, which is still exactly-once because nothing ran)."""
        if self._journal is None or req.journaled:
            return
        req.journaled = True
        self._journal.append({
            "k": "ACCEPT", "id": req.request_id, "key": req.idem_key,
            "ph": req.prompt_hash, "prompt": req.prompt0,
            "tier": req.tier, "tenant": req.tenant,
            "mt": req.max_tokens, "eos": req.eos,
            "adapter": req.adapter})
        self._jrnl_dirty = True
        with self._durable_lock:
            self._jrnl_open += 1
            # HTTP requests registered in register_or_attach already;
            # direct submits (tests, smoke drivers) register here so
            # recovery and resume see every journaled request.
            if req.request_id not in self._requests:
                self._requests[req.request_id] = req
                req._terminal_cb = self._request_terminal
                if req.idem_key is not None:
                    self._dedup.setdefault(req.idem_key, req.request_id)

    def _note_emission(self, req: "_Request", tok: int) -> None:
        """Batch this tick's emissions for ONE TOKENS record per
        request at tick end — journaling must ride the tick's
        existing host work, never add per-token writes."""
        if self._journal is not None and req.journaled:
            self._jrnl_tick.setdefault(req, []).append(tok)

    def _journal_tick_end(self) -> None:
        """Tick epilogue: flush the batched TOKENS records, apply the
        fsync policy, and checkpoint-truncate on quiescence (re-
        seeding the completed window's records so the dedupe contract
        survives the truncation)."""
        if self._journal is None:
            return
        batches, self._jrnl_tick = self._jrnl_tick, {}
        for req, toks in batches.items():
            self._journal.append({
                "k": "TOKENS", "id": req.request_id,
                "s": len(req.tokens) - len(toks), "t": toks})
            self._jrnl_dirty = True
        if self._overlap_tick:
            # The fsync rides the overlap window: _journal_tick_end
            # runs post-dispatch (the _loop_once epilogue), so the
            # flusher thread's fsync overlaps the in-flight device
            # work instead of stretching the host gap. Same crash
            # class: at most the one unflushed tick's TOKENS — a torn
            # tail replay already tolerates.
            self._journal.tick_flush_async()
        else:
            self._journal.tick_flush()
        # Quiescence = nothing open ANYWHERE: journaled-not-terminal,
        # in flight (including an unfetched overlapped dispatch), OR
        # still queued (a tier-queued request's ACCEPT is already in
        # the journal — truncating under it would orphan its later
        # TOKENS records).
        if self._jrnl_dirty and self._jrnl_open == 0 \
                and not self._active and not self._admitting \
                and not self._sched.backlog() \
                and not self._quota_parked and self._pending.empty() \
                and not self._pending_ticks:
            self._journal_checkpoint()

    def _journal_checkpoint(self) -> None:
        """Quiescent checkpoint-truncate + window re-seed: the journal
        shrinks to exactly the dedupe window's completed requests (a
        post-restart retry of ANY windowed request still returns its
        completed result instead of re-executing)."""
        if not self._journal.checkpoint(self._jrnl_open):
            return
        with self._durable_lock:
            window = [self._requests[rid]
                      for rid in self._completed_order
                      if rid in self._requests]
        for req in window:
            self._journal.append({
                "k": "ACCEPT", "id": req.request_id,
                "key": req.idem_key, "ph": req.prompt_hash,
                "prompt": req.prompt0, "tier": req.tier,
                "tenant": req.tenant, "mt": req.max_tokens,
                "eos": req.eos, "adapter": req.adapter})
            if req.tokens:
                self._journal.append({
                    "k": "TOKENS", "id": req.request_id, "s": 0,
                    "t": list(req.tokens)})
            if req.cancelled and req.error is None:
                self._journal.append({"k": "CANCEL",
                                      "id": req.request_id})
            elif req.error is not None:
                self._journal.append({
                    "k": "FAILED", "id": req.request_id,
                    "err": req.error, "status": req.status})
            else:
                self._journal.append({"k": "DONE",
                                      "id": req.request_id,
                                      "n": len(req.tokens)})
        self._journal.tick_flush()
        self._jrnl_dirty = False

    def _recover_journal(self, recovered) -> None:
        """Boot-time recovery (constructor; no engine thread exists
        yet): rebuild the dedupe/resume window from completed
        requests and re-enter every unfinished one at the FRONT of
        its tier — carrying its already-generated tokens through the
        existing fold-watermark replay path, so the restarted daemon
        finishes every accepted stream token-exact under greedy."""
        reentrant: List[_Request] = []
        for rr in recovered.values():
            try:
                tier = parse_tier(rr.tier, self._sched.default_tier,
                                  specs=self._sched.specs)
            except ValueError:
                tier = self._sched.default_tier
            req = _Request(list(rr.prompt), rr.max_tokens, rr.eos,
                           rr.adapter, tier=tier, tenant=rr.tenant)
            req.request_id = rr.request_id
            req.idem_key = rr.idempotency_key
            req.prompt0 = list(rr.prompt)
            req.tokens = list(rr.tokens)
            req.journaled = True
            with self._durable_lock:
                self._requests[req.request_id] = req
                if req.idem_key and rr.status not in ("failed",
                                                      "cancelled"):
                    # failed/cancelled: exactly-once binds
                    # completions — a retry re-executes (once).
                    self._dedup[req.idem_key] = req.request_id
            if rr.status == "open":
                # Crash after the final token but before DONE: the
                # stream is complete — close it now rather than
                # re-admitting a finished request for one extra token.
                finished = (len(req.tokens) >= req.max_tokens
                            or (req.eos is not None and req.tokens
                                and req.tokens[-1] == req.eos))
                self._stats["recovered_requests"] += 1
                req._terminal_cb = self._request_terminal
                # EVERY open request counts — including the finished
                # one, whose finish() below decrements it right back.
                # Counting only the re-entrant ones would let the
                # finished branch's decrement drive the counter to
                # zero WHILE others are still open, and a premature
                # quiescence checkpoint would truncate their records.
                with self._durable_lock:
                    self._jrnl_open += 1
                if finished:
                    req.finish()
                else:
                    req.fold_into_prompt()
                    reentrant.append(req)
                continue
            # Terminal in the journal: rebuild the completed window
            # entry exactly (NO terminal re-journal — the record is
            # already durable).
            if rr.status == "cancelled":
                req.cancelled = True
            elif rr.status == "failed":
                req.error = rr.error or "failed"
                req.status = rr.error_status
            req.done.set()
            self._retain_completed(req)
        # Front of their tiers, original acceptance order preserved
        # (push_front stacks, so push in reverse).
        for req in reversed(reentrant):
            self._sched.push_front(req)

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Stop accepting new requests and wait for accepted work to
        finish — the tenant-side half of the plugin's preemption story
        (SIGTERM -> drain -> exit 0 instead of killing mid-request).
        Returns True when the engine went idle within the timeout."""
        self._drain_sticky = True       # shutdown drains never undrain
        self._draining.set()
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            # _pop_lock makes the queue-pop + _popped handoff atomic
            # against this check: without it the engine could sit
            # between get_nowait() and the _popped assignment while
            # every container reads empty.
            with self._pop_lock:
                idle = (not self._active and not self._admitting
                        and not self._sched.backlog()
                        and not self._quota_parked
                        and self._popped is None
                        and self._pending.empty()
                        and not self._pending_ticks)
            if idle:
                return True
            time.sleep(0.05)
        return False

    def begin_drain(self) -> None:
        """Non-blocking half of drain(): refuse new work immediately,
        let everything already accepted run to completion. The
        plugin's device-health churn hook (POST /drain) calls this
        when a co-located chip goes unhealthy, so in-flight streams
        finish while the scheduler stops routing new work here."""
        self._draining.set()

    def end_drain(self) -> bool:
        """Undo a churn-initiated drain (POST /undrain — the plugin's
        chip-RECOVERED hook): the chip came back, so the replica must
        rejoin service instead of 503ing forever behind a green
        /healthz. Refuses (returns False) when the drain is sticky — a
        SIGTERM/shutdown drain must never be cancelled by a
        concurrently recovering chip."""
        if self._stop.is_set() or self._drain_sticky:
            return False
        if self._chip_health is not None:
            # The plugin's undrain hook fires only once EVERY chip is
            # healthy again (plugin.set_chip_health's all-healthy
            # gate), so undrain doubles as the all-clear for the mesh
            # domain: mark every device healthy and let the engine
            # grow back to the configured mesh at its next idle tick.
            self._chip_health[:] = [True] * len(self._chip_health)
            if self._host_health is not None:
                self._host_health[:] = [True] * len(self._host_health)
            self._mesh_fault = None
        self._draining.clear()
        return True

    def chip_event(self, device: int, healthy: bool) -> Dict[str, Any]:
        """One device of the engine's mesh changed health (POST
        /mesh/chip — the plugin's per-chip churn hook, an operator, or
        a test). The MESH failure domain (ISSUE 13): an unhealthy chip
        flags a mesh fault the engine thread picks up at its next tick
        — quarantine + token-exact replay of every in-flight request,
        re-carve the largest healthy sub-mesh, rebuild weights/pools
        there (degrade-and-replay) — instead of draining the whole
        replica. A recovered chip marks its device healthy; grow-back
        to the configured mesh happens at the next idle tick once ALL
        devices are healthy. UNSHARDED engines have no mesh domain:
        chip loss keeps the PR-4 behavior (drain the daemon), and
        recovery undrains."""
        if self._mesh_configured is None:
            if healthy:
                self.end_drain()
            else:
                self.begin_drain()
            return {"mesh": None, "draining": self._draining.is_set(),
                    "state": self.state()}
        device = int(device)
        n = self._mesh_configured.size
        if not (0 <= device < n):
            raise ValueError(f"device {device} out of range for the "
                             f"configured {n}-device mesh")
        was = self._chip_health[device]
        self._chip_health[device] = bool(healthy)
        if not healthy:
            # Flag a mesh fault only when the SERVING mesh actually
            # uses this device: a re-POSTed event for a chip already
            # resharded around, or the death of a healthy-but-idle
            # chip outside the degraded mesh, must not burn the
            # bounded reshard budget on a shape-identical rebuild
            # (the health mask alone records it — grow-back already
            # requires every chip healthy).
            if self._device_in_serving_mesh(device, default=was):
                self._mesh_fault = f"chip {device} reported unhealthy"
        elif self._mesh_fault is not None:
            # A flap (unhealthy-then-healthy between ticks) must not
            # quarantine-and-rebuild a mesh that is whole again: the
            # fault stands only while some dead device is still in
            # the serving mesh.
            if not any(not h and self._device_in_serving_mesh(i)
                       for i, h in enumerate(self._chip_health)):
                self._mesh_fault = None
        return {"mesh": True, "device": device, "healthy": bool(healthy),
                "healthy_devices": sum(self._chip_health),
                "configured_devices": n, "degraded": self._degraded,
                "state": self.state()}

    def host_event(self, rank: int, healthy: bool) -> Dict[str, Any]:
        """One whole HOST (process rank) of the engine's mesh changed
        health (gang-liaison heartbeat verdict, POST /mesh/host, the
        host.loss chaos point, or a test). The failure ladder's last
        rung (ISSUE 19): a dead host is its entire device range going
        unhealthy at once, so the existing chip-health machinery
        carries the event — the next tick quarantines, replays
        token-exact, and re-carves the largest healthy sub-mesh
        ACROSS the process boundary. A returning host marks its range
        healthy; grow-back happens at the next idle tick once every
        device (on every host) is healthy."""
        if self._topo is None:
            raise ValueError(
                "host_event needs a process-aware mesh (construct "
                "the engine with mesh= and num_processes=)")
        rank = int(rank)
        if not (0 <= rank < self._topo.num_processes):
            raise ValueError(
                f"rank {rank} out of range for "
                f"{self._topo.num_processes} processes")
        was = self._host_health[rank]
        self._host_health[rank] = bool(healthy)
        if was and not healthy:
            self._stats["host_losses"] += 1
        elif not was and healthy:
            self._stats["host_rejoins"] += 1
        out: Dict[str, Any] = {}
        for dev in self._topo.device_range(rank):
            out = self.chip_event(dev, healthy)
        out = dict(out)
        out.update(rank=rank,
                   healthy_processes=sum(self._host_health),
                   num_processes=self._topo.num_processes)
        return out

    def start(self) -> None:
        self._started = True
        self._supervisor.start()

    def _supervise(self) -> None:
        """Engine-thread supervisor: start _loop, and when a LETHAL
        error kills it (something the per-tick recovery cannot catch),
        quarantine the dead engine's in-flight work — no engine is
        running between generations, so touching srv here is safe —
        and restart with exponential backoff, up to
        max_engine_restarts before giving up (/healthz then goes
        red: this thread's death is the 'restarts exhausted' signal
        healthy() reads)."""
        backoff = self._restart_backoff_s
        while True:
            self._thread.start()
            wedged = self._join_or_watchdog()
            # Engine observed dead (or its wedged generation
            # abandoned): the serialized engine->supervisor handover.
            self._adopt_ownership()
            if self._stop.is_set():
                return
            if wedged:
                self._stats["wedge_escalations"] += 1
            if self._stats["engine_restarts"] >= self._max_engine_restarts:
                self._stats["last_error"] = (
                    f"engine thread died; {self._max_engine_restarts} "
                    f"restarts exhausted")
                # Refuse-new-work BEFORE failing the backlog: with no
                # engine left, a later submit() must 503 immediately —
                # an enqueue into a never-drained queue would park its
                # handler for the full HTTP timeout. Sticky: a dead
                # engine can never be undrained back into service.
                self._drain_sticky = True
                self._draining.set()
                self._fail_all("engine dead (restarts exhausted)")
                return
            self._stats["engine_restarts"] += 1
            try:
                self._quarantine_inflight(
                    "engine tick wedged; hard restart" if wedged
                    else "engine thread restarted")
                self._recover_mesh_after_crash()
            except Exception as e:
                # The supervisor's own recovery work hit the corrupted
                # state that killed the engine: do NOT die silently
                # with the backlog parked — refuse new work (sticky)
                # and fail everything fast, then go red.
                self._stats["last_error"] = f"supervisor recovery: {e}"
                self._drain_sticky = True
                self._draining.set()
                self._fail_all(f"engine dead (recovery failed: {e})")
                return
            if self._stop.wait(backoff):
                return
            backoff *= 2
            self._engine_gen += 1
            self._thread = threading.Thread(
                target=self._loop, args=(self._engine_gen,),
                daemon=True)

    def _join_or_watchdog(self) -> bool:
        """Wait for the engine thread to die — or, with
        --tick-wedge-ms armed, catch it WEDGED first: a tick stuck
        past the bound is escalated to a hard restart (ISSUE 14) by
        bumping the engine generation, which supersedes the stuck
        thread (Python cannot kill a thread, but it can make one
        irrelevant: the zombie aborts at its next superseded seam).
        Before the restart path touches the slot server, the zombie
        is JOINED with a bounded grace — a bounded hang (the chaos
        ``hang`` kind, a slow compile that tripped the bound) exits
        on its own and the quarantine runs with no concurrency; only
        a permanently hung thread (a dead device call that never
        returns) falls through to best-effort after the grace, where
        crash-only recovery (the journal) is the real remedy anyway.
        Returns True when the exit was a wedge escalation. The
        tick_in_flight_ms signal PR 4 shipped finally has an actor."""
        if not self._tick_wedge_ms:
            self._thread.join()
            return False
        poll_s = max(0.01, self._tick_wedge_ms / 4e3)
        while True:
            self._thread.join(timeout=poll_s)
            if not self._thread.is_alive():
                return False
            if self._stop.is_set():
                self._thread.join()
                return False
            t0 = self._tick_started
            if t0 is not None and \
                    (time.monotonic() - t0) * 1e3 > self._tick_wedge_ms:
                self._engine_gen += 1       # supersede the wedged thread
                self._tick_started = None   # its stale t0 must not
                self._stats["last_error"] = (  # re-trip the watchdog
                    f"tick wedged past {self._tick_wedge_ms:g} ms; "
                    f"hard engine restart")
                grace_s = max(5.0, 10.0 * self._tick_wedge_ms / 1e3)
                self._thread.join(timeout=grace_s)
                return True

    def stop(self) -> None:
        self._stop.set()
        if not self._started:               # never started: nothing to
            self._fail_all("server shutting down")  # join, just drain
            self._close_journal()
            return
        self._supervisor.join(timeout=5)
        self._adopt_ownership()
        if self._thread.is_alive() or self._supervisor.is_alive():
            # Engine is wedged mid-step: do NOT touch srv/_active from
            # this thread (two threads mutating the slot server's host
            # state can double-free pool blocks — silent KV reuse).
            # Fail only the queue; active handlers hit their timeout.
            self._drain_pending("server shutting down")
            self._close_journal()
            return
        # Engine is down: fail everything so no handler thread sits on
        # done.wait() until its HTTP timeout. An unfetched overlapped
        # dispatch dies with it (counted — its requests fail below).
        self._flush_pipeline()
        self._fail_all("server shutting down")
        self._close_journal()

    def _close_journal(self) -> None:
        """Flush + close after the final terminal records (a clean
        shutdown's journal replays to an all-terminal state — the
        next boot recovers a dedupe window and zero open requests)."""
        if self._journal is not None:
            batches, self._jrnl_tick = self._jrnl_tick, {}
            for req, toks in batches.items():
                self._journal.append({
                    "k": "TOKENS", "id": req.request_id,
                    "s": len(req.tokens) - len(toks), "t": toks})
            self._journal.close()

    def healthy(self) -> bool:
        """Engine alive, or dead-with-restarts-remaining (the
        supervisor will bring it back — kubelet liveness must not kill
        the pod during a recoverable restart window)."""
        if self._thread.is_alive():
            return True
        return self._supervisor.is_alive() and not self._stop.is_set()

    def ready(self) -> bool:
        """READINESS, distinct from healthy() (liveness): True only
        when the engine is live AND accepting new work. A draining or
        restarting replica is healthy-but-not-ready — the router and
        the k8s readiness probe must stop routing to it while nothing
        kills it mid-drain. The single /healthz bit used to conflate
        the two; /readyz serves this predicate."""
        return self.healthy() and self.state() == "running"

    def prefix_keys(self) -> Dict[str, Any]:
        """Prefix-cache gossip for the front door: the hex chain keys
        this replica's pool currently holds (published OR live — a
        referenced block's chain is just as hittable on a follow-up
        admit as a parked one).

        Reading the index from a handler thread races the engine's
        mutations; the dict is small and insertion-only between
        evictions, so a snapshot retry is enough (a momentarily stale
        gossip only costs one routing hit)."""
        cache = self.srv.cache
        for _ in range(3):
            try:
                keys = [k.hex() for k in list(cache.index)]
                break
            except RuntimeError:        # resized mid-iteration
                continue
        else:
            keys = []
        if self._host_tier is not None:
            # Host-tier chains gossip too (r18): the router may send
            # affinity — and siblings may send migration pulls — for
            # chains only the host tier holds; admission promotes
            # them back on the hit.
            dev = set(keys)
            keys += [k for k in self._host_tier.keys_hex()
                     if k not in dev]
        return {"kv": self.kv, "block_size": cache.block_size,
                "keys": keys}

    def kv_blocks(self, keys_hex: List[str]) -> Dict[str, Any]:
        """Raw KV block payloads by chain digest — the
        replica-to-replica migration SOURCE (GET /kv/blocks). For
        each requested key the host tier serves its copy directly;
        device-resident published blocks are fetched with
        ``jax.device_get`` — a handler-thread read, NEVER the tick
        loop (the sync-free invariant polices step methods, not this
        service endpoint), retried like prefix_keys() because a
        racing tick's donation can consume the pool mid-slice.
        Missing/raced keys are simply OMITTED: a partial response IS
        the gossip-staleness contract — the puller lands whatever
        contiguous prefix it got and recomputes the rest, so a
        sibling that evicted a chain mid-migration costs a clean
        miss, never corrupt KV."""
        import base64

        import numpy as np
        from tpushare.models.paged import read_block
        out: Dict[str, Any] = {}
        for kh in keys_hex:
            try:
                key = bytes.fromhex(kh)
            except ValueError:
                continue
            data = (self._host_tier.get(key)
                    if self._host_tier is not None else None)
            if data is None:
                for _ in range(3):
                    cache = self.srv.cache
                    blk = cache.index.get(key)
                    if blk is None:
                        break
                    try:
                        import jax
                        data = jax.device_get(read_block(cache, blk))
                        break
                    except Exception:   # donated mid-read: retry
                        data = None
            if data is None:
                continue
            out[kh] = {
                pf: {"dtype": str(arr.dtype),
                     "shape": list(np.shape(arr)),
                     "b64": base64.b64encode(
                         np.ascontiguousarray(arr).tobytes()).decode()}
                for pf, arr in data.items()}
        return {"block_size": self.srv.cache.block_size, "blocks": out}

    def kv_migrate(self, source_url: str, keys_hex: List[str],
                   tenant: Optional[str] = None) -> Dict[str, Any]:
        """Pull published chain blocks from a sibling replica into
        the host tier (POST /kv/migrate — the router instructs this
        on a routable prefix miss instead of letting the chain be
        recomputed). The crossover estimator's ``net`` channel gets
        the first word (bytes-to-move vs tokens-to-prefill at
        measured rates); payloads are validated leaf-by-leaf against
        this engine's OWN pool shapes/dtypes; only a CONTIGUOUS chain
        prefix lands (a hole would break promotion's consecutive
        walk). Every failure — refusal, transport error, stale
        sibling, malformed leaf — degrades to local recompute:
        nothing is lost, nothing corrupt."""
        if self._host_tier is None:
            return {"migrated": 0, "decision": "no_tier"}
        import base64
        import http.client
        import urllib.parse

        import numpy as np
        from tpushare.models.paged import block_nbytes, block_shapes
        cache = self.srv.cache
        shapes = block_shapes(cache)
        fields = list(shapes)
        dtypes = {pf: str(getattr(cache, pf).dtype) for pf in fields}
        block_bytes = block_nbytes(cache)
        est = self._host_tier.estimator
        if est.decide("net", block_bytes * len(keys_hex),
                      cache.block_size * len(keys_hex)) == "recompute":
            return {"migrated": 0, "decision": "recompute",
                    "requested": len(keys_hex)}
        u = urllib.parse.urlsplit(source_url)
        t0 = time.perf_counter()
        try:
            conn = http.client.HTTPConnection(u.hostname, u.port or 80,
                                              timeout=10.0)
            try:
                conn.request("GET",
                             "/kv/blocks?keys=" + ",".join(keys_hex))
                resp = conn.getresponse()
                if resp.status != 200:
                    raise OSError(f"source answered {resp.status}")
                payload = json.loads(resp.read())
            finally:
                conn.close()
        except Exception as e:
            return {"migrated": 0, "decision": "transfer",
                    "requested": len(keys_hex), "error": str(e)}
        dt = time.perf_counter() - t0
        if payload.get("block_size") != cache.block_size:
            return {"migrated": 0, "decision": "transfer",
                    "requested": len(keys_hex),
                    "error": "block_size mismatch"}
        blocks = payload.get("blocks") or {}
        landed, moved = 0, 0
        for kh in keys_hex:
            rec = blocks.get(kh)
            if not isinstance(rec, dict) or set(rec) != set(fields):
                break                       # contiguous prefix only
            data, ok = {}, True
            for pf in fields:
                leaf = rec[pf]
                if (leaf.get("dtype") != dtypes[pf]
                        or tuple(leaf.get("shape") or ())
                        != shapes[pf]):
                    ok = False
                    break
                arr = np.frombuffer(base64.b64decode(leaf["b64"]),
                                    dtype=_np_dtype(leaf["dtype"]))
                data[pf] = arr.reshape(shapes[pf]).copy()
            if not ok:
                break
            try:
                key = bytes.fromhex(kh)
            except ValueError:
                break
            if not self._host_tier.put(key, data, tenant=tenant,
                                       tokens=cache.block_size,
                                       kind="migrate"):
                break
            landed += 1
            moved += sum(int(a.nbytes) for a in data.values())
        if moved:
            est.observe_transfer("net", moved, dt)
        return {"migrated": landed, "decision": "transfer",
                "requested": len(keys_hex)}

    def state(self) -> str:
        """running | draining | restarting | shutting_down | dead — a
        wedged/crashed engine must not report ok just because a
        shutdown was requested. Draining keeps /healthz 200 (liveness
        must not kill a pod mid-drain); readiness is the 503s submit()
        answers. Restarting: the engine thread died and the supervisor
        is bringing it back (still 200)."""
        if self._thread.is_alive():
            if self._stop.is_set():
                return "shutting_down"
            return "draining" if self._draining.is_set() else "running"
        if self._stop.is_set():
            return "shutting_down"
        if self._supervisor.is_alive():
            return "restarting"
        return "dead"

    def _fail_all(self, msg: str, include_pending: bool = True) -> None:
        """Fail in-flight work; with ``include_pending`` also the
        queue/held backlog. The engine-error recovery path passes
        False: queued requests were never touched by the failed step,
        so the recovered engine serves them — failing them raced a
        just-submitted request into the previous request's error (the
        one flake test_engine_survives_step_failure used to catch).
        Shutdown keeps True: no engine will ever serve that queue."""
        for store in (self._active, self._admitting):
            for slot, req in list(store.items()):
                req.error = msg
                req.finish()
                self._safe_evict(slot)
            store.clear()
        if include_pending:
            self._drain_pending(msg)

    def _safe_evict(self, slot: int) -> None:
        """Best-effort evict on a recovery path — but never silent: a
        failed evict leaks blocks, so it is counted and recorded."""
        try:
            self.srv.evict(slot)
        except Exception as e:
            self._stats["evict_errors"] += 1
            self._stats["last_error"] = f"evict({slot}): {e}"

    def _drain_pending(self, msg: str) -> None:
        for req in self._sched.drain() + self._quota_parked:
            req.error = msg
            req.finish()
        self._quota_parked = []
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            req.error = msg
            req.finish()

    def active_count(self) -> int:
        return int(self.srv.active.sum())

    @property
    def default_tier(self) -> str:
        """Tier for requests that name none (--default-tier)."""
        return self._sched.default_tier

    @property
    def tier_specs(self):
        """The tier table THIS engine schedules by (custom
        ``tier_specs`` or the built-in three) — the handler validates
        request tier names against it, so the HTTP vocabulary always
        matches the scheduler's."""
        return self._sched.specs

    def stats(self) -> Dict[str, Any]:
        from tpushare.models.serving import mesh_axes as _mesh_axes
        from tpushare.utils.profiling import \
            gap_percentiles as _gap_percentiles
        srv = self.srv
        jst = (self._journal.stats()
               if self._journal is not None else None)
        clock = self._clock.snapshot()
        out = dict(self._stats)
        out.update({
            "active_slots": self.active_count(),
            "admitting_slots": len(self._admitting),
            "n_slots": srv.cache.n_slots,
            "model_family": self.model_family,
            "kv": self.kv,
            # Router-scoring surface (ISSUE 8): what the front door's
            # least-loaded fallback and /scale advisory read.
            # queue_depth counts accepted-not-yet-admitted work
            # (bounded queue + pressure-held re-admits);
            # admissions_in_flight is the chunked-prefill count
            # (admitting_slots kept as its alias for older readers).
            "queue_depth": (self._pending.qsize() + self._sched.backlog()
                            + len(self._quota_parked)),
            "admissions_in_flight": len(self._admitting),
            # Multi-tenant SLO surface (ISSUE 9): per-tier fairness +
            # deadline counters (the router's shed order and /scale
            # advisory read these), backlog by tier (the live queue
            # pressure per class), the engine's default tier, and the
            # per-tenant KV-block quota ledger (null = unquota'd pool
            # — the same null-not-zero contract as the pool counters).
            "default_tier": self._sched.default_tier,
            "per_tier": self._tier_stats.snapshot(),
            "queue_by_tier": self._sched.backlog_by_tier(),
            # Requests waiting on their own tenant's KV-block refunds
            # (ceiling holds live outside the tier rotation so one
            # over-quota tenant cannot head-of-line-block the rest).
            "quota_parked": len(self._quota_parked),
            "tenants": (self._kv_quota.snapshot()
                        if self._kv_quota is not None else None),
            "uptime_s": round(time.monotonic() - self._engine_t0, 1),
            "prefix_hit_tokens": srv.prefix_hit_tokens,
            "prefix_prompt_tokens": srv.prefix_prompt_tokens,
            # Ticks whose program carried at least one new block id
            # (a slot crossed a block boundary) and the blocks: the
            # slot server's own counters, beside work_ticks.
            "growth_ticks": srv.growth_ticks,
            "blocks_grown": srv.blocks_grown,
            # Target-weight-stream forwards per engine tick that did
            # work: 1.0 is the fused-tick invariant (pre-fusion, a
            # tick advancing an admission beside its decode batch
            # paid 2 — two full weight streams).
            "forwards_per_tick": (
                round(out["model_forwards"] / out["work_ticks"], 3)
                if out["work_ticks"] else None),
            # Mesh observability (ISSUE 7): the sharded engine's
            # placement footprint and the one-fetch-per-host invariant
            # made live. mesh_shape elides 1-sized axes ({} = a
            # 1-device mesh, null = unsharded). device_fetches counts
            # the device->host transfers made INSIDE work ticks
            # (deltas of the server's raw counter around each tick's
            # step/admit dispatch — whole-prompt admissions transfer
            # too but are not tick work), so fetches_per_tick <= 1.0
            # IS the sync-free invariant under sharding: mid-admission
            # chunks fetch nothing, decode and spec ticks fetch
            # exactly once — per host: the token arrays are
            # replicated, so each process gathers from its own
            # addressable shard.
            "mesh_shape": _mesh_axes(getattr(srv, "mesh", None)),
            "num_devices": (srv.mesh.size
                            if getattr(srv, "mesh", None) is not None
                            else 1),
            # Mesh failure domain (ISSUE 13): configured (the
            # operator's sized shape) vs current (shrinks on chip
            # loss, grows back on recovery). mesh_shape above IS
            # mesh_shape_current (kept as the pre-r13 spelling for
            # older readers); ``degraded`` is null for unsharded
            # engines (no mesh domain exists — the same null-not-
            # false contract as the pool counters), and the router
            # scales this replica's capacity by current/configured
            # device count while it is true. reshard_ms is the
            # shrink/grow rebuild latency (last + p99 over the
            # newest 512).
            "mesh_shape_configured": _mesh_axes(self._mesh_configured),
            "mesh_shape_current": _mesh_axes(getattr(srv, "mesh",
                                                     None)),
            "num_devices_configured": (
                self._mesh_configured.size
                if self._mesh_configured is not None else 1),
            "healthy_devices": (sum(self._chip_health)
                                if self._chip_health is not None
                                else None),
            "degraded": (self._degraded
                         if self._mesh_configured is not None
                         else None),
            "reshard_ms": (
                {"last": round(self._reshard_ms[-1], 1),
                 "p99": round(sorted(self._reshard_ms)[
                     min(len(self._reshard_ms) - 1,
                         int(0.99 * len(self._reshard_ms)))], 1)}
                if self._reshard_ms else None),
            "fetches_per_tick": (
                round(out["device_fetches"] / out["work_ticks"], 3)
                if out["work_ticks"] else None),
            # Process axis (ISSUE 19): how the mesh's devices
            # partition into processes (hosts). Null for engines
            # without a process-aware mesh (the null-not-zero
            # contract: a single-process engine has no host failure
            # domain, not a healthy one of size 1). ``gang`` is the
            # liaison's view — null unless a GangLeader is attached
            # (rank 0 of a real gang); per-process fetch counters
            # ride its heartbeats.
            "num_processes": (self._topo.num_processes
                              if self._topo is not None else None),
            "process_index": (self._topo.process_index
                              if self._topo is not None else None),
            "healthy_processes": (sum(self._host_health)
                                  if self._host_health is not None
                                  else None),
            "gang": (
                {"num_processes": self._gang.num_processes,
                 "heartbeat_timeout_s":
                     self._gang.heartbeat_timeout_s,
                 "process_fetches": {
                     str(r): f for r, f in sorted(
                         self._gang.process_fetches().items())}}
                if self._gang is not None else None),
            # Failure-domain recovery surface: chaos_active tells an
            # operator (and the fault-storm CI job) whether the
            # injector is live; the quarantine/replay/restart/breach
            # counters ride in from _stats above.
            "chaos_active": self._chaos.active,
            "chaos_spec": self._chaos.spec_summary(),
            "chaos_fired": (self._chaos.fired_snapshot()
                            if self._chaos.active else None),
            "tick_deadline_ms": self._tick_deadline_ms,
            "tick_wedge_ms": self._tick_wedge_ms,
            # Process failure domain (ISSUE 14): the journal's
            # durability counters — null when journaling is off (the
            # same null-not-zero contract as the pool counters: an
            # unjournaled engine has no durability plane, not an idle
            # one). journal_bytes / journal_fsync_ms ride top-level
            # as the ISSUE-named spellings; the full block nests
            # under "journal". recovered_requests / dedup_hits /
            # resumed_streams come from _stats above (they exist —
            # in-memory — even without a journal).
            "journal": jst,
            "journal_bytes": (jst["journal_bytes"] if jst else None),
            "journal_fsync_ms": (jst["journal_fsync_ms"] if jst
                                 else None),
            # Live wedge signal: how long the CURRENT tick has been
            # running (null between ticks). deadline_breaches only
            # counts after a tick RETURNS — a hung device_get never
            # reaches that accounting, so operators alert on this
            # exceeding the deadline instead.
            "tick_in_flight_ms": (
                round((time.monotonic() - t0) * 1e3, 1)
                if (t0 := self._tick_started) is not None else None),
            # Overlapped tick pipeline (ISSUE 17). Null-not-0 in
            # serial mode: a serial engine has no pipeline to flush
            # and no host gap to hide, not zero of each.
            "overlap_enabled": self._overlap_tick,
            "pipeline_flushes": (self._pipeline_flushes
                                 if self._overlap_tick else None),
            "host_gap_ms": (_gap_percentiles(list(self._host_gap_ms))
                            if self._overlap_tick else None),
            # The engine thread's stage clocks (ENGINE_STAGES),
            # cumulative from the engine's start like every counter:
            # ms spent in and entries of each stage. Their sum against
            # uptime is the thread's un-staged remainder. In serial
            # mode ``dispatch`` contains the fetch and ``finalize``
            # stays 0. ``finalize`` is not the only wait for the
            # device: ``admit`` holds the prefill's (its eager pool
            # scatter and first-token fetch block), so the stages but
            # ``finalize`` and ``idle`` bound the host's work from
            # above.
            "engine_thread_ms": clock["ms"],
            "engine_thread_n": clock["n"],
            # Host KV offload tier (ISSUE 18). Null-not-0 when no
            # tier is configured: an engine without a tier has no
            # offload plane, not an idle one — the router reads null
            # host-tier pressure as neutral, never as empty. The
            # nested crossover block cites every input the
            # transfer-vs-recompute policy used (measured channel
            # rates, cumulative bytes/tokens, decision counts).
            "host_tier": (self._host_tier.snapshot()
                          if self._host_tier is not None else None),
            "host_prefetch_errors": (self._prefetch_errors
                                     if self._host_tier is not None
                                     else None),
        })
        # Pool-GLOBAL under sharding, not per-shard: the pool's
        # block axis is never sharded (only kv heads split over
        # tp), so the host free list counts whole cross-shard
        # blocks and the ROADMAP-2 autoscaler reads true
        # exhaustion whatever the mesh shape.
        n_total = int(srv.cache.pool_k.shape[1])    # static shape
        allocatable = len(srv.cache.free) + len(srv.cache.lru)
        out.update({
            "free_blocks": len(srv.cache.free),
            "reclaimable_blocks": len(srv.cache.lru),
            "live_blocks": srv.cache.live_blocks(),
            # Fraction of the pool an admission could claim right
            # now (free + zero-ref reclaimable over total): the
            # router's pool-pressure signal and the /scale
            # advisory's exhaustion input.
            "pool_free_frac": (round(allocatable / n_total, 3)
                               if n_total else None),
        })
        # What only one family counts (family_stats). The latent
        # family's (models/latent.py): keys the selector saw and kept, latent rows the
        # slots hold by layer kind and those behind every window to
        # come, assignments that reached the held experts. Null for the
        # other families (null-not-zero: they have no selector, window
        # or expert share, not an idle one).
        fam = srv.family_stats() if hasattr(srv, "family_stats") else {}
        out.update({
            "select_keys_seen": fam.get("select_keys_seen"),
            "select_keys_kept": fam.get("select_keys_kept"),
            "latent_rows_live": fam.get("latent_rows_live"),
            "window_rows_dead": fam.get("window_rows_dead"),
            "latent_row_bytes": fam.get("latent_row_bytes"),
            "expert_assign_local": fam.get("expert_assign_local"),
            "expert_tokens": fam.get("expert_tokens"),
            "expert_load": fam.get("expert_load"),
            "expert_load_max": fam.get("expert_load_max"),
            # The retention family's (models/retention.py): bytes of
            # recurrent state the engine holds, those of active slots,
            # those decode and fused ticks read and wrote and how many
            # such ticks ran (counted on the host off the active mask),
            # admission chunks run.
            "retention_state_bytes": fam.get("retention_state_bytes"),
            "retention_state_bytes_live":
                fam.get("retention_state_bytes_live"),
            "retention_state_bytes_moved":
                fam.get("retention_state_bytes_moved"),
            "retention_ticks": fam.get("retention_ticks"),
            "retention_chunks": fam.get("retention_chunks"),
            # A latent server that drafts with its own multi-token-
            # prediction module: rounds run, drafts proposed (one a
            # stream a round) and accepted, tokens those rounds emitted
            # (the seam's counters under the module's names; the
            # ``speculative`` group below has the rates), and the
            # cached latent rows the rounds' attention had to read and
            # the paged-kernel calls that read them (0 where the rounds
            # gather: ops/latent_decode.latent_decode_eligible).
            "mtp_rounds": fam.get("mtp_rounds"),
            "mtp_proposed": fam.get("mtp_proposed"),
            "mtp_accepted": fam.get("mtp_accepted"),
            "mtp_emitted": fam.get("mtp_emitted"),
            "latent_rows_read": fam.get("latent_rows_read"),
            "latent_decode_calls": fam.get("latent_decode_calls"),
        })
        if srv.speculative:
            # Mean tokens per (slot, round) in [1, gamma×horizon+1] is
            # the live acceptance signal: 1.0 = speculation buying
            # nothing, the ceiling = every draft accepted. Normalized
            # per slot-round, NOT per engine step — the step batches
            # all active slots, which would conflate concurrency with
            # acceptance. Slightly conservative on eos-truncated
            # rounds (accepted-then-discarded tokens aren't counted).
            # spec_rounds/spec_accept_rate come from the seam's own
            # counters (models/spec.py): rounds actually run and
            # accepted/proposed draft tokens — the accept rate is the
            # gamma×horizon tuning signal (high rate argues a longer
            # horizon; a rate collapsing with K argues a shorter one).
            rate = srv.spec_accept_rate()
            out["speculative"] = {
                "gamma": srv.gamma,
                "spec_horizon": srv.spec_horizon,
                "spec_rounds": srv.spec_rounds,
                "spec_accept_rate": (round(rate, 3)
                                     if rate is not None else None),
                "mean_tokens_per_round": round(
                    out["tokens_out"] / max(1, out["slot_rounds"]), 3),
            }
        return out

    # -- engine side -------------------------------------------------
    def _intake_locked(self) -> None:
        """Drain the flat intake queue into the scheduler's per-tier
        queues (caller holds _pop_lock: a request must never be in
        neither container while drain()'s idle check looks). Bounded:
        once the scheduler holds max_queue requests the drain stops,
        so under a sustained flood the Queue fills and submit()'s 429
        backstop fires instead of the per-tier deques growing without
        bound (push_front re-admits stay exempt — they were accepted
        long ago)."""
        while self._sched.backlog() < self._max_queue:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                return
            self._stats["requests"] += 1
            # The accepted-durably point: the request enters the
            # engine's own queues, so its ACCEPT must be replayable
            # from here on (re-queues and replays never re-ACCEPT).
            self._journal_accept(req)
            self._sched.push(req)

    def _try_admit(self) -> bool:
        with self._pop_lock:
            self._intake_locked()
            req = self._sched.pop()
            if req is None:
                return False
            # From here until placement the request lives in no
            # container; _popped keeps drain()'s idle check honest
            # across the prefill (handoff atomic under _pop_lock).
            self._popped = req
        with self._clock.stage(
                "admit", rid=req.request_id,
                prompt_tokens=len(req.prompt)) as sp:
            return self._admit_guarded(req, sp)

    def _admit_guarded(self, req: _Request, sp) -> bool:
        """One popped request through admission, with the failure
        domain of a device fault mid-admission around it. ``sp`` is
        the admission's span (it learns ``chunked`` and
        ``cached_tokens`` when they are known)."""
        try:
            if (int(self.srv.active.sum()) + self.srv.admitting_count
                    >= self.srv.cache.n_slots):
                # Slots full. Preempt-low-for-high: a higher-tier
                # arrival evicts the newest STRICTLY lower-tier slot
                # through the token-exact preemption+replay machinery
                # instead of queueing behind it; equal-or-higher
                # occupancy just waits its turn (front of its tier).
                if not self._preempt_one(below_rank=tier_rank(
                        req.tier, self._sched.specs)):
                    self._sched.push_front(req)
                    return False
            return self._admit_popped(req, sp)
        except Exception as e:
            # A device/runtime failure mid-admission (an
            # XlaRuntimeError out of a prefill chunk or the first
            # token fetch, an injected admit fault). The popped
            # request may live in no container — losing it would park
            # its handler until the HTTP timeout — or may have been
            # registered (and its slot activated) before the failure:
            # deregister + evict first, or the replay would leave a
            # permanently-active server slot (or answer the request
            # from two slots at once). Then reap whatever slot the
            # server still holds for it (blocks must not leak).
            self._stats["engine_errors"] += 1
            self._stats["last_error"] = str(e)
            if self._is_mesh_fault(e):
                # A sharded ADMISSION dispatch died (chip loss at
                # prefill time): flag the mesh fault so _tick's
                # admission loop stops and reshards before the
                # replayed request re-pops onto the same broken
                # placement — without this, the drain-as-slots-allow
                # loop would burn the request's whole replay budget
                # inside one tick and the engine would never degrade.
                self._mesh_fault = f"admit mesh fault: {e}"
            for store in (self._active, self._admitting):
                for slot, r in list(store.items()):
                    if r is req:
                        store.pop(slot)
                        self._safe_evict(slot)
            if not req.done.is_set():
                self._replay_or_503(req, f"admit error: {e}")
            self._reap_orphan_slots()
            # The evictions above refunded this tenant's KV-block
            # charges — same contract as completion/preemption/
            # quarantine: a refund unparks, or a ceiling-parked
            # request whose tenant has nothing left in flight waits
            # until shutdown.
            self._unpark_tenant(req.tenant)
            return True
        finally:
            # Under _pop_lock like every other _popped store: a bare
            # clear here could race drain()'s pop-check-idle sequence
            # into reading "nothing in flight" mid-handoff.
            with self._pop_lock:
                self._popped = None

    def _admit_popped(self, req: _Request, sp) -> bool:
        import numpy as np
        from tpushare.utils.profiling import span
        srv = self.srv
        if req.cancelled:               # client gave up while queued
            req.finish()
            return True
        t_admit = time.monotonic()
        chunked = (self._prefill_chunk is not None
                   and len(req.prompt) > self._prefill_chunk)
        self._fault_admit()
        # The prompt goes in as a HOST array: the slot server hashes it
        # for the prefix cache and slices its chunks on the host, and
        # an array uploaded here would be read straight back there, a
        # round trip that waits for every program in flight (two,
        # since the engine runs ahead) and leaves the device idle
        # behind them.
        prompt = np.asarray(req.prompt, np.int32)
        try:
            if chunked:
                slot = srv.admit_start(
                    prompt, adapter=req.adapter,
                    chunk_tokens=self._prefill_chunk,
                    tenant=req.tenant)
            else:
                slot = srv.admit(prompt, adapter=req.adapter,
                                 tenant=req.tenant)
        except ValueError as e:         # permanently invalid (prompt
            req.error = str(e)          # exceeds capacity, bad adapter
            req.status = 400
            self._stats["rejected"] += 1
            req.finish()
            return True
        except self._quota_exceeded as e:
            # Tier-aware quota verdict, caught BEFORE its PoolExhausted
            # parent. "ceiling": the tenant's own burst cap — with none
            # of its work in flight nothing will ever refund it, so
            # answer 429 (the client's quota, not the fleet's
            # capacity); with its work in flight, hold until its own
            # completions refund blocks. "reserve": pool-wide pressure
            # (another tenant's floor) — hold, and let the tier ladder
            # preempt a strictly lower-tier victim to cure it.
            if e.kind == "ceiling":
                mine = any(r.tenant == req.tenant for r in
                           list(self._active.values())
                           + list(self._admitting.values()))
                if not mine:
                    req.error = str(e)
                    req.status = 429
                    self._stats["rejected"] += 1
                    req.finish()
                    return True
                # PARK, don't re-queue: only this tenant's own
                # refunds can cure a ceiling hold, and back at the
                # front of its tier the request would freeze every
                # other tenant's admissions (strict-priority keeps an
                # at-risk head first in every pop, and one held head
                # ends the tick's admission loop). Parked requests
                # leave the rotation entirely and re-enter at their
                # tier front the moment a slot of THIS tenant frees
                # (_unpark_tenant). True: the head moved aside —
                # other requests admit this same tick.
                self._quota_parked.append(req)
                return True
            # "reserve": first rule out the hold that can never be
            # cured — even a fully idle pool still owes the OTHER
            # tenants their full floors, so a fresh need beyond
            # (usable blocks - those floors) is permanent for this
            # deployment's quota table: answer 429 now instead of
            # pinning the admission loop forever (an at-risk
            # interactive head would re-pop every tick and starve
            # every other tenant's admissions).
            need = getattr(e, "need", None)
            usable = self.srv.cache.pool_k.shape[1] - 1
            if (need is not None and need >
                    self._kv_quota.attainable_blocks(req.tenant,
                                                     usable)):
                req.error = (f"{e} (permanent: {need} fresh blocks "
                             f"exceed the pool minus other tenants' "
                             f"reserve floors)")
                req.status = 429
                self._stats["rejected"] += 1
                req.finish()
                return True
            return self._hold_or_preempt(req, reserve_for=req.tenant)
        except self._pool_exhausted as e:
            # Typed transient pressure ONLY (paged.PoolExhausted):
            # a broad RuntimeError catch here used to swallow genuine
            # device failures as "pool pressure" and hold the request
            # forever; those now propagate to _try_admit's
            # quarantine/replay handler.
            if not self.active_count() and not srv.admitting_count:
                # Nothing in flight will ever free blocks: the pool
                # simply cannot hold this prompt — permanent for this
                # deployment size.
                req.error = str(e)
                self._stats["rejected"] += 1
                req.finish()
                return True
            # Transient: pool/slot pressure from in-flight decodes.
            # Hold the request (front of its tier: it keeps its place)
            # and retry next tick — blocks free as generations
            # complete, and a strictly lower-tier victim may be
            # preempted to free them NOW; a 503 here would reject a
            # backlog admittable moments later.
            return self._hold_or_preempt(req)
        # Placed. Queue wait ends where this admission began — once a
        # request: a replay or a preempted victim keeps its first life's.
        if req.t_admit is None:
            req.t_admit = t_admit
            self._stats["queue_wait_ms_sum"] += (
                t_admit - req.t_submit) * 1e3
            self._stats["queue_wait_n"] += 1
        sp.set_metadata(chunked=int(chunked),
                        cached_tokens=int(srv.last_cached_len))
        if chunked:
            req.cached_prefix = srv.last_cached_len
            self._seq += 1
            req.seq = self._seq
            self._admitting[slot] = req
            self._stats["chunked_admits"] += 1
            self._tier_stats.bump(req.tier, "admitted")
            return True
        req.cached_prefix = self.srv.last_cached_len
        self._seq += 1
        req.seq = self._seq
        self._tier_stats.bump(req.tier, "admitted")
        # The token sampled from the prompt's last logits is the first
        # emitted token (it is already the slot's pending last_token).
        with span("slot.admit.first_token"):
            first = int(self.srv.last_token[slot, 0])
        if self._tok_bad(first):
            # NaN logits at prefill (the sampler picked -1): same
            # slot-scoped failure domain as a poisoned decode tick.
            self._active[slot] = req
            self._quarantine_slot(slot, self._active,
                                  "NaN token (poisoned prefill)")
            return True
        self._emit(req, first)
        self._active[slot] = req
        self._maybe_finish(slot, first)
        return True

    def _hold_or_preempt(self, req: "_Request",
                         reserve_for: Optional[str] = None) -> bool:
        """Transient pressure hold, tier-aware: try to free capacity
        NOW by preempting the newest STRICTLY lower-tier victim
        (preempt-low-for-high through the token-exact machinery), then
        park the request at the front of its tier for the next tick.
        Equal-tier pressure just holds — same-tier traffic never
        churns itself. ``reserve_for`` (the held tenant, on a
        reserve-quota verdict) restricts victims to ones whose
        eviction actually raises that tenant's headroom."""
        self._preempt_one(below_rank=tier_rank(req.tier,
                                               self._sched.specs),
                          reserve_for=reserve_for)
        self._sched.push_front(req)
        return False

    def _emit(self, req: "_Request", tok: int) -> None:
        """Engine-side token emission: push + the tier's TTFT
        accounting on the request's FIRST token (replays carry their
        tokens, so their first push happened in an earlier life and
        the clock never restarts)."""
        first = not req.tokens
        req.push(tok)
        self._note_emission(req, tok)
        if first:
            self._tier_stats.record_first_token(
                req.tier, (req.t_first - req.t_submit) * 1e3)
            if req.t_admit is not None:
                self._stats["admit_ms_sum"] += (
                    req.t_first - req.t_admit) * 1e3
                self._stats["admit_n"] += 1

    def _preempt_one(self, below_rank: Optional[int] = None,
                     reserve_for: Optional[str] = None) -> bool:
        """Pool exhausted mid-step (or preempt-low-for-high with
        ``below_rank``): evict ONE victim instead of failing the whole
        batch (the vLLM recompute-preemption move). Victim = lowest
        tier first, newest admit within it (least work lost) — and
        when a quota'd tenant burst past its KV-block ceiling, its
        slots lose first (the burst is exactly what growth-time quota
        charging defers to this point). The victim's prompt is
        extended with the tokens generated so far and requeued at the
        front of its tier, so with prefix caching on the re-prefill is
        mostly cache hits and generation continues where it left off
        (_try_admit appends the re-admit's sampled token — the natural
        next token after the extended prompt)."""
        if not self._active:
            return False
        pool = self._active
        if self._kv_quota is not None:
            tenants = (self.srv.slot_tenants()
                       if hasattr(self.srv, "slot_tenants") else {})
            if reserve_for is not None:
                # Reserve-quota hold: only victims whose eviction
                # raises the held tenant's net headroom are worth
                # churning — the held tenant's own slots (their
                # refund shrinks its need side), or tenants strictly
                # over their own floor (freeing an at-or-under-floor
                # tenant's blocks grows its unmet floor by exactly
                # the freed amount: zero net). No eligible victim =
                # hold without preempting; completions cure it.
                pool = {s: r for s, r in pool.items()
                        if (t := tenants.get(s, r.tenant)) == reserve_for
                        or self._kv_quota.over_floor(t)}
                if not pool:
                    return False
            base = pool
            over = {s: r for s, r in pool.items()
                    if self._kv_quota.over_ceiling(
                        tenants.get(s, r.tenant))}
            if over:
                pool = over
        else:
            base = pool
        slot = choose_victim(pool, below_rank=below_rank,
                             specs=self._sched.specs)
        if slot is None and pool is not base:
            # Widen past the over-ceiling preference, but never past
            # the reserve-eligibility filter: a victim outside it
            # cannot cure the hold that asked for this preemption.
            slot = choose_victim(base, below_rank=below_rank,
                                 specs=self._sched.specs)
        if slot is None:
            return False
        req = self._active.pop(slot)
        self._safe_evict(slot)
        self._stats["preempted"] += 1
        self._tier_stats.bump(req.tier, "preempted")
        self._unpark_tenant(req.tenant)
        if req.cancelled:
            req.finish()
            return True
        req.fold_into_prompt()
        # Front of its tier: a preempted victim's blocks just freed,
        # and its partial work should resume before both
        # never-admitted held requests and its tier's queue.
        self._sched.push_front(req)
        return True

    def _unpark_tenant(self, tenant: str) -> None:
        """A slot of ``tenant`` just freed (completion, preemption,
        quarantine, cancelled reap) and refunded its KV-block charge:
        its ceiling-parked requests re-enter at the front of their
        tiers for the next admission pass (a still-over-ceiling
        retry just parks again — each retry costs one freed slot, so
        there is no spin)."""
        if not self._quota_parked:
            return
        mine = [r for r in self._quota_parked if r.tenant == tenant]
        if not mine:
            return
        self._quota_parked = [r for r in self._quota_parked
                              if r.tenant != tenant]
        for r in reversed(mine):        # reversed: order preserved
            self._sched.push_front(r)   # across the push_front stack

    def _finish_completed(self, req: "_Request") -> None:
        """Terminal SUCCESS transition: the flat counter, the tier's
        completion/latency accounting (cancelled reaps complete the
        slot but measure nothing — an abandoned stream's latency is
        the client's, not the engine's), and the handler wakeup."""
        self._stats["completed"] += 1
        if not req.cancelled and req.t_first is not None:
            self._tier_stats.bump(req.tier, "tokens", len(req.tokens))
            self._tier_stats.record_completion(
                req.tier, len(req.tokens),
                (req.t_last - req.t_first) * 1e3)
        self._unpark_tenant(req.tenant)
        req.finish()

    def _maybe_finish(self, slot: int, tok: int) -> None:
        req = self._active.get(slot)
        if req is None:
            return
        if (req.cancelled
                or (req.eos is not None and tok == req.eos)
                or len(req.tokens) >= req.max_tokens):
            # _safe_evict: a failed evict on the completion path must
            # count a leak, not raise past req.finish() — the request
            # IS complete, and letting the exception reach the
            # quarantine path would replay (and re-answer) it.
            self._safe_evict(slot)
            del self._active[slot]
            self._finish_completed(req)

    def _loop(self, gen: int = 0) -> None:
        self._adopt_ownership()
        while not self._stop.is_set() and gen == self._engine_gen:
            self._loop_once(gen)

    def _check_superseded(self, gen: Optional[int]) -> None:
        """Abort a superseded (wedge-escalated) thread's tick at a
        safe seam — before it can mutate the slot server or emit into
        requests the new generation already replayed."""
        if gen is not None and gen != self._engine_gen:
            raise _EngineSuperseded()

    def _fire_kill_chaos(self) -> None:
        """process.kill chaos point: a fired ``raise`` SIGKILLs this
        process — the crash-recovery storm's deterministic kill -9.
        Nothing is flushed first: the 'crash' leaves exactly what a
        real SIGKILL leaves (whatever already reached the OS)."""
        try:
            self._fault_kill()
        except InjectedFault:
            os.kill(os.getpid(), signal.SIGKILL)

    def _loop_once(self, gen: Optional[int] = None) -> None:
        """One supervised engine iteration: tick, per-tick failure
        recovery, deadline accounting. Split from _loop so tests can
        drive the recovery machinery synchronously."""
        self._fire_kill_chaos()
        t0 = time.monotonic()
        self._stats["ticks"] += 1
        # Published BEFORE the tick runs: a genuinely wedged tick
        # never reaches the post-hoc breach accounting below, so
        # /stats' tick_in_flight_ms (read from this timestamp by the
        # handler thread) is the only live signal of the wedge — and
        # the wedge watchdog's escalation trigger.
        self._tick_started = t0
        try:
            self._tick(gen)
        except _EngineSuperseded:
            # Escalated away mid-wedge: the new generation owns every
            # piece of state now — touch nothing, not even the
            # accounting, and let _loop's generation check exit.
            return
        except Exception as e:              # noqa: BLE001 — the engine
            # must survive anything step()/admit() can raise: the
            # tick is the failure domain, so every in-flight
            # slot's device state is suspect — quarantine them all
            # and REPLAY their requests (token-exact re-admission)
            # instead of 503ing work a transient fault never
            # corrupted. A dead engine thread with a happy
            # /healthz is the one unacceptable state (lethal
            # BaseExceptions escape to the supervisor, which
            # restarts the thread).
            self._stats["engine_errors"] += 1
            self._stats["last_error"] = str(e)
            if self._is_mesh_fault(e):
                # Sharded dispatch death / flagged chip loss: the
                # MESH is the failure domain — degrade-and-replay
                # (quarantine rides inside) instead of replaying onto
                # the same broken placement until replays exhaust.
                self._reshard(f"mesh fault: {e}")
            else:
                self._quarantine_inflight(f"engine error: {e}")
        finally:
            if gen is None or gen == self._engine_gen:
                # A superseded thread must not clobber the NEW
                # generation's in-flight timestamp or flush its
                # half-batched journal records.
                self._tick_started = None
                if self._journal is not None:
                    with self._clock.stage("journal"):
                        self._journal_tick_end()
            if self._tick_deadline_ms is not None:
                dt_ms = (time.monotonic() - t0) * 1e3
                if dt_ms > self._tick_deadline_ms:
                    self._stats["deadline_breaches"] += 1

    # -- mesh failure domain (ISSUE 13) --------------------------------
    def _device_in_serving_mesh(self, device: int,
                                default: bool = False) -> bool:
        """Does the CURRENT serving mesh use configured-mesh device
        ``device``? ``default`` answers when the server has no mesh to
        inspect (never for a sharded engine in practice)."""
        cur = getattr(self.srv, "mesh", None)
        if cur is None:
            return default
        conf = list(self._mesh_configured.devices.flat)
        return conf[device] in set(cur.devices.flat)

    def _is_mesh_fault(self, e: BaseException) -> bool:
        """Classify a tick failure: on a SHARDED engine, a flagged
        chip-health event or an XlaRuntimeError-shaped dispatch death
        is a MESH fault (the device state is gone, not just this
        batch's) and routes to degrade-and-replay; everything else
        keeps the PR-4 tick domain (quarantine + replay on the same
        server). Unsharded engines have no mesh domain."""
        if self._mesh_configured is None:
            return False
        if self._mesh_fault is not None:
            return True
        return (isinstance(e, InjectedXlaRuntimeError)
                or any(c.__name__ == "XlaRuntimeError"
                       for c in type(e).__mro__))

    def _fire_chip_chaos(self) -> None:
        """mesh.chip_failure chaos point (sharded engines only): a
        fired ``raise`` flips the highest-indexed still-healthy chip
        unhealthy — set_chip_health semantics at the engine's seam —
        and re-raises so THIS tick's dispatch dies with the
        XlaRuntimeError-shaped fault (_loop_once classifies it as a
        mesh fault and reshards). Never kills the LAST healthy chip:
        the injector models partial chip loss — total loss is the
        drain path, driven directly via chip_event."""
        try:
            self._fault_chip()
        except InjectedXlaRuntimeError:
            healthy = [i for i, h in enumerate(self._chip_health) if h]
            if len(healthy) <= 1:
                return
            victim = healthy[-1]
            self._chip_health[victim] = False
            self._mesh_fault = f"chip {victim} unhealthy (chaos)"
            raise

    def _fire_host_chaos(self) -> None:
        """host.loss chaos point (process-aware engines only): a
        fired ``raise`` takes one whole host dark. With a gang
        liaison attached the injection is heartbeat-SILENCE
        (gang.sever) — the loss must be *detected* by the liaison's
        timeout path, exactly as a kernel panic on a real host; a
        liaison-less engine applies the process-kill flavor directly
        (host_event). Never the engine's own rank, and never the last
        healthy host — total loss is the drain path."""
        if self._topo is None or self._topo.num_processes < 2:
            return
        try:
            self._fault_host()
        except InjectedXlaRuntimeError:
            own = self._topo.process_index
            live = [r for r in range(self._topo.num_processes)
                    if self._host_health[r] and r != own]
            if self._gang is not None:
                # Heartbeat-silence flavor needs a rank the liaison
                # has SEEN — only those can age into a detected loss.
                seen = set(self._gang.seen_ranks())
                live = [r for r in live if r in seen]
            if not live or sum(self._host_health) <= 1:
                return
            victim = live[-1]
            if self._gang is not None:
                self._gang.sever(victim)
            else:
                self.host_event(victim, False)

    def _poll_gang(self) -> None:
        """Translate liaison heartbeat verdicts into host events —
        called from the tick preamble so detection lag is bounded by
        one heartbeat timeout plus one tick."""
        if self._gang is None:
            return
        ev = self._gang.poll()
        for rank in ev["lost"]:
            self.host_event(rank, False)
        for rank in ev["rejoined"]:
            self.host_event(rank, True)

    def _reshard(self, reason: str) -> None:
        """Degrade-and-replay — the mesh failure domain's recovery:

        1. snapshot is the EXISTING quarantine path: request state is
           host-resident by construction (host mirrors + each
           request's generated tokens), so every in-flight request
           folds its tokens and replays token-exact; no device state
           survives, and none needs to;
        2. re-carve the largest healthy sub-mesh
           (models/reshard.plan_reshard — MeshPlacement-valid degraded
           specs over a contiguous healthy window);
        3. rebuild weights and pools there from the ParamStore
           (checkpoint or in-memory host copy);
        4. bounded by max_reshards, after which the replica goes
           drained-STICKY and the router sheds it.

        Engine-thread only (called from _loop_once's classifier, the
        _tick preamble, or the supervisor between engine
        generations)."""
        t0 = time.monotonic()
        inflight = len(self._active) + len(self._admitting)
        self._quarantine_inflight(reason)
        self._stats["replayed_on_reshard"] += inflight
        self._mesh_fault = None
        if self._stats["reshards"] >= self._max_reshards:
            self._stats["last_error"] = (
                f"{reason}: {self._max_reshards} reshard budget "
                f"exhausted; replica drained")
            self._drain_sticky = True
            self._draining.set()
            # Fail the backlog fast, like the no-plan branch below:
            # the mesh kept failing past the budget, so re-admitting
            # the just-quarantined requests onto the same broken
            # placement would only churn each one through its replay
            # budget while its handler waits out the HTTP timeout.
            self._fail_all(self._stats["last_error"])
            return
        from tpushare.models.reshard import plan_reshard
        plan = plan_reshard(self._mesh_configured, self._chip_health,
                            self.srv.cfg, self._draft_cfg)
        if plan.mesh is None:
            # Not even a 1x1 spec fits the survivors: nothing can
            # serve here. Drain sticky and fail the backlog fast —
            # parked handlers must not wait out the HTTP timeout.
            self._stats["last_error"] = (
                f"{reason}: no serving shape fits the "
                f"{plan.n_healthy} surviving chip(s); replica drained")
            self._drain_sticky = True
            self._draining.set()
            self._fail_all(self._stats["last_error"])
            return
        if not self._rebuild_on(plan, drain_on_failure=True):
            return
        self._stats["reshards"] += 1
        self._reshard_ms.append((time.monotonic() - t0) * 1e3)
        del self._reshard_ms[:-512]

    def _rebuild_on(self, plan, *, drain_on_failure: bool) -> bool:
        """Rebuild the slot server on plan.mesh from the ParamStore
        (the only mutation of self.srv outside __init__; engine-thread
        owned). The old server — and any shard a dead chip took with
        it — is simply dropped: block tables, free lists and the
        prefix index are host state that starts clean, and the quota
        ledger is rebuilt empty because the new pool owes nobody. A
        failed rebuild either drains the replica sticky (the shrink
        path: a half-built server must never serve) or leaves the old
        server in place (the grow path retries at the next idle
        tick)."""
        try:
            params, draft = self._param_store.load()
            spec_draft = ((draft, self._draft_cfg)
                          if draft is not None else None)
            quota = (KvQuota(self._tenant_quotas)
                     if self._tenant_quotas else None)
            srv = self._server_factory(params, spec_draft, plan.mesh,
                                       quota)
        except Exception as e:
            self._stats["engine_errors"] += 1
            self._stats["last_error"] = f"mesh rebuild failed: {e}"
            if drain_on_failure:
                self._drain_sticky = True
                self._draining.set()
                self._fail_all(self._stats["last_error"])
            return False
        self.srv = srv
        self._kv_quota = quota
        self._degraded = plan.degraded
        # The old pool's ledger died with it: ceiling-parked requests
        # re-enter their tiers (the fresh pool owes nobody, so their
        # next admission verdict is computed against it).
        for r in reversed(self._quota_parked):
            self._sched.push_front(r)
        self._quota_parked = []
        return True

    def _maybe_grow_back(self) -> bool:
        """Idle-tick grow-back: every chip healthy again (undrain or
        per-chip recovery events) and the engine shrunk — rebuild on
        the full configured mesh. Runs only with nothing in flight,
        so there is nothing to replay; a failed grow keeps the
        degraded server serving and retries at the next idle tick."""
        if (self._mesh_configured is None or not self._degraded
                or self._mesh_fault is not None
                or self._draining.is_set()
                or not all(self._chip_health)):
            return False
        from tpushare.models.reshard import plan_reshard
        t0 = time.monotonic()
        plan = plan_reshard(self._mesh_configured, self._chip_health,
                            self.srv.cfg, self._draft_cfg)
        if not self._rebuild_on(plan, drain_on_failure=False):
            return True
        self._stats["grow_backs"] += 1
        self._reshard_ms.append((time.monotonic() - t0) * 1e3)
        del self._reshard_ms[:-512]
        return True

    def _recover_mesh_after_crash(self) -> None:
        """Supervisor x mesh seam: a supervised restart must re-place
        weights on the CURRENT healthy mesh, never the boot-time one.
        The engine thread may have died mid-reshard (fault still
        flagged), or the chip event may have landed while it was down
        — either way, restarting the loop over a server still holding
        shards on a dead chip would crash it straight back into the
        restart budget. Runs between engine generations (no engine
        thread alive), so touching srv here is safe."""
        if self._mesh_configured is None:
            return
        if self._mesh_fault is not None:
            self._reshard(self._mesh_fault)
            return
        if all(self._chip_health):
            return
        conf = list(self._mesh_configured.devices.flat)
        dead = {d for i, d in enumerate(conf)
                if not self._chip_health[i]}
        cur = getattr(self.srv, "mesh", None)
        if cur is not None and dead & set(cur.devices.flat):
            self._reshard("engine restarted over a dead chip")

    # -- failure-domain recovery -------------------------------------
    def _quarantine_inflight(self, msg: str) -> None:
        """Tick-level failure domain: evict EVERY in-flight slot and
        replay its request (the whole batch shared the failed forward,
        so no slot's device state is trustworthy). Replay is
        token-exact: the request re-admits at the queue front with
        prompt + already-generated tokens, and greedy decoding
        continues exactly where it left off.

        Pipeline contract: the in-flight overlapped dispatch is
        flushed FIRST (unfetched) — at a fault the pending tick is
        None by the time slots quarantine, so "in flight" is exactly
        the dispatched tick's slot set, never the next tick's picked
        set."""
        self._flush_pipeline()
        for store in (self._active, self._admitting):
            for slot in list(store):
                self._quarantine_slot(slot, store, msg)
        self._reap_orphan_slots()

    def _quarantine_slot(self, slot: int, store: Dict[int, "_Request"],
                         msg: str) -> None:
        """Slot-level quarantine: evict the slot (its KV is suspect),
        then replay-or-503 its request."""
        req = store.pop(slot)
        self._safe_evict(slot)
        self._stats["quarantines"] += 1
        self._tier_stats.bump(req.tier, "quarantined")
        self._unpark_tenant(req.tenant)
        self._replay_or_503(req, msg)

    def _replay_or_503(self, req: "_Request", msg: str) -> None:
        """Bounded replay: re-queue at the FRONT (held work precedes
        the queue) with the generated tokens folded into the prompt —
        re-admission prefills prompt+prefix, so the continuation is
        bit-identical to the fault-free run under greedy sampling.
        After max_replays quarantines the request 503s cleanly."""
        if req.cancelled:
            req.finish()
            return
        if req.replays >= self._max_replays:
            req.error = (f"{msg} (quarantined; {req.replays} replays "
                         f"exhausted)")
            req.status = 503
            req.finish()
            return
        req.replays += 1
        self._stats["replays"] += 1
        req.fold_into_prompt()
        # Front of its tier: replays carry their tokens and deadline
        # clock — the tier contract survives quarantine (the chaos
        # suite pins exactly this).
        self._sched.push_front(req)

    def _reap_orphan_slots(self) -> None:
        """A failed admission can leave the slot server holding state
        the engine never registered: chunked-admission state (and its
        reserved blocks) from an admit_step that raised mid-chunk, or
        a fully-ACTIVE slot from an admit() that succeeded right
        before a later step of the admission path failed. Reclaim
        both, or each fault leaks a prompt's worth of blocks — and an
        orphaned active slot would consume engine capacity forever."""
        for slot in getattr(self.srv, "admission_slots", []):
            if slot not in self._admitting and slot not in self._active:
                self._safe_evict(slot)
        for slot, on in enumerate(self.srv.active):
            if on and slot not in self._active \
                    and slot not in self._admitting:
                self._safe_evict(int(slot))

    def _tok_bad(self, tok: Any) -> bool:
        """A fetched token that is NaN (poisoned logits argmax), not
        integral, or out of vocabulary marks its slot's tick output as
        garbage — the host-visible signature of a corrupted forward."""
        try:
            ti = int(tok)
        except (TypeError, ValueError, OverflowError):
            return True
        return (tok != tok or ti != tok
                or not (0 <= ti < self.srv.cfg.vocab_size))

    def _reap_cancelled_admissions(self) -> None:
        """Drop cancelled (timed-out) in-flight admissions before any
        pick can spend a tick on them."""
        for slot in list(self._admitting):
            req = self._admitting[slot]
            if req.cancelled:
                del self._admitting[slot]
                self._safe_evict(slot)
                self._unpark_tenant(req.tenant)
                req.finish()

    def _pick_admission_planned(self) -> Optional[int]:
        """The ONE admitting slot this tick advances, reaping
        cancelled admissions on the way; None when no admission is in
        flight. Tier-aware (slo.TickScheduler.pick_admission): an
        at-risk interactive admission always advances, otherwise
        tiers take weighted turns — oldest first within a tier, which
        is exactly the old oldest-first behavior when every admission
        shares one tier. The overlapped tick commits the choice
        precomputed inside its last device window iff the admitting
        set is unchanged (slot+seq identity), else recomputes fresh
        (the serial tick has no plan and always does). Either way the
        committed rotation state matches what a fresh pick_admission
        would have left — the plan only moves the host arithmetic
        into the device window."""
        self._reap_cancelled_admissions()
        admitting = self._open_admissions()
        plan, self._next_pick_plan = self._next_pick_plan, None
        if plan is not None and plan["admitting"] == tuple(sorted(
                (s, r.seq) for s, r in admitting.items())):
            return self._sched.commit_admission(plan["choice"])
        return self._sched.pick_admission(admitting)

    def _landed_admissions(self) -> Dict[int, "_Request"]:
        """Admissions a still-owed fused tick completed at dispatch:
        the slot server decodes them from the next program on, while
        their request stays in ``_admitting`` until that tick is
        applied (its first token starts the stream). A tick dispatched
        in between carries them as decode rows, never as work."""
        return {p.landed: p.slot_reqs[p.landed]
                for p in self._pending_ticks
                if p.landed is not None
                and p.carries(p.landed, self._admitting.get(p.landed))}

    def _open_admissions(self) -> Dict[int, "_Request"]:
        """The admissions that still have chunks to run."""
        landed = self._landed_admissions()
        if not landed:
            return self._admitting
        return {s: r for s, r in self._admitting.items()
                if s not in landed}

    def _plan_next_pick(self) -> None:
        """Precompute the NEXT tick's scheduling decisions inside this
        tick's overlap window — the host work the in-flight dispatch
        hides. Pure reads only: TickScheduler.peek / peek_admission
        and KvQuota.ledger_view never touch a device array, so this
        stage makes ZERO device fetches (pinned by
        test_overlap_tick). The quota-ledger snapshot rides along so
        the pick's admission verdict is rendered against ONE
        consistent ledger; the authoritative charge still lands
        dispatch-side, against the live ledger, when the admission
        actually allocates (slo/quota.py ledger_view)."""
        admitting = self._open_admissions()
        choice = self._sched.peek_admission(admitting)
        quota = getattr(self.srv, "kv_quota", None)
        head = self._sched.peek()
        self._next_pick_plan = {
            "choice": choice,
            "admitting": tuple(sorted(
                (s, r.seq) for s, r in admitting.items())),
            "head": head,
            "ledger": (quota.ledger_view()
                       if quota is not None else None),
        }
        if self._host_tier is not None and head is not None:
            # Host-tier prefetch (ISSUE 18): stage the head request's
            # tier-resident chain blocks on device NOW, so its
            # admission's promotion consumes an upload that already
            # rode this tick's in-flight dispatch. jnp.asarray is
            # host→device — still ZERO device fetches in this stage
            # (the test_overlap_tick/test_sync_free pins both cover
            # it). Best-effort: any failure just means the admission
            # pays its own upload (or recomputes) as before.
            import numpy as np
            try:
                self.srv.prefetch_prefix(
                    np.asarray(head.prompt, np.int32),
                    adapter=getattr(head, "adapter", -1))
            except Exception:
                self._prefetch_errors += 1

    def _complete_admission(self, slot: int, tok: int) -> None:
        """An admission's final chunk ran (fused or serial): its first
        sampled token starts the stream and the slot joins the decode
        batch."""
        req = self._admitting.pop(slot)
        self._emit(req, tok)
        self._active[slot] = req
        self._maybe_finish(slot, tok)

    def _advance_one_admission(self, slot: int,
                               gen: Optional[int] = None) -> None:
        """Serial admission tick (one chunk, its own forward) — the
        no-active-decodes fast path, and the decode-starved half of
        the token-budget alternation. The tick budget caps this chunk
        too (an admission-only tick must not smuggle a full unbounded
        chunk past the latency bound the budget promises)."""
        with self._clock.stage("dispatch"):
            self._fault_forward()   # chaos: this tick's model forward
            self._check_superseded(gen)  # wedge hang fired above: abort
            f0 = self.srv.device_fetches
            tok = self.srv.admit_step(
                slot, max_chunk_tokens=self._tick_token_budget or None)
            self._stats["device_fetches"] += self.srv.device_fetches - f0
            self._stats["model_forwards"] += 1
            self._stats["work_ticks"] += 1
        if tok is None:
            return
        with self._clock.stage("apply"):
            if self._tok_bad(tok):
                self._quarantine_slot(slot, self._admitting,
                                      "NaN token (poisoned prefill)")
                return
            self._complete_admission(slot, tok)

    def _tick(self, gen: Optional[int] = None) -> None:
        if self._overlap_tick:
            self._tick_overlap(gen)
        else:
            self._tick_serial(gen)

    def _tick_serial(self, gen: Optional[int] = None) -> None:
        """The pre-pipeline tick: schedule, dispatch, and fetch in one
        sequential pass. ``--overlap-tick off`` routes here — the
        fallback the overlapped mode must stay bit-exact against."""
        if not self._preamble_and_admit():
            return
        with self._clock.stage("schedule"):
            plan = self._schedule(finalized=False)
        if plan is None or self._run_unbatched(plan, gen):
            return
        _, work, room = plan
        # Fused tick: the admission's next chunk rides the decode
        # batch's forward (exactly one model forward — and still one
        # device->host transfer — per tick). `room` caps the chunk so
        # decode-rows + chunk tokens stay within the tick budget.
        with self._clock.stage("dispatch"):
            self._fault_forward()   # chaos: this tick's model forward
            self._check_superseded(gen)  # wedge hang fired above: abort
            f0 = self.srv.device_fetches
            try:
                out = (self.srv.step(prefill_work=work,
                                     max_chunk_tokens=room)
                       if work is not None else self.srv.step())
            except (self._pool_exhausted, self._slot_cap_exceeded) as e:
                if not self._shed_at_dispatch(e):
                    raise
                return
            self._stats["steps"] += 1
            self._stats["device_fetches"] += self.srv.device_fetches - f0
            self._stats["model_forwards"] += 1
            self._stats["work_ticks"] += 1
            if work is not None:
                self._stats["fused_ticks"] += 1
        with self._clock.stage("apply"):
            self._apply_step_output(out, work)

    def _preamble_and_admit(self) -> bool:
        """The head both ticks share: chaos points and the proactive
        mesh degrade, the overlapped tick's capacity pre-reap, then
        the admission drain (as slots allow). False when a mesh fault
        resharded instead: the tick is over — and the in-flight
        dispatch, as suspect as whatever flagged the fault, was
        dropped unfetched (its answers may straddle the dead shards;
        replay regenerates its tokens)."""
        with self._clock.stage("preamble"):
            if self._mesh_configured is not None:
                self._fire_chip_chaos()
                self._fire_host_chaos()
                self._poll_gang()
                if self._mesh_fault is not None:
                    # A chip- or host-health event landed since the
                    # last tick (POST /mesh/chip, /mesh/host, or a
                    # liaison verdict): degrade proactively, before
                    # any dispatch touches the dead shards.
                    self._flush_pipeline()
                    self._reshard(self._mesh_fault)
                    return False
            # Before the drain can hand a capacity-retired in-flight
            # slot to a new request.
            self._prereap_retired()
        return self._drain_admissions()

    def _drain_admissions(self) -> bool:
        """Admit as slots allow. False when an admission dispatch
        flagged a mesh fault mid-drain: reshard NOW, before another
        pop lands on the broken placement; the replayed requests
        re-admit next tick on the rebuilt mesh."""
        admitted = True
        while admitted and self._mesh_fault is None:
            admitted = self._try_admit()
        if self._mesh_fault is not None:
            self._flush_pipeline()
            self._reshard(self._mesh_fault)
            return False
        return True

    def _schedule(self, finalized: bool):
        """What this tick dispatches, for both ticks. Decided on
        serial-equivalent state (the previous tick fully applied)
        where the fetch came first; where the overlapped tick runs
        ahead, on that state less the owed tick's outcome: a stream it
        ended still rides this dispatch (its row is dropped when it
        comes home), and an admission it completed rides as a decode
        row (``_landed_admissions``).
        ``("admit", slot, None)`` a serial admission chunk with its
        own forward — the no-active-decodes fast path, and the
        decode-starved half of the token-budget alternation;
        ``("step", work, room)`` a decode step, ``work`` the admitting
        slot whose next chunk (capped at ``room`` tokens) rides it;
        ``("idle", None, None)``; or None: nothing this tick.
        ``finalized`` (overlapped tick only) gates the serial
        admission forward: a tick that already paid the finalize fetch
        defers it one tick, keeping the one-fetch-per-tick invariant
        airtight instead of merely average."""
        work = self._pick_admission_planned()
        if not self._active:
            if work is not None:
                return None if finalized else ("admit", work, None)
            if self._admitting or self._maybe_grow_back():
                return None
            return ("idle", None, None)
        # Reap cancelled (timed-out) requests before paying for a step.
        for slot in [s for s, r in self._active.items() if r.cancelled]:
            self._maybe_finish(slot, -1)
        if not self._active:
            return None
        room = None
        if work is not None and self._tick_token_budget:
            room = (self._tick_token_budget
                    - self._stream_positions * len(self._active)
                    - len(self._landed_admissions()))
            if room < self._chunk_gran:
                # No chunk fits beside this decode batch: decode-only
                # and admission-only ticks take turns so neither side
                # starves while per-tick work stays bounded — unless
                # the tier ladder overrides (an at-risk higher-tier
                # admission claims the tick; a lower-tier admission
                # never steals one from higher-tier decode rows).
                choice = self._sched.alternation(self._admitting[work],
                                                 self._active)
                if choice is None:
                    if finalized and self._admit_turn:
                        # Admission's turn, but this tick already paid
                        # the finalize fetch: hold the turn untoggled
                        # and run the chunk next tick (which dispatches
                        # nothing else).
                        return None
                    choice = "admit" if self._admit_turn else "decode"
                    self._admit_turn = not self._admit_turn
                if choice == "admit":
                    # finalized: the at-risk claim stands next tick
                    return None if finalized else ("admit", work, None)
                work, room = None, None
        return ("step", work, room)

    def _run_unbatched(self, plan, gen: Optional[int]) -> bool:
        """The two plans that need no decode batch: a serial admission
        chunk, or the idle sleep. False for a ``step`` plan."""
        kind, work, _ = plan
        if kind == "admit":
            self._advance_one_admission(work, gen)
        elif kind == "idle":
            with self._clock.stage("idle"):
                time.sleep(self._idle_sleep_s)
        return kind != "step"

    def _shed_at_dispatch(self, e: Exception) -> bool:
        """Pool pressure raised host-side at dispatch, before anything
        is in flight; False where it could not be shed and the caller
        re-raises. Typed: any OTHER RuntimeError is a
        device/runtime failure and belongs to the quarantine path in
        _loop_once. PoolExhausted — concurrent decode growth
        (admission does not reserve max_tokens worth of blocks, by
        design: that would waste most of the pool): shed ONE victim
        and retry next tick rather than 503ing every in-flight
        request. SlotCapacityExceeded — ONE slot's block table is
        full, a per-slot ceiling and not a device fault: retire
        exactly that request at its tokens-so-far; preempting or
        quarantining the batch over one sequence's ceiling would
        punish the innocents."""
        if isinstance(e, self._slot_cap_exceeded):
            req = self._active.pop(e.slot, None)
            self._safe_evict(e.slot)
            self._stats["last_error"] = str(e)
            if req is None:
                return False            # not ours: a real engine bug
            self._finish_completed(req)
            return True
        if not self._preempt_one():
            return False
        self._stats["engine_errors"] += 1
        self._stats["last_error"] = f"preempt: {e}"
        return True

    def _apply_step_output(self, out, work: Optional[int],
                           retired=None) -> None:
        """Post-fetch half of a tick: NaN quarantine scan, token
        emission, fused-admission completion, capacity reap. Shared
        verbatim by the serial tick and the overlapped finalize so the
        two modes cannot drift. ``retired``: {slot: request} for rows
        the dispatch retired at capacity whose slot was already handed
        back (overlap pre-reap) — their final tokens are emitted to
        the request directly, exactly where the serial emit loop would
        have."""
        # Token-fetch validation (the NaN failure domain is ONE slot):
        # a NaN/garbage token means that slot's forward produced
        # poisoned logits — quarantine exactly that slot and drop its
        # whole tick output; everyone else's tokens are good. Pure
        # host arithmetic: no extra device transfer on this path.
        poisoned = self._fault_token_fetch(out)
        if poisoned is not None:
            out = poisoned
        bad = [s for s, toks in out.items()
               if any(self._tok_bad(t) for t in
                      (toks if isinstance(toks, list) else [toks]))]
        for s in bad:
            out.pop(s)
            self._stats["last_error"] = f"NaN token from slot {s}"
            if retired and s in retired:
                # Quarantine minus the evict (the pre-reap already
                # returned the slot, and whoever holds it now is not
                # this row's): suspect tokens never reach the stream;
                # the request replays or 503s like any other
                # quarantined row.
                done = retired.pop(s)
                self._stats["quarantines"] += 1
                self._tier_stats.bump(done.tier, "quarantined")
                self._unpark_tenant(done.tenant)
                self._replay_or_503(done, "NaN token (poisoned logits)")
            elif s in self._active:
                self._quarantine_slot(s, self._active,
                                      "NaN token (poisoned logits)")
            elif s in self._admitting:
                self._quarantine_slot(s, self._admitting,
                                      "NaN token (poisoned logits)")
        for slot, toks in out.items():
            done = retired.pop(slot, None) if retired else None
            if done is not None:
                # Capacity-retired mid-flight: this row is the retired
                # stream's whoever holds the slot by now (the drain may
                # have handed it on). Emit its final tokens, then
                # complete it at tokens-so-far — the serial reap's
                # outcome, one stage later.
                self._stats["slot_rounds"] += 1
                for tok in (toks if isinstance(toks, list)
                            else [toks]):
                    self._emit(done, tok)
                    self._stats["tokens_out"] += 1
                self._finish_completed(done)
                continue
            req = self._active.get(slot)
            if req is None:
                continue
            # One (slot, step) emission — the per-slot denominator the
            # speculative acceptance stat divides by (tokens_out/steps
            # would conflate batch concurrency with acceptance).
            self._stats["slot_rounds"] += 1
            # Speculative servers emit a LIST per slot (up to gamma+1
            # accepted tokens); _maybe_finish per token keeps ONE
            # source of truth for the finish predicate — tokens
            # accepted past a mid-block eos are discarded (the slot is
            # evicted; its advanced device lengths are moot).
            for tok in (toks if isinstance(toks, list) else [toks]):
                self._emit(req, tok)
                self._stats["tokens_out"] += 1
                self._maybe_finish(slot, tok)
                if slot not in self._active:
                    break
        # A fused chunk that completed its admission reports the first
        # sampled token under the admitting slot's key.
        if work is not None and work in self._admitting and work in out:
            self._complete_admission(work, out[work])
        # A retired row whose tokens were all dropped (NaN scan) or
        # absent still completes at tokens-so-far, like the serial
        # reap would have.
        if retired:
            for req in retired.values():
                self._finish_completed(req)
        # A slot step() deactivated at capacity without our evict
        # (unless a younger tick still owes that row its last token:
        # the pre-reap of the pass that fetches it completes it):
        for slot in [s for s, r in self._active.items()
                     if not self.srv.active[s]
                     and not any(p.carries(s, r)
                                 for p in self._pending_ticks)]:
            req = self._active.pop(slot)
            self._safe_evict(slot)          # reclaim blocks (counted
            self._finish_completed(req)     # on failure, never raised
                                            # past the finished request

    # -- overlapped tick pipeline (ISSUE 17, 33) ----------------------
    def _tick_overlap(self, gen: Optional[int] = None) -> None:
        """Pipelined tick, up to two dispatches in flight: with tick
        N's fetch still owed, a pass dispatches tick N+1 FIRST and
        only then fetches and applies N. The device has its next
        program queued before N's tokens come home, so the fetch's
        latency, the emission, the scheduling and the launch all ride
        a device window, and the period of a plain tick is the larger
        of the program and the host's pass, not their sum. Still one
        forward and at most one device fetch a pass
        (fetches_per_tick <= 1.0). Stage order:

          1. preamble    — chip chaos + proactive mesh degrade (a mesh
                           fault FLUSHES the pipeline: never fetch
                           from a suspect dispatch)
          2. admit drain — the same pre-dispatch point as the serial
                           tick; a pre-reap first returns any
                           capacity-retired in-flight slots before the
                           drain can hand them to new requests. The
                           slots the last pass's finalize freed are
                           refilled here
          3. schedule    — pure pick: the overlap-window plan is
                           committed when still valid, else recomputed
          4. dispatch    — step_async (N+1), queued behind N on the
                           device; the generation-stamped _PendingTick
                           joins the queue. Then a second admit drain:
                           what arrived meanwhile goes in ahead of the
                           wait below, not a tick behind it
          5. finalize    — the ONE device fetch, of the OLDEST owed
                           tick (N), applied through the exact serial
                           post-step block (NaN scan, emit, fused
                           completion, reap). A row N+1 computed for a
                           stream N ended is dropped when N+1 comes
                           home (the identity guard), never emitted
          6. plan        — the next pass's pick, precomputed

        Where the next dispatch needs this fetch the order is the
        older one, 5 (then a refill drain) before 3 and 4, and one
        tick is in flight (``_runs_ahead``): the same code with the
        older tick finalized first."""
        if not self._pending_ticks:
            self._gap_anchor = None     # no sample across an empty pipe
        if not self._preamble_and_admit():
            return
        ahead = fetched = self._runs_ahead()
        if self._pending_ticks and not ahead:
            q0 = self._stats["quarantines"]
            fetched = self._finalize_pending()
            if fetched and self._stats["quarantines"] == q0:
                # Completions in the finalize freed server slots;
                # refill them NOW, like the serial tick's drain (which
                # runs after the previous tick is fully applied) —
                # otherwise every completion opens a one-tick admission
                # bubble the serial engine does not have. Skipped when
                # the finalize quarantined: a replayed request
                # re-admits at the NEXT tick's drain, keeping the
                # recovery tick itself at the one transfer the
                # sync-free invariant allows.
                if not self._drain_admissions():
                    return
        dispatched = self._schedule_and_dispatch(gen, finalized=fetched,
                                                 ahead=ahead)
        if ahead:
            # The fetch blocks until program N ends. Whatever arrived
            # while N+1 was being dispatched is admitted BEFORE that
            # wait, as the fetch-first order admits it before its own
            # (stage 2): a request that comes in just behind the drain
            # must not sit out a whole tick, which may be a fused one.
            # Not where this dispatch retired a row at capacity: its
            # slot is the retired stream's until the next pass's
            # pre-reap (two ticks may still owe it tokens).
            if (all(self.srv.active[s] for s in self._active)
                    and not self._drain_admissions()):
                return
            self._finalize_pending()
        if dispatched:
            with self._clock.stage("plan"):
                self._plan_next_pick()

    def _runs_ahead(self) -> bool:
        """Does this pass dispatch before it fetches? Decided from what
        the engine can observe, never from a flag. It does not where
        the owed tick's outcome shapes the next dispatch: a speculative
        server (the accepted counts decide the next lengths, so the
        host mirrors cannot advance without the fetch), a server with
        no ``step_async`` or an instance-patched ``step`` (the eager
        branch: its fetch is paid at dispatch), the first tick after a
        flush (nothing owed), and a batch whose every stream is on its
        last token by count (``max_tokens``): a draining engine runs no
        wasted program. A plan that turns out to need the serial
        admission path waits one tick (``_schedule`` with
        ``finalized``), as it always did behind a fetch."""
        if len(self._pending_ticks) != 1 or self._eager_step():
            return False
        if getattr(self.srv, "speculative", False):
            return False
        pend = self._pending_ticks[0]
        live = list(self._active.items())
        live += self._landed_admissions().items()
        return any(not r.cancelled
                   and len(r.tokens) + pend.carries(s, r) < r.max_tokens
                   for s, r in live)

    def _eager_step(self) -> bool:
        """Instance-level step overrides (chaos/unit tests monkeypatch
        eng.srv.step) see exactly the serial call — eagerly, with
        exceptions raising at dispatch — and their output rides the
        pipeline pre-fetched."""
        return ("step" in vars(self.srv)
                or not hasattr(self.srv, "step_async"))

    def _prereap_retired(self) -> None:
        """Dispatch-side capacity retirement (the slot's block
        ceiling) frees the server's slot while its final token is
        still in flight. Move those rows out of ``_active`` — and
        reclaim their server-side state — BEFORE the admission drain
        can hand the slot to a new request; their tokens are emitted
        at finalize from the pending tick's own identity map, so the
        stream still ends exactly where the serial engine's would.
        Runs between passes, where at most one tick is owed: the one
        that retired the row."""
        for pend in self._pending_ticks:
            for slot, req in list(pend.slot_reqs.items()):
                if (pend.carries(slot, self._active.get(slot))
                        and not self.srv.active[slot]):
                    del self._active[slot]
                    self._safe_evict(slot)
                    pend.retired[slot] = req

    def _finalize_pending(self) -> bool:
        """The one deferred device fetch, of the oldest owed tick.
        Slots whose request changed while the tick was in flight
        (ended by the tick before it, preempted, quarantined,
        completed-and-recycled) are invalidated — the generation-
        stamped identity map decides, so a recycled slot can never
        receive the old dispatch's token. Returns True when a pending
        tick was actually fetched (the caller then defers any serial
        admission forward to keep one fetch per tick)."""
        if not self._pending_ticks:
            return False
        pend = self._pending_ticks.popleft()
        if pend.engine_gen != self._engine_gen:
            # Stamped under a previous engine generation: its device
            # work answers for state that was quarantined and replayed
            # — drop it unfetched.
            self._abandon(pend, "engine generation superseded")
            return False
        with self._clock.stage("finalize"):
            stale = frozenset(
                s for s, req in pend.slot_reqs.items()
                if (not pend.carries(s, self._active.get(s))
                    and not pend.carries(s, self._admitting.get(s))
                    and s not in pend.retired))
            f1 = self.srv.device_fetches
            t0 = time.monotonic()
            try:
                out = pend.step.finalize(stale)
            except BaseException:
                # The deferred fetch surfaced the dispatch's device
                # fault. Pre-reaped retired rows live in no store the
                # quarantine sweep can see — replay them here, then
                # let the fault take the normal quarantine path for
                # everyone else (it flushes the younger tick unfetched).
                self._replay_retired(
                    pend, "device fault at pipeline finalize")
                raise
            self._gap_waited += time.monotonic() - t0
        with self._clock.stage("apply"):
            self._stats["steps"] += 1
            if pend.ahead:
                self._stats["ahead_dropped_tokens"] += len(stale)
            # Fetch accounting joins the two halves of the split tick:
            # the dispatch-side delta (zero on the async path; the
            # eager monkeypatch fallback pays there) plus the finalize
            # fetch — admission transfers in between stay excluded,
            # exactly as the serial tick excludes them.
            self._stats["device_fetches"] += (
                pend.dispatch_fetches + (self.srv.device_fetches - f1))
            self._apply_step_output(out, pend.work, retired=pend.retired)
        return True

    def _schedule_and_dispatch(self, gen: Optional[int], *,
                               finalized: bool, ahead: bool) -> bool:
        """Stages 3+4: the pick (_schedule), then the dispatch with
        its fetch left owing. ``finalized``: this pass pays a fetch
        (before or after this call), so a serial admission forward
        waits a tick. True when a tick joined the queue."""
        with self._clock.stage("schedule"):
            plan = self._schedule(finalized)
        if plan is None or self._run_unbatched(plan, gen):
            return False
        _, work, room = plan
        with self._clock.stage("dispatch"):
            self._fault_forward()   # chaos: this tick's model forward
            self._check_superseded(gen)  # wedge hang fired above: abort
            slot_reqs = dict(self._active)
            slot_reqs.update(self._landed_admissions())
            if work is not None:
                slot_reqs[work] = self._admitting[work]
            f0 = self.srv.device_fetches
            try:
                if self._eager_step():
                    from tpushare.models.serving import PendingStep
                    out = (self.srv.step(prefill_work=work,
                                         max_chunk_tokens=room)
                           if work is not None else self.srv.step())
                    pstep = PendingStep.done(out)
                else:
                    pstep = (self.srv.step_async(prefill_work=work,
                                                 max_chunk_tokens=room)
                             if work is not None
                             else self.srv.step_async())
            except (self._pool_exhausted, self._slot_cap_exceeded) as e:
                # These raise host-side at dispatch, so the pipeline
                # holds nothing suspect.
                if not self._shed_at_dispatch(e):
                    raise
                return False
            self._dispatch_seq += 1
            self._pending_ticks.append(_PendingTick(
                pstep, engine_gen=self._engine_gen,
                tick_id=self._dispatch_seq, slot_reqs=slot_reqs,
                work=work, dispatch_fetches=self.srv.device_fetches - f0,
                ahead=ahead))
            self._stats["model_forwards"] += 1
            self._stats["work_ticks"] += 1
            if work is not None:
                self._stats["fused_ticks"] += 1
            if ahead:
                self._stats["ahead_ticks"] += 1
        self._record_host_gap()
        return True

    def _flush_pipeline(self) -> None:
        """Abandon every in-flight dispatch WITHOUT its fetch: its
        tokens are never observed (quarantine replay regenerates them
        token-exactly), so a reshard/quarantine path never blocks on —
        or trusts — a suspect device computation. Counted, a tick, on
        the /stats ``pipeline_flushes`` surface."""
        if not self._pending_ticks:
            return
        self._next_pick_plan = None
        while self._pending_ticks:
            self._abandon(self._pending_ticks.popleft(),
                          "pipeline flushed")

    def _abandon(self, pend: _PendingTick, msg: str) -> None:
        self._pipeline_flushes += 1
        self._replay_retired(pend, msg)

    def _replay_retired(self, pend: _PendingTick, msg: str) -> None:
        """A tick that will never be applied still owes its pre-reaped
        retired rows an ending: they live in no store the quarantine
        sweep can see, so replay them from here."""
        while pend.retired:
            _, req = pend.retired.popitem()
            self._stats["quarantines"] += 1
            self._tier_stats.bump(req.tier, "quarantined")
            self._unpark_tenant(req.tenant)
            self._replay_or_503(req, msg)

    def _record_host_gap(self) -> None:
        """One host-gap sample: host wall-clock between the end of one
        dispatch and the end of the next, less the wait inside the
        ``finalize`` between them (the fetch, where the host only
        waits for the device): whatever else the engine's thread did
        to get from one launch to the next — apply, admissions,
        journal, preamble, schedule and the whole ``dispatch`` stage
        (block growth, the launch, the eager sampler); the stage
        clocks give them apart. No sample spans an empty pipeline
        (``_tick_overlap`` drops the anchor). Plain monotonic deltas
        into a bounded ring (no PhaseTimer — its barriers are the
        syncs the hot loop must never make)."""
        now = time.monotonic()
        anchor, self._gap_anchor = self._gap_anchor, now
        waited, self._gap_waited = self._gap_waited, 0.0
        if anchor is None:
            return
        from tpushare.utils.profiling import HOST_GAP_CAP
        self._host_gap_ms.append(
            max(0.0, now - anchor - waited) * 1e3)
        if len(self._host_gap_ms) > HOST_GAP_CAP:
            del self._host_gap_ms[
                :len(self._host_gap_ms) - HOST_GAP_CAP]


def chip_to_device(chip: int) -> int:
    """Map a plugin chip index (the vocabulary TPU_VISIBLE_CHIPS and
    the health hooks speak) to the engine's mesh device POSITION. The
    grant parse has ONE home — utils/tenant.read_tenant_env (both env
    spellings, err-as-env poison detection) — so libtpu's enumeration
    order (the sorted grant) cannot drift from the tenant contract.
    Without a grant env (tests, bare runs) the identity mapping
    applies; a poisoned err-as-env grant fails loudly."""
    from tpushare.utils.tenant import AllocationError, read_tenant_env
    try:
        granted = sorted(read_tenant_env().chips)
    except AllocationError as e:
        raise ValueError(f"cannot map chip {chip}: poisoned "
                         f"err-as-env grant ({e})")
    if not granted:
        return chip
    try:
        return granted.index(int(chip))
    except ValueError:
        raise ValueError(f"chip {chip} is not in this pod's grant "
                         f"{granted}")


def make_handler(engine: ServeEngine, timeout_s: float):
    from tpushare.utils.profiling import span

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):           # quiet by default
            pass

        def _json(self, code: int, obj) -> None:
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _stream(self, req: _Request, from_n: int = 0,
                    resume: bool = False,
                    can_cancel: Optional[bool] = None) -> None:
            """SSE token stream, event-driven: the engine's push()/
            finish() notify ``req.cond``, so each token flushes the
            moment it exists — no poll quantum under any token and no
            wakeups while the engine computes. Events are written
            OUTSIDE the condition lock (the engine must never block on
            a slow client's socket). A broken pipe (client gone)
            cancels the generation so the slot frees instead of
            decoding to max_tokens for nobody.

            Every token event carries a monotonic ``id:`` line (the
            count of tokens delivered INCLUDING this one) — the
            resume cursor GET /v1/completions/{id} and Last-Event-ID
            speak. ``from_n`` skips the first N tokens, so a resumed
            stream's token events are byte-identical to the
            uninterrupted stream's from that cursor. ``resume``
            streams — and ATTACHED (Idempotency-Key deduped) POST
            streams, via ``can_cancel=False`` — are a read-only view:
            they never cancel the generation (only the original owner
            holds that right; a retry's dropped connection must not
            kill the stream the owner is still consuming), and a
            resume's done event omits cached_prefix (an
            admission-time detail a recovered request cannot
            reproduce)."""
            if can_cancel is None:
                can_cancel = not resume
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("X-Request-Id", req.request_id)
            self.end_headers()          # HTTP/1.0: close-delimited body

            def event(obj, eid: Optional[int] = None) -> None:
                frame = b""
                if eid is not None:
                    frame += b"id: %d\n" % eid
                frame += b"data: " + json.dumps(obj).encode() + b"\n\n"
                with span("http.write", rid=req.request_id):
                    self.wfile.write(frame)
                    self.wfile.flush()

            sent = max(0, int(from_n))
            deadline = time.time() + timeout_s
            try:
                while True:
                    with req.cond:
                        req.cond.wait_for(
                            lambda: len(req.tokens) > sent
                            or req.done.is_set(),
                            timeout=max(0.0, deadline - time.time()))
                    # Sample done BEFORE draining: every push precedes
                    # finish(), so done-then-drain sees all tokens; a
                    # push landing after the drain wakes the next
                    # iteration. (Drain-then-check could break on a
                    # push+finish pair landing between the two.)
                    done = req.done.is_set()
                    toks = req.tokens        # drain outside the lock
                    while sent < len(toks):
                        event({"token": toks[sent]}, eid=sent + 1)
                        sent += 1
                    if done:
                        break
                    if time.time() > deadline:
                        if can_cancel:
                            req.cancelled = True
                        event({"error": "generation timed out"})
                        return
                if req.error:
                    event({"error": req.error})
                elif resume:
                    event({"done": True}, eid=sent)
                else:
                    event({"done": True,
                           "cached_prefix": req.cached_prefix},
                          eid=sent)
            except (BrokenPipeError, ConnectionResetError):
                if can_cancel:
                    req.cancelled = True    # engine reaps the slot

        def do_GET(self):
            if self.path == "/healthz":
                # LIVENESS only: draining/restarting replicas answer
                # ok=True (the supervisor will bring the engine back;
                # killing the pod would turn a recoverable restart
                # into a lost replica). Routability is /readyz.
                ok = engine.healthy()
                self._json(200 if ok else 503,
                           {"ok": ok, "state": engine.state()})
            elif self.path == "/readyz":
                # READINESS: 503 while draining/restarting so the
                # router and the k8s readiness probe stop sending new
                # work — without the liveness probe killing the pod.
                ok = engine.ready()
                self._json(200 if ok else 503,
                           {"ready": ok, "state": engine.state()})
            elif self.path == "/prefixes":
                self._json(200, engine.prefix_keys())
            elif self.path == "/stats":
                self._json(200, engine.stats())
            elif self.path.startswith("/v1/completions/"):
                self._resume_stream()
            elif self.path.startswith("/kv/blocks"):
                # Migration source (r18): serve raw block payloads by
                # chain digest to a pulling sibling. Keys it no longer
                # holds are omitted — partial responses ARE the
                # gossip-staleness contract.
                import urllib.parse as _up
                qs = _up.parse_qs(_up.urlparse(self.path).query)
                keys = [k for k in
                        (qs.get("keys", [""])[0] or "").split(",") if k]
                self._json(200, engine.kv_blocks(keys))
            else:
                self._json(404, {"error": "not found"})

        def _resume_stream(self) -> None:
            """GET /v1/completions/{id}?from=N (r15): re-open a
            request's event stream from cursor N — after a client
            drop, a router failover, or a serve-process death (the
            recovered request keeps its id). ?from= wins; the
            standard Last-Event-ID header is honored otherwise; no
            cursor replays from 0."""
            import urllib.parse as _up
            parsed = _up.urlparse(self.path)
            rid = parsed.path[len("/v1/completions/"):]
            if not rid or "/" in rid:
                self._json(404, {"error": "not found"})
                return
            req = engine.request_by_id(rid)
            if req is None:
                self._json(404, {
                    "error": f"unknown request id {rid!r} (completed "
                             f"requests age out of the dedupe "
                             f"window)"})
                return
            try:
                qs = _up.parse_qs(parsed.query)
                if "from" in qs:
                    from_n = int(qs["from"][0])
                else:
                    from_n = int(self.headers.get("Last-Event-ID", 0))
                if from_n < 0:
                    raise ValueError
            except (ValueError, TypeError):
                self._json(400, {"error": "from/Last-Event-ID must "
                                          "be a non-negative int"})
                return
            engine.note_resumed()
            self._stream(req, from_n=from_n, resume=True)

        def _accept(self, sp):
            """A completion request from its body to the engine's
            queue: (request, stream, attached), or None once an error
            was answered. ``sp`` is the ``http.accept`` span around
            this call; it learns the request's id, the one the engine's
            admit span and every ``http.write`` of the request
            carry."""
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("body must be a JSON object")
                prompt = body["prompt"]
                vocab = engine.srv.cfg.vocab_size
                if (not isinstance(prompt, list) or not prompt
                        or not all(isinstance(t, int)
                                   and 0 <= t < vocab for t in prompt)):
                    raise ValueError(
                        "prompt must be a non-empty list of token ids "
                        f"in [0, {vocab})")
                mt = body.get("max_tokens", 16)
                if (not isinstance(mt, int) or mt < 1
                        or mt > engine.max_tokens_cap):
                    raise ValueError(
                        f"max_tokens must be an int in "
                        f"[1, {engine.max_tokens_cap}]")
                eos = body.get("eos")
                if eos is not None and not isinstance(eos, int):
                    raise ValueError("eos must be an int token id")
                adapter = body.get("adapter", -1)
                if isinstance(adapter, bool) or not isinstance(
                        adapter, int):
                    # bool subclasses int: {"adapter": true} would
                    # silently select adapter 1 — another tenant.
                    raise ValueError("adapter must be an int bank "
                                     "index (-1 = base model)")
                stream = bool(body.get("stream", False))
                # SLO identity: "tier" orders the request against the
                # rest of the traffic (unknown names 400 — a typo'd
                # tier silently landing in the default would be an
                # unasked-for SLO downgrade); "tenant" is the KV-quota
                # accounting principal.
                tier = parse_tier(body.get("tier"),
                                  getattr(engine, "default_tier",
                                          DEFAULT_TIER),
                                  specs=getattr(engine, "tier_specs",
                                                None))
                tenant = body.get("tenant", "default")
                if not isinstance(tenant, str) or not tenant:
                    raise ValueError(
                        "tenant must be a non-empty string")
                req = _Request(prompt, mt, eos, adapter,
                               tier=tier, tenant=tenant)
                req.idem_key = (self.headers.get("Idempotency-Key")
                                or None)
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                self._json(400, {"error": str(e)})
                return None
            # Exactly-once admission (r15): an Idempotency-Key that
            # already names a request RE-ATTACHES to it — live or
            # completed — instead of double-executing; the same key
            # with a different prompt is a 409 (a client bug, not a
            # retry). getattr: test fakes implement only submit().
            reg = getattr(engine, "register_or_attach", None)
            attached = conflict = False
            if reg is not None:
                req, attached, conflict = reg(req)
            if conflict:
                self._json(409, {
                    "error": "Idempotency-Key reuse with a different "
                             "prompt (a retry must resend the same "
                             "request)"})
                return None
            sp.set_metadata(rid=req.request_id)
            if not attached and not engine.submit(req):
                if reg is not None:     # never accepted: the key must
                    engine.deregister(req)  # not pin a request that
                self._json(429, {"error": "queue full, retry later"})
                return None             # will never run
            return req, stream, attached

        def do_POST(self):
            if self.path == "/mesh/chip":
                # Per-chip health churn (the mesh failure domain's
                # front door): {"device": i} names a mesh device
                # position directly; {"chip": c} names a granted chip
                # index (the plugin health hook's vocabulary) and maps
                # through the TPU_VISIBLE_CHIPS grant. Sharded engines
                # degrade/grow; unsharded engines keep the PR-4
                # drain/undrain behavior.
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    if not isinstance(body, dict):
                        raise ValueError("body must be a JSON object")
                    healthy = body.get("healthy", False)
                    if not isinstance(healthy, bool):
                        raise ValueError("healthy must be a bool")
                    if "device" in body:
                        dev = body["device"]
                    elif "chip" in body:
                        chip = body["chip"]
                        if isinstance(chip, bool) or not isinstance(
                                chip, int):
                            raise ValueError("chip must be an int")
                        dev = chip_to_device(chip)
                    else:
                        raise ValueError(
                            "need 'device' (mesh position) or 'chip' "
                            "(granted chip index)")
                    if isinstance(dev, bool) or not isinstance(
                            dev, int):
                        raise ValueError("device must be an int")
                    out = engine.chip_event(dev, healthy)
                except (KeyError, ValueError, TypeError,
                        json.JSONDecodeError) as e:
                    self._json(400, {"error": str(e)})
                    return
                self._json(200, out)
                return
            if self.path == "/mesh/host":
                # Whole-host health churn (the failure ladder's last
                # rung): {"rank": r, "healthy": bool} transitions one
                # process rank's entire device range at once. Only
                # process-aware engines (num_processes on a mesh)
                # accept it — others 400, there is no host domain to
                # churn.
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    if not isinstance(body, dict):
                        raise ValueError("body must be a JSON object")
                    healthy = body.get("healthy", False)
                    if not isinstance(healthy, bool):
                        raise ValueError("healthy must be a bool")
                    rank = body.get("rank")
                    if isinstance(rank, bool) or not isinstance(
                            rank, int):
                        raise ValueError("rank must be an int")
                    out = engine.host_event(rank, healthy)
                except (KeyError, ValueError, TypeError,
                        json.JSONDecodeError) as e:
                    self._json(400, {"error": str(e)})
                    return
                self._json(200, out)
                return
            if self.path == "/undrain":
                ok = engine.end_drain()
                self._json(200 if ok else 409,
                           {"draining": engine._draining.is_set(),
                            "state": engine.state()})
                return
            if self.path == "/drain":
                # Device-health churn, tenant side: the co-located
                # plugin POSTs this when a chip the pod sits on goes
                # unhealthy (plugin/health.serve_drain_hook). New work
                # is refused at submit(); accepted work finishes.
                engine.begin_drain()
                self._json(200, {"draining": True,
                                 "state": engine.state()})
                return
            if self.path == "/kv/migrate":
                # Migration sink (r18): the router instructs this
                # replica to pull a published chain from a sibling
                # into its host tier ahead of the proxied admission.
                # Failures answer 200 with migrated=0 — migration is
                # an optimization; the fallback (local recompute) is
                # the caller's default path either way.
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    if not isinstance(body, dict):
                        raise ValueError("body must be a JSON object")
                    src = body.get("source")
                    keys = body.get("keys")
                    if not isinstance(src, str) or not src:
                        raise ValueError(
                            "source must be a replica base URL")
                    if (not isinstance(keys, list) or not keys
                            or not all(isinstance(k, str)
                                       for k in keys)):
                        raise ValueError(
                            "keys must be a non-empty list of hex "
                            "chain digests")
                    tn = body.get("tenant")
                    if tn is not None and (not isinstance(tn, str)
                                           or not tn):
                        raise ValueError(
                            "tenant must be a non-empty string")
                except (KeyError, ValueError, TypeError,
                        json.JSONDecodeError) as e:
                    self._json(400, {"error": str(e)})
                    return
                self._json(200, engine.kv_migrate(src, keys,
                                                  tenant=tn))
                return
            if self.path != "/v1/completions":
                self._json(404, {"error": "not found"})
                return
            with span("http.accept") as sp:
                accepted = self._accept(sp)
            if accepted is None:
                return
            req, stream, attached = accepted
            if stream:
                # An attached stream is a read-only view: its dropped
                # connection/timeout must never cancel a generation
                # the original owner is still consuming.
                self._stream(req, can_cancel=not attached)
                return
            if not req.done.wait(timeout=timeout_s):
                if not attached:
                    # Tell the engine to free the slot — an abandoned
                    # request must not decode toward max_tokens
                    # forever. An ATTACHED waiter never cancels: the
                    # original owner (or a later resume) may still be
                    # consuming the stream.
                    req.cancelled = True
                self._json(504, {"error": "generation timed out"})
                return
            if req.error:
                self._json(req.status, {"error": req.error,
                                        "id": req.request_id})
                return
            self._json(200, {"id": req.request_id,
                             "tokens": req.tokens,
                             "cached_prefix": req.cached_prefix})
    return Handler


def serve(engine: ServeEngine, host: str = "127.0.0.1", port: int = 8478,
          timeout_s: float = 300.0,
          daemon_threads: bool = True) -> ThreadingHTTPServer:
    """Start the engine + HTTP server; returns the (running) server.
    Caller owns shutdown: server.shutdown(); engine.stop().

    ``daemon_threads=False`` makes handler threads non-daemon so
    ``server_close()`` joins them — the drain path needs this, or the
    process could exit between the engine finishing a request and the
    handler writing its response bytes (client sees a reset for a
    request the server 'completed')."""
    engine.start()
    httpd = ThreadingHTTPServer((host, port),
                                make_handler(engine, timeout_s))
    httpd.daemon_threads = daemon_threads
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


def build_parser() -> argparse.ArgumentParser:
    """The tpushare-serve argv contract — split from main() so the
    deploy-manifest e2e (test_manifests_e2e.py) can parse the
    container command exactly as the daemon would."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--preset", default="tiny",
                    choices=["tiny", "gemma_2b", "llama3_8b"])
    ap.add_argument("--model-family", default="dense",
                    choices=["dense", "moe"],
                    help="moe: serve the MoE LM over the same "
                         "paged pool (moe.paged_forward; --preset "
                         "tiny maps to moe.tiny). Converted Mixtral "
                         "checkpoints serve through the same engine "
                         "via the API (convert.moe_from_hf)")
    ap.add_argument("--int8-experts", action="store_true",
                    help="moe only: serve an int8 quantize_params "
                         "tree (expert weights at half the bf16 "
                         "bytes — the dominant MoE decode stream)")
    ap.add_argument("--int8-expert-hook", choices=["fused", "dequant"],
                    default=None,
                    help="moe + --int8-experts only: 'fused' (default) "
                         "keeps expert weights int8 through to the "
                         "fused dequant×GEMM kernel (ops/q8_expert — "
                         "no materialized wide copy); 'dequant' is "
                         "the legacy per-layer widening hook "
                         "(quant.dequant_hook) for A/B runs")
    ap.add_argument("--mesh", default="",
                    help="span a device mesh, e.g. 'tp=2' (dense "
                         "tensor parallel) or 'tp=2,ep=2' (MoE expert "
                         "x tensor parallel; a size may be -1 to "
                         "absorb remaining devices). The mesh builds "
                         "over the chips the plugin granted "
                         "(TPU_VISIBLE_CHIPS / TPU_PROCESS_BOUNDS); "
                         "weights shard per the family's param specs, "
                         "KV pools split kv heads over tp, and every "
                         "tick path runs the same code SPMD. CPU "
                         "testing: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=4. "
                         "Multi-host: when the plugin injected the "
                         "gang env contract (TPUSHARE_COORDINATOR / "
                         "NUM_PROCESSES / PROCESS_ID), the engine "
                         "initializes jax.distributed first and the "
                         "mesh spans every gang member's devices — "
                         "rank 0 runs the gang liaison, host loss "
                         "shrinks the mesh across process boundaries")
    ap.add_argument("--process-view", type=int, default=0,
                    metavar="N",
                    help="partition the (single-process) mesh into N "
                         "logical process ranks — the forced-host CI "
                         "lane for multi-host serving: host_event / "
                         "POST /mesh/host / host.loss chaos drive "
                         "whole-rank loss and recovery through the "
                         "same rank->device-range->reshard path a "
                         "real gang takes, without a second OS "
                         "process (the CPU backend cannot run "
                         "cross-process computations). Conflicts "
                         "with a real gang env grant")
    ap.add_argument("--platform", default="",
                    choices=["", "cpu", "tpu"],
                    help="force the JAX backend (config.update wins "
                         "over JAX_PLATFORMS). 'tpu' fails at startup "
                         "when no chip can be opened — what an "
                         "on-chip deployment wants; the default lets "
                         "jax resolve the backend, which without a "
                         "chip is the CPU. The startup line names "
                         "the platform either way")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8478)
    ap.add_argument("--n-slots", type=int, default=8)
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="paged KV pool blocks (dense family; "
                         "default 256)")
    ap.add_argument("--block-size", type=int, default=None,
                    help="paged KV block tokens (dense family; "
                         "default 16)")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-queue", type=int, default=64,
                    help="pending-request bound; overflow answers 429")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="split admissions longer than this many tokens "
                         "into block-aligned prefill chunks FUSED into "
                         "the decode batch's forward (0 = whole-prompt "
                         "admits). Values below "
                         f"{PREFILL_CHUNK_FLOOR} are clamped (the "
                         "measured break-even; see "
                         "--prefill-chunk-force)")
    ap.add_argument("--prefill-chunk-force", action="store_true",
                    help="keep a --prefill-chunk below the "
                         f"{PREFILL_CHUNK_FLOOR}-token break-even "
                         "floor instead of clamping it (r5 measured "
                         "256-token chunks at 0.49x of whole-admit)")
    ap.add_argument("--tick-token-budget", type=int, default=0,
                    help="cap decode-rows + fused admission-chunk "
                         "tokens per engine tick (bounds per-tick "
                         "latency; 0 = unbounded). When the budget "
                         "leaves no chunk room beside the decode "
                         "batch, decode-only and admission-only ticks "
                         "alternate")
    ap.add_argument("--draft-preset", default="",
                    choices=["", "tiny", "gemma_2b", "int8-self"],
                    help="enable speculative decoding with this draft "
                         "model (same vocabulary; EVERY family "
                         "composes with sampling — temperature>0 uses "
                         "the exact stochastic acceptance rule on the "
                         "shared seam, models/spec.py; the moe family "
                         "supports int8-self). 'int8-self': the "
                         "target's own int8 rounding as the draft — "
                         "near-total acceptance at half the draft "
                         "weight stream, no second model")
    ap.add_argument("--gamma", type=int, default=4,
                    help="draft tokens per speculative round (the "
                         "horizon multiplies this)")
    ap.add_argument("--spec-horizon", type=int, default=1,
                    help="multi-token draft horizon K: each "
                         "speculative round drafts gamma*K tokens and "
                         "verifies the whole block in ONE target "
                         "weight stream (acceptance-prefix semantics; "
                         "greedy output bit-identical at any K, "
                         "sampling keeps the target law). 1 = classic "
                         "rounds. Pays off when the draft's accept "
                         "rate is high (int8-self); /stats "
                         "speculative.spec_accept_rate is the tuning "
                         "signal. Requires --draft-preset; validated "
                         "against --tick-token-budget (a round is "
                         "unsplittable, so a budget below gamma*K+1 "
                         "would be breached by every round)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples (composes with "
                         "--draft-preset via the exact stochastic "
                         "acceptance rule)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="truncate sampling to the k most likely "
                         "tokens (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass cutoff (1.0 = off)")
    ap.add_argument("--chaos-spec", default=None,
                    help="deterministic fault injection "
                         "(tpushare.chaos), e.g. "
                         "'forward:raise@p=0.02;token_fetch:nan"
                         "@p=0.01;seed=7'. Default: the "
                         f"{ENV_CHAOS} env var; unset = zero-overhead "
                         "no-op fault points")
    ap.add_argument("--tick-deadline-ms", type=float, default=0,
                    help="per-engine-tick deadline; a tick running "
                         "longer counts a deadline_breaches /stats "
                         "breach (0 = off). Also bounds injected "
                         "'hang' faults")
    ap.add_argument("--journal-dir", default=None,
                    help="crash-only serving (r15): write-ahead "
                         "request journal directory. Every accepted "
                         "request is journaled (ACCEPT -> per-tick "
                         "TOKENS batches -> DONE/CANCEL/FAILED, "
                         "length-prefixed + CRC32); a kill -9'd "
                         "daemon restarted on the same directory "
                         "replays the journal and finishes every "
                         "accepted stream token-exact. Also makes "
                         "the Idempotency-Key dedupe window durable "
                         "across process death. Unset = no journal "
                         "(bit-exact streams, zero journal I/O)")
    ap.add_argument("--journal-fsync", default="tick",
                    choices=["tick", "batch", "off"],
                    help="journal durability policy: 'tick' fsyncs "
                         "every work tick (a token a client saw is a "
                         "token on disk); 'batch' fsyncs on segment "
                         "rotation/checkpoint (bounded loss on POWER "
                         "failure, still zero loss on process death); "
                         "'off' never fsyncs (kill -9 safe via the "
                         "page cache, power-loss may lose the tail)")
    ap.add_argument("--tick-wedge-ms", type=float, default=0,
                    help="wedge watchdog: a tick stuck past this "
                         "bound (tick_in_flight_ms is the live "
                         "signal) is escalated by the supervisor to "
                         "a hard engine restart through the bounded "
                         "--max-engine-restarts path — the wedged "
                         "thread is superseded and its in-flight "
                         "requests replay token-exact (0 = off)")
    ap.add_argument("--max-replays", type=int, default=3,
                    help="per-request quarantine-replay budget before "
                         "a clean 503 (replays are token-exact "
                         "re-admissions carrying generated tokens)")
    ap.add_argument("--max-engine-restarts", type=int, default=3,
                    help="engine-thread restarts (with backoff) the "
                         "loop supervisor attempts before /healthz "
                         "goes red")
    ap.add_argument("--max-reshards", type=int, default=3,
                    help="mesh-shrink (degrade-and-replay) budget for "
                         "a sharded engine: a chip-health event or an "
                         "XlaRuntimeError out of a sharded dispatch "
                         "replays every in-flight request token-exact "
                         "onto the largest healthy sub-mesh, at most "
                         "this many times before the replica goes "
                         "drained-sticky and the router sheds it "
                         "(grow-backs are free — they happen at idle "
                         "with nothing to replay)")
    ap.add_argument("--reshard-checkpoint", default=None,
                    help="directory for the reshard weight source "
                         "(requires --mesh): the unsharded host trees "
                         "are checkpointed here once at boot "
                         "(utils/checkpoint, orbax) and every reshard "
                         "restores them under the new mesh's "
                         "shardings. Default: an in-memory host copy "
                         "(one resident duplicate of the weights)")
    from tpushare.slo import TIER_ORDER
    ap.add_argument("--default-tier", default=DEFAULT_TIER,
                    choices=list(TIER_ORDER),
                    help="priority tier for requests that name none "
                         "(requests pass {'tier': ...}; interactive "
                         "outranks standard outranks batch — tier "
                         "deadlines/weights are the tpushare.slo "
                         "tier table)")
    ap.add_argument("--overlap-tick", choices=("on", "off"),
                    default="on",
                    help="overlapped tick pipeline: while tick N's "
                         "dispatch is in flight, tick N+1's host "
                         "scheduling (and tick N's journal fsync) run "
                         "in the overlap window and the one device "
                         "fetch lands one tick late — streams stay "
                         "bit-exact at any pipeline depth. 'off' "
                         "restores the serial schedule-dispatch-fetch "
                         "tick (the fallback every flush trigger — "
                         "drain, reshard, chaos quarantine — degrades "
                         "to for one tick)")
    ap.add_argument("--tenant-quota", default="",
                    help="per-tenant KV-pool block quotas: "
                         "'tenant=reserve:ceiling' pairs, comma-"
                         "separated (e.g. 'acme=16:64,bg=0:32'; empty "
                         "ceiling = unlimited burst). Layered on the "
                         "paged pool counters; the plugin-injected "
                         "TPUSHARE_KV_BLOCK_RESERVE/_LIMIT env grants "
                         "a 'default'-tenant quota when no flag names "
                         "one")
    ap.add_argument("--host-kv-bytes", type=int, default=0,
                    help="host-RAM KV offload tier budget in bytes "
                         "(r18): cold paged blocks DEMOTE to pinned "
                         "host numpy instead of being destroyed, and "
                         "promote back (prefetched in the overlap "
                         "window) on a prefix hit; also the landing "
                         "zone for cross-replica block migration "
                         "(POST /kv/migrate). 0 = no tier. Needs the "
                         "paged pool + prefix cache; rejected with "
                         "--mesh (sharded pool rows live split across "
                         "devices)")
    return ap


def main() -> int:
    for flag in ("--kv", "--max-len"):
        if any(a.split("=")[0] == flag for a in sys.argv[1:]):
            raise SystemExit(
                f"{flag} was removed in PR 30 with the dense-row slot "
                f"servers: every family serves over the paged pool "
                f"(context is --n-blocks x --block-size)")
    args = build_parser().parse_args()
    engine = build_engine(args)
    httpd = serve(engine, args.host, args.port, daemon_threads=False)
    import jax
    devs = jax.devices()

    def _hbm(key):
        # Per local device; None where the backend keeps no allocator
        # stats (the CPU).
        return [(d.memory_stats() or {}).get(key)
                for d in jax.local_devices()]

    # The one place the daemon names its device: a deployment (and
    # chip_smoke.py) reads platform= here to know the weights are on
    # the chip and, under --mesh, spread over it.
    print(f"tpushare-serve on {args.host}:{httpd.server_address[1]} "
          f"({args.model_family}/{args.preset}, {args.n_slots} slots"
          f"{', mesh ' + args.mesh if args.mesh else ''}) "
          f"platform={devs[0].platform} "
          f"device_kind={devs[0].device_kind!r} devices={len(devs)} "
          f"bytes_in_use={_hbm('bytes_in_use')}",
          flush=True)

    # SIGTERM (the kubelet's preemption signal) drains: refuse new
    # work, finish accepted requests within the pod's grace period,
    # exit 0. SIGKILL after the grace period is the backstop.
    import signal as _signal
    stop = threading.Event()
    _signal.signal(_signal.SIGTERM, lambda *_: stop.set())
    try:
        while not stop.is_set():
            stop.wait(1.0)
        print("SIGTERM: draining", flush=True)
        engine.drain(timeout_s=25.0)
        httpd.shutdown()
        # Joins the (non-daemon) handler threads: every completed
        # request's response bytes reach the socket before exit.
        httpd.server_close()
        engine.stop()
        print(f"drained: peak_bytes_in_use={_hbm('peak_bytes_in_use')} "
              f"bytes_limit={_hbm('bytes_limit')}", flush=True)
        return 0
    except KeyboardInterrupt:
        return 0


def resolve_tenant_quotas(flag_text: str):
    """Per-tenant KV quotas: the plugin-injected env grant
    (TPUSHARE_KV_BLOCK_RESERVE/_LIMIT, the pod's "default" tenant)
    merges UNDER any explicit --tenant-quota pairs — per tenant, the
    flag wins (the operator standing in front of the pod outranks the
    scheduler's default grant), but a flag naming only OTHER tenants
    never silently discards the pod's own isolation grant. None when
    neither names a quota. A poisoned env grant (limit < reserve)
    raises loudly, exactly like the chip grants."""
    from tpushare.slo.quota import parse_quota_spec
    from tpushare.utils.tenant import kv_quota_env
    quotas = parse_quota_spec(flag_text) if flag_text else {}
    for tenant, spec in (kv_quota_env() or {}).items():
        quotas.setdefault(tenant, spec)
    return quotas or None


def build_engine(args) -> ServeEngine:
    """Build the engine exactly as ``tpushare-serve`` would from parsed
    args — the CLI's validation guards included. Split from main() so
    the demo/e2e path (and tests) can drive the argv contract without
    binding a port."""
    if (args.prefill_chunk and args.prefill_chunk < PREFILL_CHUNK_FLOOR
            and not args.prefill_chunk_force):
        # VERDICT r5 #7: --prefill-chunk 256 was "accepted silently at
        # a measured 2x cost". Warn LOUDLY and clamp to the break-even
        # floor; --prefill-chunk-force keeps the small value for
        # people who measured their own shapes.
        print(f"WARNING: --prefill-chunk {args.prefill_chunk} is below "
              f"the measured break-even floor of {PREFILL_CHUNK_FLOOR} "
              f"tokens (r5 on-chip: 256-token chunks decoded admits at "
              f"0.49x of whole-admit); clamping to "
              f"{PREFILL_CHUNK_FLOOR}. Pass --prefill-chunk-force to "
              f"keep {args.prefill_chunk}.",
              file=sys.stderr, flush=True)
        args.prefill_chunk = PREFILL_CHUNK_FLOOR

    from tpushare.utils.tenant import AllocationError
    try:
        quotas = resolve_tenant_quotas(getattr(args, "tenant_quota", ""))
    except ValueError as e:
        raise SystemExit(f"--tenant-quota: {e}")
    except AllocationError as e:
        # kv_quota_env's poisoned-grant class (limit < reserve in the
        # plugin-injected env) — same loud one-liner as a bad flag,
        # not a raw traceback.
        raise SystemExit(f"KV-block env grant: {e}")
    default_tier = getattr(args, "default_tier", DEFAULT_TIER)

    # Speculation flags: validated LOUDLY before any jax work. The
    # horizon is a speculation knob (meaningless without a draft), and
    # the tick budget's granule math must cover one spec round —
    # gamma*K+1 tokens verified in one dispatch per slot — or the
    # deployment could never run the rounds it was configured for.
    spec_horizon = getattr(args, "spec_horizon", 1)
    if spec_horizon < 1:
        raise SystemExit(f"--spec-horizon must be >= 1, got "
                         f"{spec_horizon}")
    if spec_horizon > 1 and not args.draft_preset:
        raise SystemExit("--spec-horizon is a speculation knob: it "
                         "multiplies --gamma's drafted block per "
                         "round, so it needs --draft-preset (no draft "
                         "model, nothing to draft)")
    if (args.draft_preset and args.tick_token_budget
            and args.tick_token_budget
            < args.gamma * spec_horizon + 1):
        raise SystemExit(
            f"--tick-token-budget {args.tick_token_budget} is below "
            f"the speculative round granule gamma*spec_horizon+1 = "
            f"{args.gamma * spec_horizon + 1}: a spec round cannot "
            f"be split (acceptance is decided on device), so every "
            f"round would emit past this budget and silently breach "
            f"the per-tick bound it promises. Raise the budget or "
            f"lower --gamma/--spec-horizon")

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    if getattr(args, "reshard_checkpoint", None) and not args.mesh:
        raise SystemExit("--reshard-checkpoint is the sharded "
                         "engine's reshard weight source; it needs "
                         "--mesh (an unsharded engine has no mesh "
                         "failure domain)")
    mesh = None
    num_processes, process_index, gang = 1, 0, None
    if args.mesh:
        from tpushare.parallel import parse_mesh_spec, serving_mesh
        from tpushare.parallel.multihost import (gang_contract,
                                                 initialize)
        # Real multi-host lane: the plugin's Allocate injected the
        # gang env contract (all-or-nothing — a partial contract was
        # refused at grant time), so bring up jax.distributed BEFORE
        # the first device query and let the mesh span every gang
        # member's devices.
        contract = gang_contract()
        if contract is not None and contract["num_processes"] > 1:
            initialize(contract["coordinator"],
                       contract["num_processes"],
                       contract["process_id"])
            num_processes = contract["num_processes"]
            process_index = contract["process_id"]
        try:
            sizes = parse_mesh_spec(args.mesh)
            if (args.model_family != "moe"
                    and sizes.get("ep", 1) != 1):
                raise ValueError(
                    "ep is expert parallelism (--model-family moe); "
                    "the dense family shards over tp")
            mesh = serving_mesh(sizes)
        except ValueError as e:
            raise SystemExit(
                f"--mesh {args.mesh!r}: {e} (CPU testing recipe: "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        pview = int(getattr(args, "process_view", 0) or 0)
        if pview > 1:
            if num_processes > 1:
                raise SystemExit(
                    "--process-view is the single-process CI lane; "
                    "it conflicts with a real gang env grant "
                    "(TPUSHARE_NUM_PROCESSES > 1)")
            if mesh.size % pview != 0:
                raise SystemExit(
                    f"--process-view {pview}: the {mesh.size}-device "
                    f"mesh does not divide into {pview} ranks")
            num_processes = pview
        if num_processes > 1 and contract is not None:
            # The gang liaison rides one port above the jax.distributed
            # coordinator: rank 0 listens and owns the host-loss
            # verdicts; followers drip heartbeats (attached to the
            # engine after construction, below, so each beat can carry
            # the rank's device-fetch counter).
            from tpushare.parallel.gang import GangLeader
            host, _, port = contract["coordinator"].rpartition(":")
            if process_index == 0:
                gang = GangLeader(num_processes,
                                  port=int(port) + 1,
                                  host=host or "0.0.0.0")
    # After the gang bring-up above (jax.distributed initializes
    # BEFORE the first device query), before the first compile.
    if jax.default_backend() != "cpu":
        # Every daemon start on the chip would otherwise compile its
        # model from nothing. CPU daemons compile fast and XLA:CPU
        # cache entries are machine-specific — no cache there.
        from tpushare.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    if args.model_family == "moe":
        from tpushare.models import moe
        if args.preset != "tiny":
            raise SystemExit("--model-family moe serves --preset tiny "
                             "(load real Mixtral trees via the API: "
                             "convert.moe_from_hf + ServeEngine)")
        if args.draft_preset and args.draft_preset != "int8-self":
            raise SystemExit("moe speculative serving supports "
                             "--draft-preset int8-self (the target's "
                             "own int8 rounding; no second model)")
        if args.int8_experts and args.draft_preset == "int8-self":
            # ADVICE r5: the int8-self draft IS the served int8 target
            # bit-for-bit, so every speculative round streams gamma+1
            # identical full weight sets for a speedup that is
            # impossible by construction (speculation pays off only
            # when the draft stream is cheaper than the target's).
            raise SystemExit(
                "--int8-experts + --draft-preset int8-self: the draft "
                "is bit-identical to the served int8 target, so "
                "speculation can only add work. Serve EITHER int8 "
                "weights (drop --draft-preset) OR int8-self "
                "speculation over bf16 weights (drop --int8-experts)")
        if args.kv_quant:
            raise SystemExit("--kv-quant is a dense-family flag "
                             "(int8 KV pools); --model-family moe "
                             "serves full-precision KV")
        cfg = moe.tiny(remat=False)
        params = moe.init_params(jax.random.PRNGKey(args.seed), cfg)
        mhook, mspec, mdhook = None, None, None
        from tpushare.models import quant
        if args.draft_preset == "int8-self":
            mspec = (quant.quantize_params(params, cfg), cfg)
            # The draft streams its weights every round too — same
            # fused no-wide-copy path as the served int8 target.
            mdhook = quant.fused_expert_hook(cfg)
        if args.int8_expert_hook and not args.int8_experts:
            raise SystemExit("--int8-expert-hook picks the layers_hook "
                             "for --int8-experts; pass --int8-experts "
                             "(or drop the hook flag)")
        if args.int8_experts:
            params = quant.quantize_params(params, cfg)
            # Fused by default: the dequant hook's materialized wide
            # expert copies are the measured r5 roofline-gap culprit;
            # --int8-expert-hook dequant keeps the A/B oracle.
            mhook = (quant.dequant_hook(cfg)
                     if args.int8_expert_hook == "dequant"
                     else quant.fused_expert_hook(cfg))
        # Sharded int8 trees need the quant spec trees (the int8 +
        # scale leaves don't match the full-precision param_specs).
        mps = (quant.quant_moe_param_specs(cfg)
               if mesh is not None and args.int8_experts else None)
        mdps = (quant.quant_moe_param_specs(cfg)
                if mesh is not None and args.draft_preset == "int8-self"
                else None)
        engine = ServeEngine(params, cfg, model_family="moe",
                             n_slots=args.n_slots,
                             n_blocks=args.n_blocks or 256,
                             block_size=args.block_size or 16,
                             prefix_cache=not args.no_prefix_cache,
                             prefill_chunk=args.prefill_chunk or None,
                             tick_token_budget=args.tick_token_budget,
                             max_queue=args.max_queue,
                             temperature=args.temperature,
                             top_k=args.top_k or None,
                             top_p=(args.top_p if args.top_p < 1.0
                                    else None),
                             seed=args.seed, layers_hook=mhook,
                             speculative_draft=mspec, gamma=args.gamma,
                             spec_horizon=spec_horizon,
                             draft_layers_hook=mdhook,
                             chaos_spec=args.chaos_spec,
                             tick_deadline_ms=(args.tick_deadline_ms
                                               or None),
                             max_replays=args.max_replays,
                             max_engine_restarts=args.max_engine_restarts,
                             mesh=mesh, param_specs=mps,
                             draft_param_specs=mdps,
                             default_tier=default_tier,
                             tenant_quotas=quotas,
                             reshard_checkpoint=getattr(
                                 args, "reshard_checkpoint", None),
                             max_reshards=getattr(
                                 args, "max_reshards", 3),
                             journal_dir=getattr(args, "journal_dir",
                                                 None),
                             journal_fsync=getattr(
                                 args, "journal_fsync", "tick"),
                             tick_wedge_ms=(getattr(
                                 args, "tick_wedge_ms", 0) or None),
                             overlap_tick=(getattr(
                                 args, "overlap_tick", "on") == "on"),
                             host_kv_bytes=getattr(
                                 args, "host_kv_bytes", 0),
                             num_processes=num_processes,
                             process_index=process_index, gang=gang)
    else:
        if args.int8_experts:
            raise SystemExit("--int8-experts is a moe flag; dense int8 "
                             "weights load via the API (quantize_params "
                             "+ layers_hook)")
        if args.int8_expert_hook:
            raise SystemExit("--int8-expert-hook is a moe flag "
                             "(pairs with --int8-experts)")
        from tpushare.models import transformer as tf
        cfg = {"tiny": tf.tiny, "gemma_2b": tf.gemma_2b,
               "llama3_8b": tf.llama3_8b}[args.preset]()
        key = jax.random.PRNGKey(args.seed)
        if mesh is None:
            params = tf.init_params(key, cfg)
        else:
            # Born under the serving placement: unplaced, the whole
            # tree lands on the first device, each leaf drawn in
            # float32 first — llama3_8b's w_gate alone is 7.5 GB on a
            # 16 GB chip that is about to hold its quarter of 16 GB of
            # weights. Under jit with out_shardings each device draws
            # only its own shard (threefry is partitionable: the
            # values are those of the unplaced tree).
            from tpushare.parallel import tree_shardings
            params = jax.jit(
                lambda k: tf.init_params(k, cfg),
                out_shardings=tree_shardings(
                    mesh, tf.param_specs(cfg)))(key)
        spec, hook, dps = None, None, None
        if args.draft_preset == "int8-self":
            from tpushare.models import quant
            spec = (quant.quantize_params(params, cfg), cfg)
            hook = quant.dequant_hook(cfg)
            if mesh is not None:
                dps = quant.quant_param_specs(cfg)
        elif args.draft_preset:
            dcfg = {"tiny": tf.tiny, "gemma_2b": tf.gemma_2b}[
                args.draft_preset]()
            spec = (tf.init_params(jax.random.PRNGKey(args.seed + 1),
                                   dcfg), dcfg)
        engine = ServeEngine(params, cfg, n_slots=args.n_slots,
                             n_blocks=args.n_blocks or 256,
                             block_size=args.block_size or 16,
                             prefix_cache=not args.no_prefix_cache,
                             kv_quant=args.kv_quant,
                             max_queue=args.max_queue,
                             prefill_chunk=args.prefill_chunk or None,
                             tick_token_budget=args.tick_token_budget,
                             speculative_draft=spec, gamma=args.gamma,
                             spec_horizon=spec_horizon,
                             draft_layers_hook=hook,
                             temperature=args.temperature,
                             top_k=args.top_k or None,
                             top_p=(args.top_p if args.top_p < 1.0
                                    else None),
                             seed=args.seed,
                             chaos_spec=args.chaos_spec,
                             tick_deadline_ms=(args.tick_deadline_ms
                                               or None),
                             max_replays=args.max_replays,
                             max_engine_restarts=args.max_engine_restarts,
                             mesh=mesh, draft_param_specs=dps,
                             default_tier=default_tier,
                             tenant_quotas=quotas,
                             reshard_checkpoint=getattr(
                                 args, "reshard_checkpoint", None),
                             max_reshards=getattr(
                                 args, "max_reshards", 3),
                             journal_dir=getattr(args, "journal_dir",
                                                 None),
                             journal_fsync=getattr(
                                 args, "journal_fsync", "tick"),
                             tick_wedge_ms=(getattr(
                                 args, "tick_wedge_ms", 0) or None),
                             overlap_tick=(getattr(
                                 args, "overlap_tick", "on") == "on"),
                             host_kv_bytes=getattr(
                                 args, "host_kv_bytes", 0),
                             num_processes=num_processes,
                             process_index=process_index, gang=gang)
    if num_processes > 1 and process_index > 0:
        # Follower ranks drip heartbeats at the leader's liaison
        # port; each beat carries this rank's device-fetch counter so
        # rank 0's /stats can publish per-process fetch telemetry.
        from tpushare.parallel.gang import GangFollower
        host, _, port = contract["coordinator"].rpartition(":")
        engine._gang_follower = GangFollower(
            f"{host}:{int(port) + 1}", process_index,
            fetches_fn=lambda: engine.srv.device_fetches)
    return engine


if __name__ == "__main__":
    raise SystemExit(main())
