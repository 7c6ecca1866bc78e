"""Continuous (iteration-level) batching for autoregressive decode.

The static-batch path (:mod:`serving.engine`) dispatches whole requests:
for autoregressive decode that means every slot in a micro-batch idles
until the SLOWEST request in it drains — measured tokens/sec is bounded
by the worst request per batch, not the hardware. :class:`DecodeEngine`
replaces that execution model for LM decode: requests are admitted into
and evicted from the running batch *between decode iterations*, so a slot
freed by a short generation is refilled on the very next step while long
generations keep streaming.

Execution model (single decode-loop thread)::

    submit(prompt, max_new_tokens) ──▶ admission control (per-token cost)
        ──▶ weighted-fair scheduler (DRR by predicted token cost)
        ──▶ slot + page assignment (serving.kv_cache)
        ──▶ chunked prefill, bounded per iteration (never stalls decode)
        ──▶ ONE jitted decode step per iteration over all active slots
        ──▶ host-side finish checks (eos / budget / cancel / deadline)
        ──▶ freed slots refill from the queue before the next step

The KV cache is paged (:mod:`serving.kv_cache`): fixed-size pages plus
per-slot page tables, so the jitted step's shapes depend only on static
config ``(max_slots, table_width, page_size)`` — XLA compiles the step
once at warmup and admission/eviction/preemption never recompile
(:meth:`DecodeEngine.decode_step_cache_size` stays flat; the acceptance
test pins it). Prefill runs as fixed-size chunks through the same pages,
at most ``prefill_chunks_per_iter`` per iteration, so a long prompt is
absorbed a chunk at a time between decode steps instead of stalling them.
An iteration enqueues its step first and its chunks behind it, and only
then waits for the step's tokens; a prompt's first token, the last chunk's
sample, is read one iteration on with the next step already queued. The
device so goes from step to chunk to step while the host lands tokens,
admits and packs.

When the page pool is exhausted mid-growth the engine preempts the most
recently admitted other request (LIFO — oldest work finishes first):
its pages are freed, its generated prefix is kept, and it re-enters at
the front of the line to re-prefill ``prompt + generated`` and continue.
Greedy decode therefore produces identical tokens with or without
preemption. ``num_pages`` must exceed one slot's worth of pages
(enforced), so a lone request can always run to completion — the
preemption loop cannot deadlock.

Deadline admission uses a per-token cost model (:class:`DecodeCostModel`)
instead of the whole-request latency histograms the static path predicts
from: predicted latency = chunks x chunk-EMA + max_new_tokens x step-EMA,
which prices a 4-token and a 400-token generation differently where a
request-latency histogram cannot.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import os
import threading
import time
import zlib
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from paddle_tpu.core import locks
from paddle_tpu import observability, tracing
from paddle_tpu.concurrency import ChannelClosedError, go
from paddle_tpu.core import config as cfg_mod
from paddle_tpu.core import logging as ptlog
from paddle_tpu.core import profiler as prof
from paddle_tpu.core import retry as retry_mod
from paddle_tpu.core.enforce import enforce
from paddle_tpu.models import serving_programs
from paddle_tpu.observability import roofline, runlog
from paddle_tpu.parallel import collective
from paddle_tpu.tracing import waterfall
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.circuit import CircuitBreaker
from paddle_tpu.serving import admission as admission_mod
from paddle_tpu.serving import scheduler as sched_mod
from paddle_tpu.serving.admission import AdmissionRejected, TenantConfig
from paddle_tpu.serving.engine import (
    DeadlineExceeded,
    EngineClosedError,
    PendingResult,
    ServingConfig,
)
from paddle_tpu.serving.host_tier import HostPageCorrupt, HostPagePool
from paddle_tpu.serving.kv_cache import SCRATCH_PAGE, PagedKVCache, SlotStates
from paddle_tpu.serving.metrics import DecodeMetrics
from paddle_tpu.serving.prefix_cache import RadixPrefixCache
from paddle_tpu.serving.shardgroup import (
    GroupLayout,
    GroupStragglerWatch,
    ReplicaGroup,
    default_layout,
    probe_members,
)
from paddle_tpu.serving.recovery import (
    EngineUnhealthy,
    RequestJournal,
    RescuePacket,
    RetriesExhausted,
)

__all__ = [
    "DecodeConfig",
    "DecodeCostModel",
    "DecodeEngine",
    "DecodeHandle",
    "DecodeOutput",
]

# request-id salt: keeps rids unique across processes sharing one journal
# (engine labels restart from decode0 in every process)
_RID_SALT = os.urandom(3).hex()


@dataclasses.dataclass
class DecodeConfig:
    """Continuous-batching policy knobs (the model/tenant/admission side
    rides on :class:`~paddle_tpu.serving.engine.ServingConfig`)."""

    # concurrent sequences per decode step (the step's static batch dim)
    max_slots: int = 4
    # tokens per KV page; pages are the HBM allocation granularity
    page_size: int = 16
    # per-sequence position capacity (prompt + generation); must be a
    # multiple of page_size (and, for a state cache, of prefill_chunk)
    max_context: int = 256
    # physical page pool; None = every slot fully grown + scratch
    num_pages: Optional[int] = None
    # prompt tokens absorbed per prefill call (fixed-shape chunks)
    prefill_chunk: int = 32
    # prefill chunks run per decode iteration (prefill never monopolizes
    # the loop; decode steps keep landing between chunks)
    prefill_chunks_per_iter: int = 1
    # sampling policy (engine-wide; greedy by default)
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    rng_seed: int = 0
    # stop token; None = run every request to its max_new_tokens budget
    eos_id: Optional[int] = None
    # KV page dtype; overrides ServingConfig.cache_dtype when set
    cache_dtype: Optional[Any] = None
    # compile the prefill + step executables at init
    warmup: bool = True
    # with warmup off, compile them anyway when a persisted warmup
    # manifest (paddle_tpu.tune.warmup) says a previous process did —
    # replayed before the scheduler loop starts; None = the `prewarm` flag
    prewarm: Optional[bool] = None
    # idle poll interval on the scheduler when no slot is active
    idle_poll_s: float = 0.02
    # -- speculative decoding (draft-and-verify) --------------------------
    # draft tokens proposed per verify iteration; takes effect when the
    # engine is built with draft model params (greedy only — acceptance
    # compares argmaxes, so temperature must stay 0.0)
    spec_tokens: int = 4
    # -- radix prefix cache (serving.prefix_cache) ------------------------
    # share prompt-prefix KV pages across requests: a hit skips whole
    # prefill chunks; pages are refcounted with copy-on-write
    prefix_cache: bool = False
    # page budget for the tree (LRU-evicted past it); None = unbounded,
    # evicted only under allocator pressure
    prefix_cache_pages: Optional[int] = None
    # -- zero-loss recovery (serving.recovery) ----------------------------
    # survive decode-step faults by quarantining the poisoned iteration
    # and re-admitting live requests through the proven resume path
    # (False = the pre-recovery behavior: one step fault fails every
    # in-flight request)
    recovery: bool = True
    # per-request quarantine budget over its LIFETIME (not reset on
    # progress — re-prefill samples one token per cycle, so a progress
    # reset would let a deterministic poison loop forever): past this
    # many re-admissions the request fails with RetriesExhausted
    recovery_retries: int = 8
    # decorrelated-jitter backoff between faulted iterations (core.retry)
    recovery_base_delay_s: float = 0.002
    recovery_max_delay_s: float = 0.1
    # consecutive faulted decode iterations before this engine declares
    # itself unhealthy: trips its CircuitBreaker and — inside a
    # DecodeFleet — drains live requests to a healthy engine
    unhealthy_after: int = 3
    breaker_cooldown_s: float = 0.25
    breaker_max_cooldown_s: float = 5.0
    # durable request journal (WAL): records admissions + every
    # generated token; recovery.replay_journal()/resume_incomplete()
    # rebuild in-flight work after a process restart. None = off.
    journal_path: Optional[str] = None
    journal_fsync_every: int = 16
    # WAL size (bytes) that triggers an in-place compaction: finished
    # requests drop, incomplete ones are rewritten as snapshots into a
    # fresh segment (atomic publish). None = unbounded growth.
    journal_compact_bytes: Optional[int] = None
    # -- replica groups (serving.shardgroup) ------------------------------
    # per-member canary cadence when the engine is group-backed: each
    # member device is timed individually so a fault or stall is
    # attributable to ONE chip of the group
    group_probe_every_s: float = 0.05
    # per-shard probe-time skew (vs the median shard) that flags a
    # straggler chip inside the group
    group_skew_ratio: float = 4.0
    # statically lint the GroupLayout against the actual param tree + KV
    # geometry BEFORE placing anything on devices (analysis.shard_analysis):
    # layout errors (dead rules, rank mismatches, kv-geometry violations)
    # raise here instead of surfacing as a wrong placement on a pod
    lint_layout: bool = True
    # -- hierarchical KV host tier (serving.host_tier) --------------------
    # byte budget for a PRIVATE host-RAM page pool behind the radix tree
    # (requires prefix_cache): radix inserts write through to host RAM,
    # radix misses whose continuation the pool holds promote back
    # asynchronously. None = no private pool; pass a shared HostPagePool
    # to DecodeEngine(host_tier=...) for fleet-wide sharing + crash
    # recovery (the pool survives any one engine's kill()).
    host_tier_bytes: Optional[int] = None
    # publish a compact per-prefix digest set for prefix-aware fleet
    # routing: DecodeFleet/DisaggRouter route each prompt to the engine
    # with the longest cached prefix (least-loaded tiebreak)
    prefix_digest: bool = False
    # promote-apply budget: pages implanted from the host tier per loop
    # iteration — bounds added per-iteration latency so promotion stays
    # decode-p99-neutral (the bench leg pins this)
    host_promote_pages_per_iter: int = 4


@dataclasses.dataclass
class DecodeOutput:
    """One finished generation. ``tokens`` holds the generated ids
    (including ``eos_id`` when that ended it); ``finish_reason`` is
    ``"eos"`` | ``"length"`` | ``"cancelled"`` | ``"drain_timeout"``
    (close() deadline enforced: partial tokens returned)."""

    tokens: np.ndarray
    finish_reason: str
    prompt_len: int
    n_preemptions: int = 0


class DecodeHandle(PendingResult):
    """Future for one decode request, plus mid-generation cancellation:
    :meth:`cancel` marks the request; the loop completes it with the
    tokens generated so far (``finish_reason="cancelled"``) at the next
    iteration boundary."""

    def __init__(self, req: "_DecodeRequest"):
        super().__init__()
        self._req = req

    def cancel(self) -> None:
        self._req.cancelled = True


class _DecodeRequest:
    __slots__ = ("prompt", "mnt", "n", "bytes", "tenant", "cls", "deadline",
                 "t_submit", "handle", "generated", "slot", "phase", "seq",
                 "chunks_done", "cur_len", "last_tok", "cancelled",
                 "n_preemptions", "trace", "t_enqueue_pc", "t_admit_pc",
                 "rid", "recoveries", "first_tok")

    def __init__(self, prompt: np.ndarray, mnt: int, n_chunks: int,
                 deadline: Optional[float], t_submit: float,
                 tenant: str = "default", cls: str = "interactive"):
        self.prompt = prompt
        self.mnt = mnt
        # DRR weight: predicted device iterations (decode steps + prefill
        # chunks), so fairness is by token cost, not request count
        self.n = mnt + n_chunks
        self.bytes = int(prompt.nbytes)
        self.tenant = tenant
        self.cls = cls
        self.deadline = deadline
        self.t_submit = t_submit
        self.handle = DecodeHandle(self)
        self.generated: List[int] = []
        self.slot: Optional[int] = None
        self.phase = "queued"  # queued | prefill | first_token | decode
        self.seq: Optional[np.ndarray] = None  # tokens being prefilled
        self.chunks_done = 0
        self.cur_len = 0               # K/V positions written so far
        self.last_tok = 0              # next token to feed the step
        self.cancelled = False
        self.n_preemptions = 0
        self.trace: Optional[tracing.SpanContext] = None
        self.t_enqueue_pc: Optional[float] = None
        self.t_admit_pc: Optional[float] = None
        self.rid: Optional[str] = None   # journal/migration identity
        self.recoveries = 0              # quarantine cycles survived
        # phase "first_token": the last chunk's sample, still on the
        # device, with the chunk's enqueue time and index
        self.first_tok = None


def _on_device(refs):
    """A step's slot references as device arrays: one array, or the pair a
    model with pages and states is handed."""
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.asarray, refs)


def _under_mesh(group, fn):
    """``fn`` as a replica group's engine traces it: under the group's mesh,
    so that code which cannot run as one program over several chips (a
    Mosaic kernel is not partitioned automatically) sees it is not alone.
    With no group, ``fn`` itself."""
    if group is None:
        return fn

    @functools.wraps(fn)  # jit finds the arguments it donates by their names
    def traced(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(group.mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return traced


class DecodeCostModel:
    """EMA cost model for decode admission: per-iteration step cost and
    per-chunk prefill cost, observed by the loop. ``step_s``/``chunk_s``
    preset the EMAs (deterministic tests / warm handoff); cold (no step
    observations and no preset) estimates are None so admission falls
    back to admitting everything — shedding on zero data would reject
    the traffic that builds the model."""

    def __init__(self, alpha: float = 0.2, step_s: Optional[float] = None,
                 chunk_s: Optional[float] = None,
                 verify_s: Optional[float] = None,
                 accepted_per_step: Optional[float] = None):
        enforce(0.0 < alpha <= 1.0, f"alpha must be in (0, 1], got {alpha}")
        self.alpha = float(alpha)
        self._step_s = step_s
        self._chunk_s = chunk_s
        # speculative decoding: per-verify-iteration cost and how many
        # tokens one iteration lands on average (1 + accepted drafts).
        # Without these, estimate() assumes 1 token/step — wildly
        # pessimistic under speculation.
        self._verify_s = verify_s
        self._accepted = accepted_per_step
        self._lock = locks.Lock("serving.decode_cost_model")

    def observe_step(self, seconds: float) -> None:
        with self._lock:
            self._step_s = (seconds if self._step_s is None else
                            self.alpha * seconds +
                            (1 - self.alpha) * self._step_s)

    def observe_chunk(self, seconds: float) -> None:
        with self._lock:
            self._chunk_s = (seconds if self._chunk_s is None else
                             self.alpha * seconds +
                             (1 - self.alpha) * self._chunk_s)

    def observe_verify(self, seconds: float, accepted_tokens: float) -> None:
        """One draft-and-verify iteration: its wall cost (drafting
        included) and the tokens it landed per participating slot."""
        with self._lock:
            self._verify_s = (seconds if self._verify_s is None else
                              self.alpha * seconds +
                              (1 - self.alpha) * self._verify_s)
            self._accepted = (accepted_tokens if self._accepted is None else
                              self.alpha * accepted_tokens +
                              (1 - self.alpha) * self._accepted)

    def estimate(self, n_chunks: int, max_new_tokens: int,
                 queue_cost: int = 0) -> Optional[float]:
        """Predicted service latency: prefill chunks + decode iterations,
        plus ``queue_cost`` iterations already queued ahead. Under
        speculation an iteration is one verify step landing
        ``accepted_per_step`` tokens; otherwise one step = one token.
        None while cold."""
        with self._lock:
            step_s, chunk_s = self._step_s, self._chunk_s
            verify_s, accepted = self._verify_s, self._accepted
        if verify_s is not None:
            per_iter = verify_s
            tokens_per_iter = max(accepted if accepted else 1.0, 1.0)
            if chunk_s is None:
                chunk_s = verify_s
            iters = max_new_tokens / tokens_per_iter
            return (n_chunks * chunk_s + iters * per_iter
                    + queue_cost * per_iter)
        if step_s is None:
            return None
        if chunk_s is None:
            chunk_s = step_s
        return (n_chunks * chunk_s + max_new_tokens * step_s
                + queue_cost * step_s)

    def snapshot(self) -> Dict[str, Optional[float]]:
        with self._lock:
            return {"step_s": self._step_s, "chunk_s": self._chunk_s,
                    "verify_s": self._verify_s,
                    "accepted_per_step": self._accepted}


class DecodeEngine:
    """Iteration-level batched autoregressive serving over a trained
    language model (params as created by its ``lm_forward``). The model
    brings its own programs (:func:`~paddle_tpu.models.serving_programs`):
    ``transformer_lm`` a paged KV cache, ``retention_lm`` one fixed
    recurrent state per slot, ``hybrid_ssm_lm`` both (Mamba-2 layers' states
    beside attention layers' pages, under one slot and page manager: a
    state is indexed by the slot's number, a preempted or quarantined
    request loses pages and state alike and prefills again from position
    0). The engine owns the cache arrays in every case; what needs pages
    that can be shared, copied or rolled back (prefix cache, host tier,
    handoff, a draft model, a replica group) is refused at construction
    for a model that keeps a state, alone or beside pages.

    ::

        eng = DecodeEngine(variables, cfg, decode=DecodeConfig(max_slots=8))
        out = eng.infer(prompt_ids, max_new_tokens=32)   # DecodeOutput
        h = eng.submit(prompt_ids, 128)                  # async
        h.cancel()                                       # mid-generation
        eng.close()                                      # graceful drain

    Passing ``draft_variables`` (plus its ``draft_cfg`` when the draft is
    a different architecture) turns on draft-and-verify speculative
    decoding: each iteration the draft proposes ``DecodeConfig.spec_tokens``
    tokens sequentially, one jitted ``paged_verify_step`` scores all of
    them (plus the bonus position) against the target's paged cache, and
    the longest draft prefix matching the target's own greedy choices is
    accepted — token-exact vs ``generate()`` by construction. The draft
    shares the slot page tables and allocator geometry with its own page
    arrays, so admission/preemption bookkeeping stays single-sourced.
    ``DecodeConfig.prefix_cache=True`` adds the radix prefix cache: hot
    prompt prefixes prefill once and later requests adopt the shared
    pages (refcounted, copy-on-write).
    """

    def __init__(
        self,
        variables,
        model_cfg: dict,
        *,
        config: Optional[ServingConfig] = None,
        decode: Optional[DecodeConfig] = None,
        draft_variables=None,
        draft_cfg: Optional[dict] = None,
        group: Optional[ReplicaGroup] = None,
        layout: Optional[GroupLayout] = None,
        host_tier: Optional[HostPagePool] = None,
    ):
        self.config = config or ServingConfig()
        self.decode_config = dconf = decode or DecodeConfig()
        self.model_cfg = dict(model_cfg)
        enforce(dconf.max_slots >= 1,
                f"max_slots must be >= 1, got {dconf.max_slots}")
        enforce(dconf.prefill_chunk >= 1,
                f"prefill_chunk must be >= 1, got {dconf.prefill_chunk}")
        enforce(dconf.max_context % dconf.page_size == 0,
                f"max_context ({dconf.max_context}) must be a multiple of "
                f"page_size ({dconf.page_size})")
        self._programs = progs = serving_programs(self.model_cfg)
        # a model brings pages, states, or both ("pages+state": layers of
        # both kinds). With pages the slot and page manager is the paged
        # one, which a state is indexed by the slot numbers of; the
        # features that share, copy, ship or roll back pages are refused
        # wherever a state would need a snapshot beside them
        self._paged, self._stateful = progs.has_pages, progs.has_state
        # padded prompt chunks must stay inside the table they are handed —
        # a chunk running past it would clamp-scatter into the last page.
        # Where max_context is no multiple of prefill_chunk, a paged
        # chunk's table row is lengthened by scratch entries to a whole
        # number of chunks (_slot_ref): the overhang lands on the scratch
        # page, as an idle slot's step does
        enforce(self._paged or dconf.max_context % dconf.prefill_chunk == 0,
                f"max_context ({dconf.max_context}) must be a multiple of "
                f"prefill_chunk ({dconf.prefill_chunk}) for a model that "
                f"keeps {progs.mechanism}")
        chunked = -(-dconf.max_context // dconf.prefill_chunk) * dconf.prefill_chunk
        self._chunk_table_pad = (-(-chunked // dconf.page_size)
                                 - dconf.max_context // dconf.page_size)
        if dconf.prefix_cache:
            self._refuse_unless_paged("the prefix cache")
        for feature, asked in (
                ("the host tier", host_tier is not None or dconf.host_tier_bytes),
                ("a draft model", draft_variables is not None),
                ("a replica group", group is not None)):
            if asked:
                self._refuse_unless_kv_pair(feature)
        if draft_variables is not None:
            self._refuse_unless_verified()
        pages_per_slot = dconf.max_context // dconf.page_size
        num_pages = (dconf.num_pages if dconf.num_pages is not None
                     else 1 + dconf.max_slots * pages_per_slot)
        self._kv = (PagedKVCache(
            max_slots=dconf.max_slots, page_size=dconf.page_size,
            num_pages=num_pages, pages_per_slot=pages_per_slot)
            if self._paged else SlotStates(
                max_slots=dconf.max_slots, max_context=dconf.max_context))
        self.metrics = DecodeMetrics(engine_label=self.config.engine_label)
        observability.setup()
        self.cost = DecodeCostModel()

        params = variables.params if hasattr(variables, "params") else variables
        # replica-group mode (serving.shardgroup): the engine's program
        # spans the group's tp submesh — params and KV pages are committed
        # with the layout's NamedShardings and every jit pins its page
        # outputs to the same sharding, so the cache arrays never change
        # placement and the compile-once invariants hold per GROUP exactly
        # as they do per device
        self._group = group
        self._layout = (layout or default_layout()) if group is not None else None
        self._straggler = (GroupStragglerWatch(group,
                                               ratio=dconf.group_skew_ratio)
                           if group is not None else None)
        self._last_probe = 0.0
        cdt = (dconf.cache_dtype if dconf.cache_dtype is not None
               else self.config.cache_dtype)
        import jax.numpy as jnp

        self._cache_dtype = cdt or jnp.float32
        specs = progs.cache_specs(
            self.model_cfg, max_slots=dconf.max_slots, num_pages=num_pages,
            page_size=dconf.page_size, dtype=self._cache_dtype)
        pshape = specs[0].shape
        if group is None:
            self._params = jax.device_put(params)
            kvs = rep = None
        else:
            if dconf.lint_layout:
                # fail on a bad layout BEFORE any device_put: errors raise
                # with every finding listed, warnings warn_once
                from paddle_tpu.analysis.shard_analysis import (
                    lint_group_layout_or_raise,
                )

                lint_group_layout_or_raise(
                    params, self._layout, group.mesh, kv_page_shape=pshape,
                    kv_geometry=dict(self._kv.geometry(),
                                     kv_heads=progs.kv_heads(self.model_cfg)),
                    where=f"DecodeEngine[{group.name}]",
                )
            self._params = self._layout.shard_params(group, params)
            kvs = self._layout.kv_page_sharding(
                group, pshape, progs.kv_heads(self.model_cfg))
            rep = self._layout.replicated(group)
        # The engine is the sole owner of its cache arrays (the model's K
        # and V pages, its per-slot states, or both): every jit that returns a
        # new version of one takes the old one donated, so a write updates
        # the array in place instead of copying it. Every call site
        # rebinds the result and nothing else may hold a cache array
        # across a loop pass. Group mode keeps the alias per shard: the
        # page outputs are pinned to the inputs' sharding. A paged model's
        # list is [K pages, V pages] or one array of latent rows: what
        # touches a page by its id loops over the list, and the paths that
        # name [0] and [1] (host tier, handoff) are reached only through
        # features _refuse_unless_kv_pair let through.
        self._cache = [self._zero_pages(sp.shape, kvs, sp.dtype) for sp in specs]
        jit_kw = {"donate_argnames": progs.cache_args}
        page_kw = {"donate_argnames": ("pages",)}
        if group is not None:
            # tokens, the K and the V pages, then the programs' small extras
            jit_kw["out_shardings"] = (rep, kvs, kvs) + (rep,) * len(progs.extras)
            page_kw["out_shardings"] = kvs
        sample_kw = dict(temperature=dconf.temperature, top_k=dconf.top_k,
                         top_p=dconf.top_p)
        model_kw = dict(sample_kw, cfg=self.model_cfg)
        # which of the arrays are states indexed by slot; the rest are pages
        self._is_state = [progs.is_state(a) for a in progs.cache_args]
        if self._paged:
            model_kw["page_size"] = dconf.page_size
            per_token = sum(sp.shape[0] * sp.shape[3] * np.dtype(sp.dtype).itemsize
                            for sp, st in zip(specs, self._is_state) if not st)
            self.metrics.set_cache_bytes_per_token(per_token)
            # one page over every plane and array: what a live page costs a
            # step that attends it (on the step's span, for the roofline)
            self._page_bytes = dconf.page_size * per_token
        if self._stateful:
            self.metrics.set_state_bytes(sum(
                c.nbytes for c, st in zip(self._cache, self._is_state) if st))
        if progs.gauges is not None:
            self.metrics.set_program_gauges(progs.gauges(self.model_cfg))
        # (chunk number, span, extras) of the chunks whose extras are not
        # read yet: a later turn reads them, once they have run
        self._chunk_extras: Deque = deque()
        self._chunk_seq = 0
        # the turn's account (_close_turn): the loop thread's CPU clock when
        # the last turn closed, and the seconds of bookings since
        self._turn_cpu0: Optional[float] = None
        self._turn_telemetry = 0.0
        # roofline-instrumented: these jits bypass Executor.prepare(), so
        # they feed the cost ledger through their own wrapper (compiles
        # capture cost/memory analysis, later calls book wall seconds)
        self._step = roofline.instrument(
            "serving.decode.step", jax.jit(_under_mesh(group, functools.partial(
                progs.decode_step, **model_kw)), **jit_kw))
        self._prefill = roofline.instrument(
            "serving.decode.prefill", jax.jit(functools.partial(
                progs.prefill_chunk, **model_kw), **jit_kw))
        # which programs attend over live pages through a kernel and not over
        # a gathered table: the family's own rule, asked where they are traced
        in_kernel = (_under_mesh(group, progs.attends_in_kernel)(
            self.model_cfg, specs[0], dconf.page_size)
            if self._paged and progs.attends_in_kernel is not None else ())
        self._attend_kernel = int("step" in in_kernel)
        self._chunk_attend_kernel = "chunk" in in_kernel
        # disagg KV handoff (serving.disagg): one page is the fixed-shape
        # [L, page_size, H_kv * dh] slice, so gather/implant compile once.
        # In group mode the gather's output is pinned replicated — the
        # wire image is always the FULL logical page regardless of tp —
        # and the implant re-scatters it back over the group's heads.
        self._gather_page = jax.jit(
            collective.gather_kv_page,
            **({} if group is None else {"out_shardings": rep}))
        self._implant_page = jax.jit(collective.scatter_kv_page, **page_kw)
        self._rng = (jax.random.PRNGKey(dconf.rng_seed)
                     if dconf.temperature > 0.0 else None)

        # -- speculative decoding (draft-and-verify) ----------------------
        self._spec_k = 0
        self._draft_params = None
        if draft_variables is not None:
            enforce(dconf.spec_tokens >= 1,
                    f"spec_tokens must be >= 1 with a draft model, "
                    f"got {dconf.spec_tokens}")
            enforce(dconf.temperature == 0.0,
                    "speculative decoding is greedy-only: acceptance "
                    "compares argmaxes, so temperature must be 0.0")
            self.draft_cfg = dict(draft_cfg) if draft_cfg else self.model_cfg
            dprogs = serving_programs(self.draft_cfg)
            self._refuse_unless_kv_pair("a draft model", dprogs)
            self._refuse_unless_verified(dprogs)
            enforce(self.draft_cfg.get("vocab") == self.model_cfg.get("vocab"),
                    "draft and target models must share a vocabulary "
                    f"({self.draft_cfg.get('vocab')} vs "
                    f"{self.model_cfg.get('vocab')})")
            dp = (draft_variables.params
                  if hasattr(draft_variables, "params") else draft_variables)
            self._spec_k = int(dconf.spec_tokens)
            # the draft reads/writes THROUGH the same page tables: its own
            # page arrays, same (num_pages, page_size) geometry, so slot
            # bookkeeping (grow/preempt/trim) covers both caches at once
            dshape = dprogs.cache_specs(
                self.draft_cfg, max_slots=dconf.max_slots,
                num_pages=num_pages, page_size=dconf.page_size,
                dtype=self._cache_dtype)[0].shape
            djit_kw = {"donate_argnames": ("k_pages", "v_pages")}
            if group is None:
                self._draft_params = jax.device_put(dp)
                dkvs = None
            else:
                self._draft_params = self._layout.shard_params(group, dp)
                dkvs = self._layout.kv_page_sharding(
                    group, dshape, dprogs.kv_heads(self.draft_cfg))
                djit_kw["out_shardings"] = (rep, dkvs, dkvs)
            self._dk_pages = self._zero_pages(dshape, dkvs)
            self._dv_pages = self._zero_pages(dshape, dkvs)
            self._draft_step = roofline.instrument(
                "serving.decode.draft_step", jax.jit(_under_mesh(group, functools.partial(
                    dprogs.decode_step, cfg=self.draft_cfg,
                    page_size=dconf.page_size, temperature=0.0)), **djit_kw))
            self._draft_prefill = roofline.instrument(
                "serving.decode.draft_prefill", jax.jit(functools.partial(
                    dprogs.prefill_chunk, cfg=self.draft_cfg,
                    page_size=dconf.page_size, temperature=0.0), **djit_kw))
            self._verify = roofline.instrument(
                "serving.decode.verify", jax.jit(functools.partial(
                    progs.verify_step, cfg=self.model_cfg,
                    page_size=dconf.page_size), **jit_kw))

        # -- radix prefix cache -------------------------------------------
        self._prefix: Optional[RadixPrefixCache] = None
        if dconf.prefix_cache:
            self._prefix = RadixPrefixCache(
                self._kv.allocator, dconf.page_size,
                max_pages=dconf.prefix_cache_pages)
            # device-side page copy for CoW; src/dst are traced scalars so
            # this compiles once per page-array shape. Group mode pins the
            # output to the page arrays' sharding (target and draft pages
            # may shard differently, hence two jits) so the cache arrays
            # never drift placement between iterations.
            _copy = lambda pages, src, dst: pages.at[:, dst].set(pages[:, src])
            self._copy_page = jax.jit(_copy, **page_kw)
            self._copy_page_d = (self._copy_page if group is None
                                 or not self._spec_k else jax.jit(
                                     _copy, donate_argnames=("pages",),
                                     out_shardings=dkvs))

        # -- hierarchical KV host tier (serving.host_tier) ----------------
        # a pool passed in is SHARED (fleet-wide prefix sharing + crash
        # recovery: it survives this engine's kill()); host_tier_bytes
        # builds a private one. Draft-model engines skip the tier — the
        # pool carries only target-cache pages, and adopting them without
        # the draft's would desynchronize speculation (same rationale as
        # handoff adoption degrading to re-prefill).
        self._host_tier: Optional[HostPagePool] = host_tier
        if self._host_tier is None and dconf.host_tier_bytes:
            self._host_tier = HostPagePool(dconf.host_tier_bytes,
                                           dconf.page_size)
        if self._host_tier is not None and self._spec_k:
            ptlog.warning(
                "host tier disabled for engine %s: the pool carries only "
                "target-cache pages, which a speculative engine cannot "
                "adopt", self.config.engine_label)
            self._host_tier = None
        if self._host_tier is not None:
            enforce(self._prefix is not None,
                    "host tier requires DecodeConfig(prefix_cache=True): "
                    "it extends the radix tree, not the raw page pool")
            enforce(self._host_tier.compatible(dconf.page_size),
                    f"host tier page_size {self._host_tier.page_size} != "
                    f"engine page_size {dconf.page_size}")
        # promote jobs applied on the loop thread, budgeted per iteration
        # (host_promote_pages_per_iter); keys dedup in-flight prefixes
        self._promote_jobs: Deque = deque()
        self._promote_keys: set = set()
        # prefix-aware routing digest: republished (lock-free swap of an
        # immutable frozenset) on the loop thread whenever the tree's
        # digest_version moved; fleets read it from any thread
        self._digest_pub: frozenset = frozenset()
        self._digest_seen = -1

        # tenants / scheduler / admission — same wiring as ServingEngine,
        # but deadline feasibility runs through the per-token cost model
        tenant_cfgs = [t.resolved() for t in (self.config.tenants or ())]
        if not tenant_cfgs:
            tenant_cfgs = [TenantConfig(
                "default", queue_capacity=self.config.queue_capacity,
            ).resolved()]
        self._tenants = {t.name: t for t in tenant_cfgs}
        self._default_tenant = (
            "default" if "default" in self._tenants else tenant_cfgs[0].name)
        admission_on = (self.config.admission
                        if self.config.admission is not None
                        else self.config.tenants is not None)
        self._queue = sched_mod.WeightedFairScheduler(
            self._tenants,
            quantum_rows=max(8, dconf.max_slots * 8),
            batch_min_share=(self.config.batch_min_share
                             if self.config.batch_min_share is not None
                             else cfg_mod.flags().tenant_batch_min_share),
            legacy_capacity=(None if admission_on
                             else self.config.queue_capacity),
            on_expired=self._expire,
        )
        self._admission: Optional[admission_mod.AdmissionController] = None
        if admission_on:
            self._admission = admission_mod.AdmissionController(
                self._queue, self.metrics, self._tenants,
                request_cost=self._request_cost,
                brownout_min_s=self.config.brownout_min_s,
            )
            admission_mod.install(self._admission)

        self._active: List[_DecodeRequest] = []     # admission order
        self._resume: Deque[_DecodeRequest] = deque()
        self._pending_admit: Deque[_DecodeRequest] = deque()
        # disaggregated serving (serving.disagg): a prefill-role engine
        # publishes finished prefills through _handoff_sink instead of
        # decoding them; a decode-role engine admits adopted payloads
        # from _pending_handoff (implanted on the loop thread)
        self._handoff_sink: Optional[Callable[..., None]] = None
        self._pending_handoff: Deque = deque()  # (req, HandoffPayload)
        self._closed = False
        self._close_lock = locks.Lock("serving.decode_close")
        # zero-loss recovery state (serving.recovery)
        self._breaker = CircuitBreaker(
            failure_threshold=dconf.unhealthy_after,
            cooldown_s=dconf.breaker_cooldown_s,
            max_cooldown_s=dconf.breaker_max_cooldown_s)
        self._rescue_sink: Optional[Callable[..., int]] = None  # DecodeFleet
        self._consec_faults = 0
        self._recover_prev_delay = 0.0
        self._breaker_dirty = False
        self._journal: Optional[RequestJournal] = None
        # a DisaggRouter may swap in a journal SHARED across its workers;
        # then close()/kill() must not close it (the router owns its fd)
        self._journal_owned = True
        if dconf.journal_path:
            self._journal = RequestJournal(
                dconf.journal_path, fsync_every=dconf.journal_fsync_every,
                compact_bytes=dconf.journal_compact_bytes)
        self._rid_seq = itertools.count()
        self._killed = False
        self._drain_abort = False
        # the loop thread's spans hang under this one trace
        self._loop_trace = tracing.SpanContext.new_trace()

        if dconf.warmup:
            self._warmup()
        elif (dconf.prewarm if dconf.prewarm is not None
              else cfg_mod.flags().prewarm):
            self.prewarm()
        self._thread = go(self._loop)

    # -- startup -----------------------------------------------------------

    def _refuse_unless_paged(self, feature: str, programs=None) -> None:
        """The one error for everything that needs pages of any row."""
        programs = programs or self._programs
        enforce(programs.cache == "pages",
                f"DecodeEngine: {feature} cannot be used with a model that "
                f"keeps {programs.mechanism}. It shares, copies, ships or "
                "rolls back KV pages; a recurrent state would need "
                "snapshots, which the engine does not have")

    def _refuse_unless_kv_pair(self, feature: str, programs=None) -> None:
        """The one error for everything that names a K and a V page array
        of whole heads: the host tier's and the handoff's page images and
        checksums, a replica group's sharding of pages by heads, a draft
        model's verify step."""
        programs = programs or self._programs
        self._refuse_unless_paged(feature, programs)
        enforce(len(programs.cache_args) == 2 and programs.kv_heads is not None,
                f"DecodeEngine: {feature} cannot be used with a model that "
                f"keeps {programs.mechanism}. It ships, checksums or shards "
                "a K and a V page of whole heads; a one-array cache needs "
                "its own page geometry there (ROADMAP M4)")

    def _refuse_unless_verified(self, programs=None) -> None:
        """The one error for a draft model beside programs that bring no
        verify step (as target: nothing scores the draft's block; as draft:
        the engine's draft calls take three results and no extras)."""
        programs = programs or self._programs
        enforce(programs.verify_step is not None,
                f"DecodeEngine: a draft model cannot be used with a model that "
                f"keeps {programs.mechanism}. Speculative decoding scores a "
                "block of draft tokens in one verify step over a K and a V "
                "page a layer, which these programs do not have")

    def _take(self, out):
        """A program's results: rebinds the cache arrays it returned (the
        ones it was handed are donated) and returns ``(first result,
        extras)``."""
        n = len(self._cache)
        self._cache = list(out[1:1 + n])
        return out[0], tuple(out[1 + n:])

    def _land_extras(self, span, extras) -> None:
        """Read a call's extras (small arrays of a call that has run) and
        put what they say on its span and under the counters."""
        attrs = self._programs.span_attrs(
            self.model_cfg, *(np.asarray(e) for e in extras))
        span.set(**attrs)
        self.metrics.record_call_attrs(attrs)

    def _land_chunk_extras(self, before: int) -> None:
        """The same for the chunks enqueued before chunk number ``before``:
        they have run once anything enqueued behind them was read."""
        while self._chunk_extras and self._chunk_extras[0][0] < before:
            _, span, extras = self._chunk_extras.popleft()
            try:
                self._land_extras(span, extras)
            except Exception as e:
                # reading a failed chunk's outputs raises its error again;
                # the request's own path has failed it already
                ptlog.warning("prefill chunk's extras not read: %r", e)

    def _zero_pages(self, shape, sharding, dtype=None):
        """One zeroed cache array (``sharding`` None = single device)."""
        import jax.numpy as jnp

        pages = jnp.zeros(shape, dtype or self._cache_dtype)
        return pages if sharding is None else jax.device_put(pages, sharding)

    def _slot_ref(self, slot: int):
        """What a prefill chunk finds its slot's cache by: the page-table
        row, the slot's index into the state arrays, or the pair of them
        for a model that keeps both."""
        import jax.numpy as jnp

        if not self._paged:
            return jnp.int32(slot)
        row = self._kv.page_tables[slot]
        if self._chunk_table_pad:  # the last chunk's overhang: scratch
            row = np.concatenate(
                [row, np.full((self._chunk_table_pad,), SCRATCH_PAGE, row.dtype)])
        if self._stateful:
            return jnp.asarray(row), jnp.int32(slot)
        return jnp.asarray(row)

    def _slot_refs(self, decoding):
        """The same for a decode step over ``decoding``: every other slot
        gets a scratch table row, or a 0 that leaves its state alone; a
        model that keeps both gets the pair (tables, mask)."""
        S = self.decode_config.max_slots
        active = tables = None
        if self._stateful:
            active = np.zeros((S,), np.int32)
            active[[r.slot for r in decoding]] = 1
        if self._paged:
            tables = np.full((S, self._kv.pages_per_slot), SCRATCH_PAGE, np.int32)
            for req in decoding:
                tables[req.slot] = self._kv.page_tables[req.slot]
        if not self._paged:
            return active
        return (tables, active) if self._stateful else tables

    def _publish_cache(self) -> None:
        if self._paged:
            self.metrics.set_pages(self._kv.pages_in_use, self._kv.pages_free)
        if self._stateful:
            self.metrics.set_state_slots_in_use(len(self._kv.active_slots()))

    def _warmup(self) -> None:
        """Compile every executable that writes the cache arrays before
        traffic arrives, and publish whether each consumed the arrays it
        was handed (``serving.decode.pages_donated`` / ``state_donated``,
        both for a model that keeps both, each judged on its own arrays)
        and whether the device holds the page arrays as the model spells
        them (``serving.decode.pages_row_major``).
        Warmup writes land on the scratch page (zero tables), or in slot
        0's state, which the first chunk of an admission starts over, so
        no reset is needed afterwards."""
        import jax.numpy as jnp

        dconf = self.decode_config
        S, P = dconf.max_slots, dconf.max_context // dconf.page_size
        chunk0 = jnp.zeros((dconf.prefill_chunk,), jnp.int32)
        slots0 = jnp.zeros((S,), jnp.int32)
        z = jnp.int32(SCRATCH_PAGE)  # page 0, and the chunk's position 0
        kept: List[str] = []  # write-jits that left a cache array alive
        kept_kinds = set()    # ... and of which kind: "pages", "state"

        def consumed(name, *arrays):
            alive = {"state" if st else "pages"
                     for a, st in zip(arrays, self._is_state) if not a.is_deleted()}
            if alive:
                kept.append(name)
                kept_kinds.update(alive)

        old = list(self._cache)
        self._take(self._prefill(
            self._params, chunk0, z, z, self._slot_ref(0), *old,
            self._next_key()))
        consumed("prefill", *old)
        old = list(self._cache)
        out, _ = self._take(self._step(
            self._params, slots0, slots0, _on_device(self._slot_refs([])),
            *old, self._next_key()))
        consumed("step", *old)
        jax.block_until_ready(out)
        if self._paged and not self._stateful:
            # what these serve (handoff, the host tier, the prefix cache, a
            # draft) is refused beside a state
            self._warmup_page_jits(consumed, chunk0, slots0, z)
        for name in kept:
            ptlog.warn_once(
                ("decode.pages_not_donated", name),
                "DecodeEngine: %s left a cache array it was handed alive: "
                "the donation did not engage and every write copies the "
                "whole array", name)
        if self._paged:
            self.metrics.set_pages_donated("pages" not in kept_kinds)
            pages = [c for c, st in zip(self._cache, self._is_state) if not st]
            if self._spec_k:
                pages += [self._dk_pages, self._dv_pages]
            self.metrics.set_pages_row_major(all(
                list(p.format.layout.major_to_minor) == list(range(p.ndim))
                for p in pages))
        if self._stateful:
            self.metrics.set_state_donated("state" not in kept_kinds)
        # persist the compiled keys so a restarted engine can prewarm
        from paddle_tpu.tune import warmup as tune_warmup

        name = self._manifest_name()
        tune_warmup.record_compile(
            name, "prefill_chunk", save=False,
            chunk=int(dconf.prefill_chunk), page_size=int(dconf.page_size),
            max_context=int(dconf.max_context))
        tune_warmup.record_compile(
            name, "decode_step", save=False,
            max_slots=int(S), page_size=int(dconf.page_size),
            pages_per_slot=int(P))
        if self._spec_k:
            tune_warmup.record_compile(
                name, "verify_step", save=False,
                max_slots=int(S), spec_tokens=int(self._spec_k),
                page_size=int(dconf.page_size), pages_per_slot=int(P))
        path = tune_warmup.manifest_path(name)
        if path:
            try:
                tune_warmup.get_manifest(name, path).save()
            except Exception as e:
                ptlog.warning("warmup manifest save failed: %s", e)

    def _warmup_page_jits(self, consumed, chunk0, slots0, z) -> None:
        """The write-jits only a paged model has: the draft's and the verify
        step, the page implant and the copy-on-write."""
        import jax.numpy as jnp

        S, P = self.decode_config.max_slots, self._kv.pages_per_slot
        tables0 = jnp.zeros((S, P), jnp.int32)
        table0 = jnp.zeros((P + self._chunk_table_pad,), jnp.int32)
        if self._spec_k:
            k, v = self._dk_pages, self._dv_pages
            _, self._dk_pages, self._dv_pages = self._draft_prefill(
                self._draft_params, chunk0, z, z, table0, k, v, None)
            consumed("draft_prefill", k, v)
            k, v = self._dk_pages, self._dv_pages
            _, self._dk_pages, self._dv_pages = self._draft_step(
                self._draft_params, slots0, slots0, tables0, k, v, None)
            consumed("draft_step", k, v)
            k, v = self._cache
            vout, *self._cache = self._verify(
                self._params, jnp.zeros((S, self._spec_k + 1), jnp.int32),
                slots0, tables0, k, v)
            consumed("verify", k, v)
            jax.block_until_ready(vout)
        # scratch -> scratch: harmless. The implant serves handoff adoption
        # and host-tier promotes, the copy the prefix cache's copy-on-write
        old = list(self._cache)
        self._cache = [self._implant_page(
            c, z, jnp.zeros(c.shape[:1] + c.shape[2:], c.dtype)) for c in old]
        consumed("implant_page", *old)
        if self._prefix is not None:
            old = list(self._cache)
            self._cache = [self._copy_page(c, z, z) for c in old]
            consumed("copy_page", *old)
            if self._spec_k:
                k, v = self._dk_pages, self._dv_pages
                self._dk_pages = self._copy_page_d(k, z, z)
                self._dv_pages = self._copy_page_d(v, z, z)
                consumed("copy_page_d", k, v)

    def _manifest_name(self) -> str:
        """Manifest identity for this engine: model dims + the static
        decode-shape knobs (a config change must not replay stale keys).
        The depth it names is the cache's: the planes of its first array,
        which are the layers or, where a stack runs several passes, passes
        x layers; two engines that differ only in passes never share keys."""
        d = self.decode_config
        mc = self.model_cfg
        name = ("decode_L{l}_D{dm}_S{s}_P{p}_C{c}".format(
            l=self._cache[0].shape[0], dm=mc.get("d_model", 0),
            s=d.max_slots, p=d.page_size, c=d.prefill_chunk))
        if "family" in mc:
            name = f"{mc['family']}_{name}"
        if self._group is not None:
            # a group program is a different executable than the
            # single-device one — never replay the other's keys
            name += f"_tp{self._group.tp}"
        return name

    def prewarm(self) -> int:
        """Replay the persisted warmup manifest: when a previous process
        recorded this engine's prefill/step keys, compile them now —
        before the scheduler loop admits traffic — so a restart with a
        populated persistent compilation cache pays (near-)zero
        ``compile_seconds``. The jitted step stays compile-once:
        :meth:`decode_step_cache_size` is 1 after prewarm and stays 1
        under traffic. Returns the number of manifest keys replayed."""
        from paddle_tpu.tune import warmup as tune_warmup

        manifest = tune_warmup.get_manifest(self._manifest_name())
        keys = [e for e in manifest.entries()
                if e.get("kind") in ("prefill_chunk", "decode_step",
                                     "verify_step")]
        if not keys:
            return 0
        with prof.record_event("decode.prewarm"):
            self._warmup()
        prof.inc_counter("tune.prewarm.replayed_total", len(keys))
        runlog.emit("tune", phase="prewarm", engine="decode",
                    model=self._manifest_name(), keys=len(keys))
        return len(keys)

    def decode_step_cache_size(self) -> int:
        """Compiled-executable count inside the jitted decode step (−1
        when jax doesn't expose it). Flat after warmup ⇒ continuous
        batching never triggered a recompile — the shape-stability
        contract the acceptance test pins."""
        return (self._step._cache_size()
                if hasattr(self._step, "_cache_size") else -1)

    def prefill_cache_size(self) -> int:
        return (self._prefill._cache_size()
                if hasattr(self._prefill, "_cache_size") else -1)

    def verify_step_cache_size(self) -> int:
        """Compiled-executable count inside the jitted verify step: 0 with
        speculation off, and pinned at 1 under mixed traffic — the block
        shape ``[max_slots, spec_tokens + 1]`` is static config, so the
        verify step compiles exactly once ever."""
        if not self._spec_k:
            return 0
        return (self._verify._cache_size()
                if hasattr(self._verify, "_cache_size") else -1)

    @property
    def kv(self):
        return self._kv

    @property
    def group(self) -> Optional[ReplicaGroup]:
        """The tp replica group backing this engine (None = the classic
        single-device mode)."""
        return self._group

    @property
    def tp_degree(self) -> int:
        """Tensor-parallel degree of the backing program (1 = single
        device). Stamped into handoff payloads so cross-group adoption
        with a DIFFERENT degree degrades to re-prefill instead of
        implanting pages scattered for the wrong head partition."""
        return self._group.tp if self._group is not None else 1

    @property
    def prefix(self) -> Optional[RadixPrefixCache]:
        """The engine's radix prefix cache (None unless
        ``DecodeConfig.prefix_cache`` is set)."""
        return self._prefix

    @property
    def spec_tokens(self) -> int:
        """Draft tokens proposed per verify iteration (0 = speculation
        off: no draft model configured)."""
        return self._spec_k

    @property
    def admission(self) -> Optional[admission_mod.AdmissionController]:
        return self._admission

    def load(self) -> float:
        """Live work on this engine: active slots plus every parked or
        queued request. ``DecodeFleet._pick`` routes new work to the
        least-loaded healthy engine by this number. Read lock-free from
        any thread — ``len()`` is atomic under the GIL, and staleness
        only costs routing optimality, never correctness."""
        return float(len(self._active) + len(self._resume)
                     + len(self._pending_admit)
                     + len(self._pending_handoff)
                     + self._queue.qsize())

    # -- admission cost ----------------------------------------------------

    def _n_chunks(self, length: int) -> int:
        return max(1, -(-length // self.decode_config.prefill_chunk))

    def _request_cost(self, req) -> Optional[float]:
        """Per-token deadline prediction for the admission controller:
        chunks x chunk-EMA + max_new_tokens x step-EMA, plus the queued
        work ahead priced in iterations."""
        queued = self._queue.qsize() + len(self._pending_admit)
        return self.cost.estimate(
            self._n_chunks(len(req.prompt)), req.mnt,
            queue_cost=queued)

    # -- request intake ----------------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        deadline_s: Optional[float] = None,
        timeout: Optional[float] = None,
        tenant: Optional[str] = None,
        cls: Optional[str] = None,
    ) -> DecodeHandle:
        """Enqueue one generation request. ``prompt`` is a 1-D int token
        array; the result is a :class:`DecodeOutput` via the returned
        handle. Admission/backpressure semantics mirror
        :meth:`~paddle_tpu.serving.engine.ServingEngine.submit`."""
        if self._closed:
            raise EngineClosedError("engine is closed")
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        dconf = self.decode_config
        enforce(prompt.size >= 1, "prompt must be non-empty")
        enforce(max_new_tokens >= 1,
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        enforce(
            int(prompt.size) + max_new_tokens <= dconf.max_context,
            f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds max_context ({dconf.max_context})")
        now = time.monotonic()
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        if deadline_s is not None and deadline_s <= 0:
            self.metrics.record_timeout()
            raise DeadlineExceeded(
                f"deadline {deadline_s}s already expired at submit")
        deadline = None if deadline_s is None else now + deadline_s
        tname = tenant if tenant is not None else self._default_tenant
        tcfg = self._tenants.get(tname)
        rcls = cls if cls is not None else (
            tcfg.default_class if tcfg is not None
            else cfg_mod.flags().tenant_default_class)
        enforce(rcls in sched_mod.CLASSES,
                f"unknown priority class {rcls!r} "
                f"(expected one of {sched_mod.CLASSES})")
        if self._admission is None:
            enforce(tcfg is not None,
                    f"unknown tenant {tname!r} "
                    f"(configured: {sorted(self._tenants)})")
        req = _DecodeRequest(prompt, int(max_new_tokens),
                             self._n_chunks(int(prompt.size)),
                             deadline, now, tenant=tname, cls=rcls)
        req.rid = (f"{self.metrics.engine_label}-{_RID_SALT}-"
                   f"{next(self._rid_seq)}")
        if tracing.tracing_enabled():
            req.trace = tracing.SpanContext.new_trace()
            req.handle.trace = req.trace
            req.t_enqueue_pc = time.perf_counter()
        # token-latency waterfall opens at submit: TTFT includes queue wait
        waterfall.start(req.rid, time.perf_counter(),
                        engine=self.metrics.engine_label, tenant=req.tenant,
                        cls=req.cls)
        # journal BEFORE enqueue: the loop may start generating (and
        # journaling tokens) the instant the scheduler has the request
        self._j_admit(req)
        try:
            if self._admission is not None:
                self._admission.admit(req)
            else:
                self._queue.send(req, timeout=timeout)
        except ChannelClosedError:
            self._j_fin(req, "shed")
            waterfall.finish(req.rid, time.perf_counter(), "shed")
            raise EngineClosedError("engine is closed") from None
        except AdmissionRejected:
            self._j_fin(req, "shed")
            waterfall.finish(req.rid, time.perf_counter(), "shed")
            if req.trace is not None:
                self._finish_trace(req, time.perf_counter(), status="shed")
            raise
        self.metrics.record_submit()
        return req.handle

    def infer(self, prompt, max_new_tokens: int, **kwargs) -> DecodeOutput:
        """Synchronous decode: submit + wait."""
        return self.submit(prompt, max_new_tokens, **kwargs).result()

    # -- journal hooks (no-ops with journaling off) ------------------------

    def _j_admit(self, req: _DecodeRequest) -> None:
        if self._journal is not None and req.rid is not None:
            self._journal.log_admit(req.rid, req.prompt, req.mnt,
                                    req.generated, req.tenant, req.cls,
                                    trace=(req.trace.to_traceparent()
                                           if req.trace is not None
                                           else None))
            self.metrics.record_journal_records(1)

    def _j_tok(self, req: _DecodeRequest, tok: int) -> None:
        if self._journal is not None and req.rid is not None:
            self._journal.log_token(req.rid, tok)
            self.metrics.record_journal_records(1)

    def _j_fin(self, req: _DecodeRequest, reason: str) -> None:
        if self._journal is not None and req.rid is not None:
            self._journal.log_finish(req.rid, reason)
            self.metrics.record_journal_records(1)

    # -- completion paths (loop thread, except _expire) --------------------

    def _finish_trace(self, req: _DecodeRequest, t1_pc: float,
                      **attrs) -> None:
        if req.trace is None:
            return
        tracing.record_span(
            "serving.decode.request", req.t_enqueue_pc, t1_pc,
            context=req.trace, engine=self.metrics.engine_label,
            tenant=req.tenant, cls=req.cls,
            generated=len(req.generated), **attrs)

    def _wf_tokens(self, landed: "List[Tuple[_DecodeRequest, int]]",
                   t_pc: float, phase: str) -> None:
        """Book a turn's tokens, ``n`` of ``req`` for each ``(req, n)``
        landing at ``t_pc``, in the requests' waterfalls (one taking of
        its lock) and mirror the returned TTFT / per-token TPOT samples
        into the labeled histogram families, the TPOT samples once a
        class. Called BEFORE the tokens are appended — an append can
        finish the request, and a finished waterfall refuses further
        bookings."""
        landed = [(req, n) for req, n in landed if req.rid is not None]
        booked = waterfall.on_tokens_many(
            [(req.rid, n) for req, n in landed], t_pc, phase=phase)
        tpot: Dict[str, List[float]] = {}
        for (req, _), (ttft, samples) in zip(landed, booked):
            if ttft is not None:
                self.metrics.record_ttft(ttft, cls=req.cls)
            if samples:
                tpot.setdefault(req.cls, []).extend(samples)
        for cls, samples in tpot.items():
            self.metrics.record_tpot(samples, cls=cls)

    # -- the turn's account ------------------------------------------------

    def _clock(self) -> Optional[float]:
        """``perf_counter`` now, for :meth:`_booked`; with tracing off None,
        and no clock is read."""
        return time.perf_counter() if tracing.tracing_enabled() else None

    def _booked(self, since: Optional[float]) -> None:
        """Count the stretch from ``since`` to now as spent in the turn's
        bookings (``telemetry_seconds``): the registry writes of
        ``DecodeMetrics``, the waterfall, the cost model, the gauges of
        ``.publish``. They are gathered into a few stretches a turn so
        that a handful of clock reads times them; what a request books
        once in its life (admission, its finish) is not among them."""
        if since is not None and tracing.tracing_enabled():
            self._turn_telemetry += time.perf_counter() - since

    def _close_turn(self, step_span) -> None:
        """Put the turn's account on its ``serving.decode.step`` span, which
        ends the turn: ``cpu_seconds``, this thread's CPU time since the last
        such span closed (one clock read; a wait for the device sleeps, so
        it holds none), and ``telemetry_seconds``, what :meth:`_booked`
        counted over the same stretch. Wall time less the ``.wait`` spans
        less ``cpu_seconds`` is the loop thread off the CPU: another
        thread's interpreter lock, a named lock, the scheduler."""
        if not tracing.tracing_enabled():
            self._turn_cpu0 = None
            return
        now = time.thread_time()
        if self._turn_cpu0 is not None:
            step_span.set(cpu_seconds=now - self._turn_cpu0,
                          telemetry_seconds=self._turn_telemetry)
        self._turn_cpu0 = now
        self._turn_telemetry = 0.0

    def _expire(self, req: _DecodeRequest) -> None:
        """Deadline lapsed while queued (scheduler callback) or mid-
        generation (loop check)."""
        self.metrics.record_timeout()
        self.metrics.record_evict("deadline")
        self._j_fin(req, "deadline")
        waterfall.finish(req.rid, time.perf_counter(), "deadline")
        self._finish_trace(req, time.perf_counter(),
                           status="deadline_exceeded")
        req.handle._fail(DeadlineExceeded(
            f"request expired after "
            f"{time.monotonic() - req.t_submit:.3f}s "
            f"({len(req.generated)}/{req.mnt} tokens generated)"))

    def _release(self, req: _DecodeRequest) -> None:
        if req.slot is not None:
            self._kv.release_slot(req.slot)
            req.slot = None
        if req in self._active:
            self._active.remove(req)

    def _finish(self, req: _DecodeRequest, reason: str) -> None:
        self._release(req)
        self._j_fin(req, reason)
        self.metrics.record_evict(reason)
        if reason == "cancelled":
            self.metrics.record_cancel()
        latency = time.monotonic() - req.t_submit
        self.metrics.record_response(latency)
        waterfall.finish(req.rid, time.perf_counter(), reason)
        self._finish_trace(req, time.perf_counter(), status=reason)
        runlog.emit("decode_evict", reason=reason, tenant=req.tenant,
                    generated=len(req.generated),
                    engine=self.metrics.engine_label)
        req.handle._complete(DecodeOutput(
            tokens=np.asarray(req.generated, dtype=np.int32),
            finish_reason=reason,
            prompt_len=int(req.prompt.size),
            n_preemptions=req.n_preemptions))

    def _fail(self, req: _DecodeRequest, exc: BaseException) -> None:
        self._release(req)
        self._j_fin(req, "error")
        self.metrics.record_error()
        self.metrics.record_evict("error")
        waterfall.finish(req.rid, time.perf_counter(), "error")
        self._finish_trace(req, time.perf_counter(), status="error",
                           error=type(exc).__name__)
        req.handle._fail(exc)

    # -- the decode loop ---------------------------------------------------

    def _loop(self) -> None:
        try:
            self._loop_body()
        except BaseException as e:  # fail everything rather than hang
            ptlog.error("decode loop died: %r", e)
            for req in (list(self._active) + list(self._resume)
                        + list(self._pending_admit)
                        + [item[0] for item in self._pending_handoff]):
                try:
                    self._fail(req, RuntimeError(f"decode loop died: {e!r}"))
                except Exception:
                    pass
            raise

    def _in_flight(self) -> bool:
        return bool(self._active or self._resume or self._pending_admit
                    or self._pending_handoff)

    def _loop_body(self) -> None:
        dconf = self.decode_config
        loop = self._loop_trace
        while True:
            if self._killed:
                return  # abrupt death: kill() resolves the handles
            if self._drain_abort:
                self._force_drain()
                break
            # an engine with nothing in flight turns every idle_poll_s:
            # such a pass cancels its spans (the profiler annotation stays),
            # or an idle engine would fill the span store with empty passes
            with tracing.start_span("serving.decode.admit", parent=loop) as sp:
                self._sweep_cancel_deadline()
                self._probe_group()
                self._admit_handoffs()
                self._admit()
                if not self._in_flight():
                    sp.cancel()
            with tracing.start_span("serving.decode.step", parent=loop) as sp:
                did_promote = self._apply_promotes()
                did = self._decode_step() or did_promote
                if did:
                    sp.set(active=len(self._active))
                    self._close_turn(sp)
                else:
                    sp.cancel()
            if did:
                # after the span that ends the turn: the next turn's account
                with tracing.start_span("serving.decode.publish", parent=loop):
                    since = self._clock()
                    self._publish_cache()
                    self.metrics.set_active_slots(len(self._active))
                    self.metrics.set_load(self.load())
                    self.metrics.set_queue_depth(self._queue.qsize())
                    self._publish_digest()
                    self._booked(since)
                continue
            # idle: nothing to prefill or step — wait for work or drain out
            if self._in_flight():
                continue
            with tracing.start_span("serving.decode.idle", parent=loop) as sp:
                try:
                    req, ok = self._queue.recv(timeout=dconf.idle_poll_s)
                except TimeoutError:
                    sp.cancel()
                    continue
            if not ok:
                break  # closed AND drained, nothing in flight
            self._pending_admit.append(req)
        if self._prefix is not None:
            self._prefix.clear()  # drained: drop the tree's page refs
        self._promote_jobs.clear()
        self._promote_keys.clear()
        self._publish_digest()  # tree gone: publish the empty digest
        self.metrics.set_active_slots(0)
        self._publish_cache()

    def _sweep_cancel_deadline(self) -> None:
        now = time.monotonic()
        for req in list(self._active):
            if req.cancelled:
                self._finish(req, "cancelled")
            elif req.deadline is not None and now > req.deadline:
                self._release(req)
                self._expire(req)
        for pool in (self._resume, self._pending_admit):
            for req in list(pool):
                if req.cancelled:
                    pool.remove(req)
                    self._finish(req, "cancelled")
                elif req.deadline is not None and now > req.deadline:
                    pool.remove(req)
                    self._expire(req)
        for item in list(self._pending_handoff):
            req = item[0]
            if req.cancelled:
                self._pending_handoff.remove(item)
                self._finish(req, "cancelled")
            elif req.deadline is not None and now > req.deadline:
                self._pending_handoff.remove(item)
                self._expire(req)

    def _admit(self) -> None:
        """Fill free slots: preempted requests first (front of line), then
        parked arrivals, then fresh pops from the scheduler. A request
        that cannot get a slot parks; pages are granted lazily at
        prefill/step time."""
        while len(self._active) < self.decode_config.max_slots:
            resumed = False
            if self._resume:
                req = self._resume.popleft()
                resumed = True
            elif self._pending_admit:
                req = self._pending_admit.popleft()
            else:
                try:
                    req, ok = self._queue.recv(timeout=0)
                except TimeoutError:
                    return
                if not ok:
                    return  # closed and drained
            if req.cancelled:
                self._finish(req, "cancelled")
                continue
            slot = self._kv.acquire_slot()
            if slot is None:  # raced vs max_slots accounting; park
                (self._resume if resumed
                 else self._pending_admit).appendleft(req)
                return
            req.slot = slot
            req.phase = "prefill"
            req.seq = (np.concatenate([req.prompt,
                                       np.asarray(req.generated, np.int32)])
                       if req.generated else req.prompt)
            req.chunks_done = 0
            self._maybe_prefix_adopt(req)
            req.t_admit_pc = time.perf_counter()
            self._active.append(req)
            if resumed:
                self.metrics.record_resume()
                runlog.emit("decode_resume", tenant=req.tenant,
                            generated=len(req.generated),
                            engine=self.metrics.engine_label)
            else:
                self.metrics.record_slot_admit()
                runlog.emit("decode_admit", tenant=req.tenant,
                            prompt_len=int(req.prompt.size), mnt=req.mnt,
                            engine=self.metrics.engine_label)
                if req.trace is not None:
                    tracing.record_span(
                        "serving.decode.queue_wait", req.t_enqueue_pc,
                        req.t_admit_pc, parent=req.trace,
                        engine=self.metrics.engine_label)

    def _admit_handoffs(self) -> None:
        """Admit handed-off requests (serving.disagg): implant the
        transferred KV pages into this engine's page arrays and enter the
        decode phase directly — no re-prefill. Any failure (geometry
        mismatch, page-pool pressure, implant error) degrades to the
        proven resume path, which re-prefills ``prompt + generated``
        token-exactly — a bad transfer costs latency, never a request."""
        if not self._pending_handoff:
            return
        import jax.numpy as jnp

        dconf = self.decode_config
        page_shape = (self._cache[0].shape[:1] + self._cache[0].shape[2:])
        while (self._pending_handoff
               and len(self._active) < dconf.max_slots):
            req, payload = self._pending_handoff.popleft()
            if req.cancelled:
                self._finish(req, "cancelled")
                continue
            slot = self._kv.acquire_slot()
            if slot is None:  # raced vs max_slots accounting; park
                self._pending_handoff.appendleft((req, payload))
                return
            req.slot = slot
            n_pages = -(-int(payload.cur_len) // dconf.page_size)
            t0_adopt = time.perf_counter()
            ok = False
            # a draft model keeps its own page arrays, which the payload
            # does not carry — re-prefill fills both caches correctly.
            # A payload gathered under a DIFFERENT tp degree ran a
            # different partitioned program; adopting its pages verbatim
            # would splice two programs' numerics mid-sequence, so
            # cross-degree adoption degrades to re-prefill (the target
            # group recomputes the context self-consistently).
            if (not self._spec_k
                    and int(getattr(payload, "tp_degree", 1)) == self.tp_degree
                    and payload.page_size == dconf.page_size
                    and 0 < payload.cur_len <= dconf.max_context
                    and len(payload.k_pages) == n_pages
                    and len(payload.v_pages) == n_pages
                    and all(p.shape == page_shape
                            for p in payload.k_pages + payload.v_pages)):
                try:
                    if self._ensure_pages(req, int(payload.cur_len)):
                        table = self._kv.page_tables[req.slot]
                        for li in range(n_pages):
                            pid = jnp.int32(table[li])
                            self._cache[0] = self._implant_page(
                                self._cache[0], pid,
                                jnp.asarray(payload.k_pages[li],
                                            self._cache_dtype))
                            self._cache[1] = self._implant_page(
                                self._cache[1], pid,
                                jnp.asarray(payload.v_pages[li],
                                            self._cache_dtype))
                        ok = True
                except Exception as e:
                    ptlog.warning(
                        "handoff page adoption failed (%r); "
                        "re-prefilling request %s", e, req.rid)
            if not ok:
                self._release(req)
                req.phase = "queued"
                req.seq = None
                req.chunks_done = 0
                req.cur_len = 0
                self._resume.append(req)
                self.metrics.record_recover(1)
                continue
            # the adopted pages cover positions [0, cur_len); last_tok is
            # the token pending its KV write — exactly mid-decode state
            req.seq = None
            req.phase = "decode"
            req.cur_len = int(payload.cur_len)
            req.chunks_done = self._n_chunks(
                int(req.prompt.size) + len(req.generated))
            req.last_tok = int(payload.last_tok)
            self._kv.seq_lens[req.slot] = req.cur_len
            req.t_admit_pc = time.perf_counter()
            self._active.append(req)
            self.metrics.record_handoff_in()
            self.metrics.record_slot_admit()
            if req.trace is not None:
                tracing.record_span(
                    "serving.handoff.adopt", t0_adopt, time.perf_counter(),
                    parent=req.trace, engine=self.metrics.engine_label,
                    from_engine=payload.src, pages=n_pages, rid=req.rid)
            runlog.emit("handoff_adopted", rid=req.rid,
                        from_engine=payload.src, pages=n_pages,
                        engine=self.metrics.engine_label)

    def _maybe_prefix_adopt(self, req: _DecodeRequest) -> None:
        """Consult the radix prefix cache at slot assignment: adopt the
        longest cached page run of ``req.seq`` (capped at ``len(seq)-1`` —
        the final token must always prefill so its logits seed the first
        generated token) and skip the prefill chunks it fully covers.
        When the hit boundary is not chunk-aligned, the continuation chunk
        would write into shared pages, so the straddled pages are
        copied-on-write first (device-side page copy; the chunk then
        rewrites the straddled span with identical values into the private
        pages). If the pool cannot supply the CoW pages, the hit shrinks
        to the chunk-aligned boundary instead — never a partial adopt."""
        if self._prefix is None:
            return
        self.metrics.record_prompt_tokens(len(req.seq))
        ps = self.decode_config.page_size
        C = self.decode_config.prefill_chunk
        max_pages = min((len(req.seq) - 1) // ps, self._kv.pages_per_slot)
        if max_pages <= 0:
            return
        pages = self._prefix.match(req.seq, max_pages)
        m = len(pages)
        # hierarchical KV: the tree's true depth (pre-CoW-shrink) is the
        # promote frontier — when the host tier holds the NEXT page of
        # this prefix, enqueue an async promote so the next same-prefix
        # request hits in HBM. THIS request prefills as usual either way
        # (token-exact regardless of promotion timing).
        if (self._host_tier is not None and m < max_pages
                and self._host_tier.contains(req.seq, m + 1)):
            self._host_request_promote(req.seq, max_pages, trace=req.trace)
        while m > 0:
            c0 = (m * ps) // C
            lo = (c0 * C) // ps  # first logical page the next chunk touches
            n_cow = 0 if (m * ps) % C == 0 else m - lo
            if n_cow == 0 or self._kv.allocator.num_free >= n_cow:
                break
            m = lo  # drop the straddled tail; strictly decreasing
        if m <= 0:
            return
        import jax.numpy as jnp

        self._kv.adopt_pages(req.slot, pages[:m])
        c0 = (m * ps) // C
        cow_done = 0
        if (m * ps) % C != 0:
            for li in range((c0 * C) // ps, m):
                src, dst = self._kv.private_copy(req.slot, li)
                s, d = jnp.int32(src), jnp.int32(dst)
                self._cache = [self._copy_page(c, s, d) for c in self._cache]
                if self._spec_k:
                    self._dk_pages = self._copy_page_d(self._dk_pages, s, d)
                    self._dv_pages = self._copy_page_d(self._dv_pages, s, d)
                cow_done += 1
        req.chunks_done = c0
        self._kv.seq_lens[req.slot] = m * ps
        if cow_done:
            self.metrics.record_cow(cow_done)
        self.metrics.record_prefix_hit(m * ps, saved_chunks=c0)
        runlog.emit("decode_prefix_hit", hit_tokens=m * ps,
                    saved_chunks=c0, cow=cow_done,
                    engine=self.metrics.engine_label)

    # -- hierarchical KV host tier (serving.host_tier) ---------------------

    def _host_demote(self, req: _DecodeRequest, n_full: int) -> None:
        """Write-through demote: gather ``req``'s first ``n_full`` fully-
        written pages off-device and store them in the host tier. Called
        on the loop thread right after the radix insert, while the tree
        holds refs — the pages are immutable and cannot be recycled under
        a stale key. Also the crash-recovery write: with a SHARED pool,
        these bytes outlive this engine's kill(), so a restarted engine
        repopulates its tree from here after journal replay."""
        if self._host_tier is None:
            return
        import jax.numpy as jnp

        pages = self._kv.slot_pages(req.slot)[:n_full]
        wrote = 0
        bp = 0
        t0_demote = time.perf_counter()
        try:
            for i, p in enumerate(pages):
                if self._host_tier.contains(req.seq, i + 1):
                    continue  # shared prefix already demoted — dedup
                k = np.asarray(self._gather_page(self._cache[0],
                                                 jnp.int32(p)))
                v = np.asarray(self._gather_page(self._cache[1],
                                                 jnp.int32(p)))
                res = self._host_tier.put(
                    req.seq, i, k, v, engine=self.metrics.engine_label)
                wrote += res["added"]
                if res["evicted"]:
                    bp += 1
        except Exception as e:
            # demote is strictly best-effort: an injected stall/error (or
            # real host-memory pressure) must never fail the request —
            # the page simply stays HBM-only
            ptlog.warning("host-tier demote failed: %r; page stays "
                          "HBM-only", e)
        if wrote:
            self.metrics.record_host_demote(wrote)
            if req.trace is not None:
                tracing.record_span(
                    "serving.host_tier.demote", t0_demote,
                    time.perf_counter(), parent=req.trace,
                    engine=self.metrics.engine_label, pages=wrote)
        if bp:
            self.metrics.record_host_backpressure(bp)
        self.metrics.set_host_tier_bytes(self._host_tier.bytes_used,
                                         self._host_tier.max_bytes)

    def _host_request_promote(self, seq: np.ndarray, want_pages: int,
                              trace=None) -> None:
        """Enqueue an async promote of this prefix up to ``want_pages``
        pages; dedup by prefix digest so a storm of same-prefix requests
        enqueues one job. The hit is counted HERE (the routing-visible
        event), not at apply time. ``trace`` is the enqueueing request's
        span context — the applied promote parents its span there, so the
        fleet trace shows which request warmed the prefix."""
        ps = self.decode_config.page_size
        toks = np.asarray(seq[:want_pages * ps], np.int32)
        key = zlib.crc32(toks.tobytes()) & 0xFFFFFFFF
        if key in self._promote_keys:
            return
        self._promote_keys.add(key)
        self._promote_jobs.append((key, toks, want_pages, trace))
        self.metrics.record_host_hit()

    def _apply_promotes(self) -> bool:
        """Apply queued host-tier promotions on the loop thread, at most
        ``host_promote_pages_per_iter`` pages per iteration — off the
        request path (the enqueueing request prefilled normally) and
        bounded so promotion stays decode-p99-neutral.

        Each application re-checks the tree (``peek``) because the job
        may be stale: a concurrent admission may have prefilled the
        prefix already, or eviction may have shortened it since enqueue.
        Page ownership follows the loader-handoff discipline documented
        on ``PageAllocator.refcounts``: alloc (ref 1) → implant →
        ``insert`` refs for the tree (→ 2) → free the loader ref (→ 1,
        tree-owned). A CRC failure quarantines the host page and drops
        the job — the prefix simply stays cold and re-prefills."""
        if self._host_tier is None or not self._promote_jobs:
            return False
        import jax.numpy as jnp

        ps = self.decode_config.page_size
        budget = self.decode_config.host_promote_pages_per_iter
        did = False
        while budget > 0 and self._promote_jobs:
            key, toks, want, job_trace = self._promote_jobs.popleft()
            if self._prefix.max_pages is not None:
                # promoting past the tree's own size cap is wasted motion:
                # the insert would be trimmed right back out
                want = min(want, self._prefix.max_pages)
            tree_pages = self._prefix.peek(toks, want)
            d = len(tree_pages)
            if d >= want:  # stale: someone prefilled it meanwhile
                self._promote_keys.discard(key)
                continue
            t0 = time.perf_counter()
            try:
                got = self._host_tier.get(
                    toks, d, engine=self.metrics.engine_label)
            except HostPageCorrupt:
                # bit-flipped host page: quarantined by the pool; the
                # prefix stays cold and the next request re-prefills
                # token-exactly instead of trusting it
                self.metrics.record_host_quarantine()
                self._promote_keys.discard(key)
                continue
            except Exception as e:
                ptlog.warning("host-tier promote read failed: %r", e)
                self._promote_keys.discard(key)
                continue
            if got is None:  # evicted from the pool since enqueue
                self._promote_keys.discard(key)
                continue
            alloced = self._kv.allocator.alloc(1)
            if alloced is None:
                # never steal device pages from live traffic for a
                # warm-ahead; drop the job — the next admission re-probes
                self._promote_keys.discard(key)
                continue
            page = alloced[0]
            p = jnp.int32(page)
            self._cache[0] = self._implant_page(
                self._cache[0], p, jnp.asarray(got[0], self._cache_dtype))
            self._cache[1] = self._implant_page(
                self._cache[1], p, jnp.asarray(got[1], self._cache_dtype))
            self._prefix.insert(toks[:(d + 1) * ps], tree_pages + [page])
            self._kv.allocator.free([page])  # hand ownership to the tree
            budget -= 1
            did = True
            t1 = time.perf_counter()
            self.metrics.record_host_promote(t1 - t0)
            tracing.record_span(
                "serving.host_tier.promote", t0, t1,
                parent=job_trace if job_trace is not None else self._loop_trace,
                engine=self.metrics.engine_label, page=d)
            # progress guard: the insert can be trimmed straight back out
            # (size-cap eviction, allocator pressure). Re-enqueue only on
            # real depth growth — otherwise a capped tree and a warm pool
            # would promote-evict-promote forever and the loop never idles
            nd = len(self._prefix.peek(toks, want))
            if d < nd < want and self._host_tier.contains(toks, nd + 1):
                self._promote_jobs.append((key, toks, want, job_trace))
            else:
                self._promote_keys.discard(key)
        if did:
            self.metrics.set_pages(self._kv.pages_in_use,
                                   self._kv.pages_free)
        return did

    def _publish_digest(self) -> None:
        """Republish the routing digest when the tree changed. Loop-thread
        only; readers (DecodeFleet._pick, any thread) see an immutable
        frozenset swapped atomically under the GIL."""
        if not self.decode_config.prefix_digest or self._prefix is None:
            return
        v = self._prefix.digest_version
        if v != self._digest_seen:
            self._digest_seen = v
            self._digest_pub = self._prefix.digests()

    def prefix_digest(self) -> frozenset:
        """The engine's published prefix-digest set (empty unless
        ``DecodeConfig.prefix_digest``). Lock-free snapshot."""
        return self._digest_pub

    def prefix_match_depth(self, digests: "List[int]") -> int:
        """Longest prefix (in pages) of a prompt's digest chain (from
        :func:`serving.host_tier.prefix_digests`) this engine has cached.
        The routing score: fleets send each prompt to the deepest match."""
        pub = self._digest_pub
        depth = 0
        for dg in digests:
            if dg not in pub:
                break
            depth += 1
        return depth

    @property
    def host_tier(self) -> Optional[HostPagePool]:
        """The engine's host-RAM page pool (shared or private; None when
        the tier is off)."""
        return self._host_tier

    def _ensure_pages(self, req: _DecodeRequest, n_positions: int) -> bool:
        """Grow ``req``'s slot to ``n_positions``, evicting prefix-cache
        pages first and then preempting the most recently admitted OTHER
        request (LIFO) while the pool is short. The kv-cache deadlock
        guard guarantees a lone request can always grow to max_context
        once the tree is drained, so this terminates."""
        while not self._kv.ensure_capacity(req.slot, n_positions):
            if self._prefix is not None and self._prefix.evict(1) > 0:
                continue  # tree pages are cheaper to reclaim than preempts
            victim = next((r for r in reversed(self._active) if r is not req),
                          None)
            if victim is None:  # unreachable per the pool-size guard
                self._fail(req, RuntimeError(
                    "page pool exhausted with no preemption victim"))
                return False
            self._preempt(victim)
        return True

    def _preempt(self, victim: _DecodeRequest) -> None:
        """Evict ``victim`` on page exhaustion, keeping its generated
        prefix: it re-enters at the front of the line and re-prefills
        ``prompt + generated`` — greedy decode continues identically."""
        freed = self._kv.slot_page_count(victim.slot)
        self._release(victim)
        victim.phase = "queued"
        victim.seq = None
        victim.chunks_done = 0
        victim.cur_len = 0
        victim.n_preemptions += 1
        self._resume.append(victim)
        self.metrics.record_preempt()
        runlog.emit("decode_preempt", tenant=victim.tenant,
                    generated=len(victim.generated), pages_freed=freed,
                    engine=self.metrics.engine_label)

    def _append_token(self, req: _DecodeRequest, tok: int) -> None:
        """Host-side finish checks for one sampled token."""
        req.generated.append(tok)
        self._j_tok(req, tok)
        eos = self.decode_config.eos_id
        if eos is not None and tok == eos:
            self._finish(req, "eos")
        elif len(req.generated) >= req.mnt:
            self._finish(req, "length")
        else:
            req.last_tok = tok

    def _next_key(self):
        if self._rng is None:
            return None
        self._rng, key = jax.random.split(self._rng)
        return key

    def _prefill_some(self) -> bool:
        """Run up to ``prefill_chunks_per_iter`` chunks across prefill-
        phase requests (oldest first)."""
        import jax.numpy as jnp

        dconf = self.decode_config
        budget = dconf.prefill_chunks_per_iter
        progressed = False
        for req in list(self._active):
            if budget <= 0:
                break
            if req.phase != "prefill":
                continue
            C = dconf.prefill_chunk
            c = req.chunks_done
            n_chunks = self._n_chunks(len(req.seq))
            chunk_end = (c + 1) * C
            if not self._ensure_pages(req, min(chunk_end, len(req.seq))):
                continue
            last_chunk = (c == n_chunks - 1)
            # the chunk under the loop's trace: packing, the enqueue, the
            # last chunk's wait, landing. The request's own tree gets its
            # copy (enqueue to sync) once the chunk has gone through
            with tracing.start_span("serving.decode.prefill", chunk=c,
                                    last_chunk=last_chunk) as chunk_span:
                if self._chunk_attend_kernel:
                    # as a step's: the pages its kernel reads, up to the
                    # chunk's last query, of the table a gather would read
                    chunk_span.set(
                        attend_live_pages=-(-chunk_end // self.decode_config.page_size),
                        attend_table_pages=self._kv.pages_per_slot,
                        attend_page_bytes=self._page_bytes, attend_kernel=1)
                chunk = np.zeros((C,), np.int32)
                seg = req.seq[c * C:min((c + 1) * C, len(req.seq))]
                chunk[:len(seg)] = seg
                last = len(req.seq) - 1 - c * C
                t0 = time.perf_counter()
                try:
                    table_row = self._slot_ref(req.slot)
                    tok, extras = self._take(self._prefill(
                        self._params, jnp.asarray(chunk),
                        jnp.int32(c * C), jnp.int32(max(last, 0)),
                        table_row, *self._cache, self._next_key()))
                    if extras:
                        self._chunk_extras.append((self._chunk_seq, chunk_span, extras))
                    self._chunk_seq += 1
                    if self._spec_k:
                        # the draft's cache must cover the same prefix so its
                        # proposals attend real context (sampled token unused)
                        _, self._dk_pages, self._dv_pages = self._draft_prefill(
                            self._draft_params, jnp.asarray(chunk),
                            jnp.int32(c * C), jnp.int32(max(last, 0)),
                            table_row,
                            self._dk_pages, self._dv_pages, None)
                except Exception as e:
                    self._recover_request(req, e)
                    continue
                t1 = time.perf_counter()
                self.metrics.record_prefill_chunk(t1 - t0)
                if not last_chunk:
                    self.cost.observe_chunk(t1 - t0)
                    if req.trace is not None:
                        tracing.record_span("serving.decode.prefill", t0, t1,
                                            parent=req.trace, chunk=c,
                                            engine=self.metrics.engine_label)
                self._booked(t1)
                req.chunks_done = c + 1
                self._kv.seq_lens[req.slot] = min(chunk_end, len(req.seq))
                budget -= 1
                progressed = True
                if last_chunk:
                    # its sample is the one value the prefill path reads
                    # back, and not here: the device would stand idle from
                    # the chunk's end until the next call reaches it.
                    # _land_first_tokens reads it once more work is queued
                    # (and books the chunk for the cost model and the
                    # request's trace then, with its true end)
                    req.phase = "first_token"
                    req.first_tok = (tok, t0, c)
        return progressed

    def _land_first_token(self, req: _DecodeRequest, in_step: bool) -> None:
        """Read the sample of ``req``'s last prefill chunk (the one wait
        for the device in the prefill path) and move the request on to
        decoding: it joins the next step that is packed. A chunk that
        fails here is its own request's fault, unless a step is enqueued
        behind it (``in_step``): that step is lost too, and the error is
        its caller's to take down the step-fault ladder."""
        tok, t0, c = req.first_tok
        req.first_tok = None
        try:
            with tracing.start_span("serving.decode.prefill.wait"):
                tok = int(tok)
        except Exception as e:
            if in_step:
                raise
            self._recover_request(req, e)
            return
        t1 = time.perf_counter()
        self.cost.observe_chunk(t1 - t0)
        if req.trace is not None:
            tracing.record_span("serving.decode.prefill", t0, t1,
                                parent=req.trace, chunk=c,
                                engine=self.metrics.engine_label)
        # the final chunk's sample IS the next token after the
        # prefilled sequence — the first (or, after a resume, the
        # next) generated token
        self._wf_tokens([(req, 1)], t1, "prefill")
        self._booked(t1)
        if self._prefix is not None:
            # every fully-written page is immutable from here on
            # (decode writes land past len(seq)) — publish them
            n_full = len(req.seq) // self.decode_config.page_size
            if n_full:
                self._prefix.insert(
                    req.seq, self._kv.slot_pages(req.slot)[:n_full])
                # write-through demote: the same immutable pages,
                # while the tree holds refs (no recycle race)
                self._host_demote(req, n_full)
        req.phase = "decode"
        req.cur_len = len(req.seq)
        self._append_token(req, tok)
        # prefill role (serving.disagg): publish instead of
        # decoding here — unless that one sampled token already
        # finished the request (it left _active via _finish).
        # Draft-model engines keep their work local: the payload
        # carries only the target cache.
        if (self._handoff_sink is not None and not self._spec_k
                and req in self._active):
            self._publish_handoff(req)

    def _first_tokens_due(self) -> List[_DecodeRequest]:
        return [r for r in self._active if r.phase == "first_token"]

    def _land_first_tokens(self, due: List[_DecodeRequest],
                           in_step: bool = False) -> bool:
        due = [r for r in due if r.phase == "first_token"]
        for req in due:
            self._land_first_token(req, in_step)
        return bool(due)

    def _decode_step(self) -> bool:
        """One iteration's device work: the step over the decoding slots,
        and up to ``prefill_chunks_per_iter`` prefill chunks enqueued
        BEHIND it, before the loop waits for the step's tokens. The device
        then runs chunk and step back to back while the host lands tokens,
        admits and packs: it never waits for the host in an iteration that
        carries a chunk. A last chunk's sampled token is read one
        iteration on, once the next step is queued behind that chunk, or
        at the end of an iteration that queued no step.

        With a draft model configured the chunk goes first and its token
        is read at once (a draft-and-verify iteration is several calls
        with waits between them: nothing to queue work behind); slots with
        headroom for a full ``spec_tokens + 1`` block go through the
        draft-and-verify path; the rest (within ``spec_tokens`` positions
        of ``max_context``) fall back to the plain one-token step, which
        is always exact. Both substeps keep the scratch-page discipline:
        uninvolved slots get scratch table rows and position 0."""
        chunks: List[bool] = []  # one entry once the chunks went out

        def chunks_behind() -> None:
            chunks.append(self._prefill_some())

        did = False
        handled: set = set()
        if self._spec_k:
            chunks_behind()
            self._land_first_tokens(self._first_tokens_due())
            limit = self.decode_config.max_context - self._spec_k - 1
            spec = [r for r in self._active
                    if r.phase == "decode" and r.cur_len <= limit]
            if spec:
                handled = {id(r) for r in spec}
                did = self._verify_decode_step(spec) or did
        rest = [r for r in self._active
                if r.phase == "decode" and id(r) not in handled]
        if rest:
            did = self._plain_decode_step(
                rest, None if chunks else chunks_behind) or did
        if not chunks:
            # no step went out: nothing to queue the chunks behind, and
            # nothing to read their first tokens behind either
            ran_before = self._chunk_seq
            chunks_behind()
            did = self._land_first_tokens(self._first_tokens_due()) or did
            self._land_chunk_extras(ran_before)
        return did or chunks[0]

    def _plain_decode_step(self, decoding: List[_DecodeRequest],
                           chunks_behind=None) -> bool:
        """One jitted iteration over the given decode-phase slots. Slots
        that are inactive or mid-prefill get a scratch table row and
        position 0, so their garbage writes land on the scratch page (a
        state model is told to leave their states alone) and their
        outputs are ignored — no per-slot branching inside the step.
        ``chunks_behind`` is called once the step is enqueued, or not at
        all where no step goes out: it enqueues this iteration's prefill
        chunks. Its seconds are not the step's: the chunks book their
        own."""
        import jax.numpy as jnp

        if not decoding:
            return False
        S = self.decode_config.max_slots
        with tracing.start_span("serving.decode.model_step",
                                max_slots=S) as step_span:
            with tracing.start_span("serving.decode.model_step.pack") as pack_span:
                for req in list(decoding):
                    if req not in self._active:
                        # preempted as the victim of an earlier grow this
                        # iteration
                        decoding.remove(req)
                        continue
                    if not self._ensure_pages(req, req.cur_len + 1):
                        decoding.remove(req)
                # a later grow can also preempt an already-checked request
                decoding = [r for r in decoding if r in self._active]
                if not decoding:
                    pack_span.cancel()
                    step_span.cancel()
                    return False
                tokens = np.zeros((S,), np.int32)
                positions = np.zeros((S,), np.int32)
                for req in decoding:
                    tokens[req.slot] = req.last_tok
                    positions[req.slot] = req.cur_len
                refs = self._slot_refs(decoding)
                attend = None
                if self._paged:
                    # the pages the decoding slots hold rows in, of the
                    # table the gather reads whole
                    attend = {
                        "attend_live_pages": int(sum(
                            r.cur_len // self.decode_config.page_size + 1
                            for r in decoding)),
                        "attend_table_pages": S * self._kv.pages_per_slot,
                        "attend_page_bytes": self._page_bytes,
                        "attend_kernel": self._attend_kernel}
                    step_span.set(**attend)
            t0 = time.perf_counter()
            try:
                faults.inject(faults.DECODE_STEP,
                              engine=self.metrics.engine_label)
                with tracing.start_span("serving.decode.model_step.dispatch"):
                    nxt, extras = self._take(self._step(
                        self._params, jnp.asarray(tokens),
                        jnp.asarray(positions), _on_device(refs),
                        *self._cache, self._next_key()))
                ran_before = self._chunk_seq  # the chunks queued ahead of this step
                # the step is queued behind the last iteration's chunks: a
                # prompt whose last chunk was among them gets its first
                # token now, with this iteration's chunks queued too. A
                # chunk that fails here took the step with it
                due = self._first_tokens_due()
                behind = time.perf_counter()
                if chunks_behind is not None:
                    chunks_behind()
                behind = time.perf_counter() - behind
                self._land_first_tokens(due, in_step=True)
                with tracing.start_span("serving.decode.model_step.wait"):
                    nxt = np.asarray(nxt)
                    if extras:
                        self._land_extras(step_span, extras)
            except Exception as e:
                # a failed step loses this iteration's K/V writes for every
                # in-flight sequence
                self._step_faulted(e, "decode")
                return True
            t1 = time.perf_counter()
            seconds = t1 - t0 - behind
            # a chunk enqueued behind the step may have preempted one of
            # its slots, or lost the arrays and sent every request back to
            # the queue: their tokens are made again after the re-prefill
            decoding = [r for r in decoding if r in self._active]
            # the counts at the boundary, and the very float the metrics
            # get: a reader finds an iteration by it, without a tap
            step_span.set(active=len(decoding), new_tokens=len(decoding),
                          seconds=seconds)
            with tracing.start_span("serving.decode.model_step.land"):
                # the turn's bookings in one stretch, then the appends
                since = self._clock()
                self._land_chunk_extras(ran_before)
                self._note_step_ok()
                if attend is not None:
                    self.metrics.record_call_attrs(attend)
                self.metrics.record_step(len(decoding), S, seconds,
                                         len(decoding))
                self.cost.observe_step(seconds)
                self._wf_tokens([(req, 1) for req in decoding], t1, "decode")
                self._booked(since)
                for req in list(decoding):
                    req.cur_len += 1
                    self._kv.seq_lens[req.slot] = req.cur_len
                    self._append_token(req, int(nxt[req.slot]))
        return True

    def _verify_decode_step(self, spec: List[_DecodeRequest]) -> bool:
        """One draft-and-verify iteration: K sequential draft steps
        propose a block, one jitted verify step scores all K+1 positions
        against the target's paged cache, and each slot accepts the
        longest draft prefix matching the target's own greedy argmaxes
        plus the bonus token — at least 1, at most K+1 tokens per slot
        per iteration, token-exact vs sequential decode.

        Rollback is host-side only: rejected positions sit past the
        accepted frontier, masked until the next block overwrites them
        (both caches), so :meth:`PagedKVCache.trim` just returns the
        surplus pages granted for the block."""
        import jax.numpy as jnp

        K = self._spec_k
        S = self.decode_config.max_slots
        with tracing.start_span("serving.decode.verify") as verify_span:
            with tracing.start_span("serving.decode.verify.pack") as pack_span:
                for req in list(spec):
                    if req not in self._active:
                        # preempted as the victim of an earlier grow this
                        # iteration
                        spec.remove(req)
                        continue
                    if not self._ensure_pages(req, req.cur_len + K + 1):
                        spec.remove(req)
                spec = [r for r in spec if r in self._active]
                if not spec:
                    pack_span.cancel()
                    verify_span.cancel()
                    return False
                P = self._kv.pages_per_slot
                tokens = np.zeros((S,), np.int32)
                positions = np.zeros((S,), np.int32)
                tables = np.full((S, P), SCRATCH_PAGE, np.int32)
                for req in spec:
                    tokens[req.slot] = req.last_tok
                    positions[req.slot] = req.cur_len
                    tables[req.slot] = self._kv.page_tables[req.slot]
            t0 = time.perf_counter()
            try:
                faults.inject(faults.DECODE_STEP,
                              engine=self.metrics.engine_label)
                with tracing.start_span("serving.decode.verify.dispatch",
                                        model="draft"):
                    tables_j = jnp.asarray(tables)
                    pos = jnp.asarray(positions)
                    cur = jnp.asarray(tokens)
                    cols = []
                    for j in range(K):
                        cur, self._dk_pages, self._dv_pages = self._draft_step(
                            self._draft_params, cur, pos + j, tables_j,
                            self._dk_pages, self._dv_pages, None)
                        cols.append(cur)
                with tracing.start_span("serving.decode.verify.wait",
                                        model="draft"):
                    draft_mat = np.stack([np.asarray(c) for c in cols], 1)  # [S, K]
                with tracing.start_span("serving.decode.verify.dispatch",
                                        model="target"):
                    block = np.concatenate([tokens[:, None], draft_mat], 1)
                    out, *self._cache = self._verify(
                        self._params, jnp.asarray(block), pos, tables_j,
                        *self._cache)
                with tracing.start_span("serving.decode.verify.wait",
                                        model="target"):
                    out = np.asarray(out)
            except Exception as e:
                # same contract as the plain step: the iteration's K/V writes
                # (draft and target) are lost; recovery re-prefills from host
                self._step_faulted(e, "verify")
                return True
            t1 = time.perf_counter()
            seconds = t1 - t0
            with tracing.start_span("serving.decode.verify.land"):
                eos = self.decode_config.eos_id
                accepted = []  # (req, drafts accepted, tokens that land)
                for req in spec:
                    row = out[req.slot]
                    n_acc = 0
                    while (n_acc < K
                           and int(draft_mat[req.slot, n_acc]) == int(row[n_acc])):
                        n_acc += 1
                    # mirrors _append_token's finish conditions exactly:
                    # the block truncates at eos / budget, and the n tokens
                    # this iteration lands book n TPOT samples of dt/n —
                    # the speculation-aware accounting contract
                    n_land = min(n_acc + 1, req.mnt - len(req.generated))
                    if eos is not None:
                        for j in range(n_land):
                            if int(row[j]) == eos:
                                n_land = j + 1
                                break
                    accepted.append((req, n_acc, n_land))
                new_tokens = sum(n_land for _, _, n_land in accepted)
                # the turn's bookings in one stretch, then the appends
                since = self._clock()
                self._note_step_ok()
                self.metrics.record_verify_step(
                    len(spec), S, seconds, new_tokens,
                    drafts_proposed=len(spec) * K,
                    drafts_accepted=sum(n_acc for _, n_acc, _ in accepted))
                self.cost.observe_verify(seconds, new_tokens / len(spec))
                self._wf_tokens([(req, n_land) for req, _, n_land in accepted],
                                t1, "verify")
                self._booked(since)
                for req, n_acc, _ in accepted:
                    row = out[req.slot]
                    for j in range(n_acc + 1):
                        if req not in self._active:
                            break  # finished (eos / budget) mid-block
                        req.cur_len += 1
                        self._kv.seq_lens[req.slot] = req.cur_len
                        self._append_token(req, int(row[j]))
                    if req in self._active:
                        # roll back pages granted for rejected draft positions
                        self._kv.trim(req.slot, req.cur_len)
            verify_span.set(slots=len(spec), accepted=new_tokens,
                            seconds=seconds)
        return True

    # -- zero-loss recovery (serving.recovery) -----------------------------

    @property
    def breaker(self) -> CircuitBreaker:
        """This engine's health breaker: tripped on ``unhealthy_after``
        consecutive step faults; a DecodeFleet routes around OPEN
        breakers and spends half-open probes to re-admit."""
        return self._breaker

    def _flight_dump(self, reason: str) -> None:
        """Best-effort post-mortem hook: when a FlightRecorder is
        installed, dump a bundle capturing this engine's terminal state
        (span/runlog tails, held locks, page refcounts, breaker and
        host-tier snapshots). Never raises — observability must not
        alter the failure path it is recording."""
        try:
            from paddle_tpu.observability import flight_recorder as fr
            fr.maybe_dump(reason, engine=self)
        except Exception as e:
            ptlog.warning("flight-recorder dump failed: %r", e)

    def _note_step_ok(self) -> None:
        """A clean decode iteration: the device is serving again."""
        if not self._consec_faults and not self._breaker_dirty:
            return
        self._consec_faults = 0
        self._recover_prev_delay = 0.0
        self.metrics.set_consecutive_faults(0)
        self._breaker_dirty = False
        if self._breaker.record_success():
            runlog.emit("engine_recovered",
                        engine=self.metrics.engine_label)

    def _probe_group(self) -> None:
        """Group-backed engines only: per-member canary at
        ``group_probe_every_s`` cadence. ANY member fault is fatal for
        the WHOLE group — the jitted program spans every chip, so one
        sick member poisons every shard's collectives: trip the breaker
        and eject (migrate via the fleet when attached, else quarantine
        through the resume path). Healthy probes feed the shard-skew
        straggler watch, which localizes a slow chip by shard index."""
        if self._group is None:
            return
        now = time.monotonic()
        if now - self._last_probe < self.decode_config.group_probe_every_s:
            return
        self._last_probe = now
        try:
            times = probe_members(
                self._group, engine_label=self.metrics.engine_label)
        except Exception as e:
            self.metrics.record_member_fault()
            self._breaker_dirty = True
            runlog.emit("group_member_fault",
                        engine=self.metrics.engine_label,
                        group=self._group.name, error=repr(e),
                        in_flight=len(self._active))
            ptlog.error("group %s member fault (%r): ejecting whole group",
                        self._group.name, e)
            if self._rescue_sink is not None:
                self._migrate_out(e)
            else:
                self._breaker.trip()
                self._quarantine(e)
            return
        skew, flagged = self._straggler.observe(times)
        self.metrics.set_shard_skew(skew)
        for shard, secs in times.items():
            self.metrics.set_shard_probe_seconds(shard, secs)
        if flagged is not None:
            self.metrics.record_shard_straggler()
            runlog.emit("group_shard_straggler",
                        engine=self.metrics.engine_label,
                        group=self._group.name, shard=flagged,
                        skew=round(skew, 3))

    def _restore_lost_pages(self) -> bool:
        """A write-jit that fails AFTER consuming its donated inputs
        leaves the engine holding deleted arrays, and every later call
        would raise "Array has been deleted". Replace each lost array with
        zeros of the same shape, dtype and sharding and drop what indexed
        into the lost contents: the radix tree's device pages (the host
        tier repopulates it as it does after a restart). Live slots are
        the caller's to quarantine: their KV went with the arrays. Returns
        whether anything was lost."""
        def rebuilt(old):
            return self._zero_pages(
                old.shape, None if self._group is None else old.sharding,
                old.dtype)

        lost = [i for i, c in enumerate(self._cache) if c.is_deleted()]
        for i in lost:
            self._cache[i] = rebuilt(self._cache[i])
        if self._spec_k:
            for n in ("_dk_pages", "_dv_pages"):
                if getattr(self, n).is_deleted():
                    setattr(self, n, rebuilt(getattr(self, n)))
                    lost.append(n)
        if lost:
            if self._prefix is not None:
                self._prefix.clear()
            runlog.emit("decode_pages_rebuilt", arrays=len(lost),
                        engine=self.metrics.engine_label)
            ptlog.warning("engine %s lost %d page array(s) to a failed "
                          "call; rebuilt zeroed", self.metrics.engine_label,
                          len(lost))
        return bool(lost)

    def _step_faulted(self, exc: BaseException, what: str) -> None:
        """A decode or verify step raised: recover every live request, or
        with recovery off fail them all."""
        if self.decode_config.recovery:
            self._recover_step_fault(exc)
            return
        self._restore_lost_pages()
        runlog.emit("decode_step_error", error=repr(exc),
                    engine=self.metrics.engine_label)
        ptlog.error("%s step failed: %r", what, exc)
        for req in list(self._active):
            self._fail(req, exc)

    def _recover_step_fault(self, exc: BaseException) -> None:
        """A jitted decode step failed: only that iteration's KV writes
        are lost, and every live request is reconstructible from host
        state. Ladder: quarantine + re-admit (per-request budget) →
        after ``unhealthy_after`` consecutive faults, migrate everything
        to a healthy engine via the fleet's rescue sink. A fault inside
        recovery itself (DECODE_RECOVER) escalates one rung. Where the
        failed call had already consumed the page arrays, they are
        rebuilt first: the contract is the same, more was lost."""
        dconf = self.decode_config
        self._restore_lost_pages()
        self.metrics.record_step_fault()
        self._consec_faults += 1
        self.metrics.set_consecutive_faults(self._consec_faults)
        self._breaker_dirty = True
        tripped = self._breaker.record_failure()
        if tripped:
            self._flight_dump("engine_fault")
        runlog.emit("decode_step_error", error=repr(exc), recovering=True,
                    consecutive=self._consec_faults, tripped=tripped,
                    engine=self.metrics.engine_label)
        ptlog.warning(
            "decode step failed (%r); recovering %d request(s) "
            "(consecutive fault %d)", exc, len(self._active),
            self._consec_faults)
        try:
            faults.inject(faults.DECODE_RECOVER,
                          engine=self.metrics.engine_label)
            if (self._consec_faults >= dconf.unhealthy_after
                    and self._rescue_sink is not None):
                self._migrate_out(exc)
                return
            self._quarantine(exc)
        except Exception as rexc:
            # recovery itself faulted: escalate straight to migration
            # when a fleet can take the work, else the pre-recovery
            # fail-everything behavior (never hang the handles)
            ptlog.error("decode recovery failed: %r", rexc)
            if self._rescue_sink is not None:
                self._migrate_out(rexc)
            else:
                for req in list(self._active):
                    self._fail(req, rexc)
                self._kv.release_all()
            return
        # spread repeated quarantine cycles out (decorrelated so engines
        # sharing a sick host don't re-synchronize on the device)
        d = retry_mod.decorrelated_backoff(
            self._recover_prev_delay, dconf.recovery_base_delay_s,
            dconf.recovery_max_delay_s)
        self._recover_prev_delay = d
        time.sleep(d)

    def _quarantine(self, exc: BaseException) -> None:
        """Release every slot (the poisoned iteration's KV writes are
        untrusted) and send live requests back through the proven
        resume/re-prefill path — token-exact, per the preemption
        contract. A request past its lifetime recovery budget fails with
        a typed RetriesExhausted instead of looping."""
        requeued = 0
        for req in list(self._active):
            self._release(req)
            req.recoveries += 1
            if req.recoveries > self.decode_config.recovery_retries:
                self.metrics.record_retries_exhausted()
                err = RetriesExhausted(
                    f"request {req.rid}: recovery budget "
                    f"({self.decode_config.recovery_retries}) exhausted "
                    f"(last fault: {exc!r})", request_id=req.rid)
                err.__cause__ = exc
                self._fail(req, err)
                continue
            req.phase = "queued"
            req.seq = None
            req.chunks_done = 0
            req.cur_len = 0
            self._resume.append(req)
            requeued += 1
            runlog.emit(
                "request_recovered", rid=req.rid,
                recoveries=req.recoveries, generated=len(req.generated),
                engine=self.metrics.engine_label,
                trace_id=req.trace.trace_id if req.trace else None)
        self._kv.release_all()  # nothing survives the poisoned iteration
        if requeued:
            self.metrics.record_recover(requeued)

    def _recover_request(self, req: _DecodeRequest,
                         exc: BaseException) -> None:
        """A prefill chunk failed for ONE request (garbage confined to
        its slot's pages): quarantine just that request through the
        resume path, on the same lifetime budget. Does not count toward
        engine-level consecutive faults — a single poison prompt must
        exhaust its own budget, not condemn the engine. A chunk that
        failed after it consumed the page arrays took every slot's KV with
        it: that is the engine's fault, not the prompt's, and goes down
        the step-fault ladder."""
        if self._restore_lost_pages():
            self._step_faulted(exc, "prefill")
            return
        if not self.decode_config.recovery:
            self._fail(req, exc)
            return
        self.metrics.record_step_fault()
        self._release(req)
        req.recoveries += 1
        if req.recoveries > self.decode_config.recovery_retries:
            self.metrics.record_retries_exhausted()
            err = RetriesExhausted(
                f"request {req.rid}: recovery budget "
                f"({self.decode_config.recovery_retries}) exhausted "
                f"(last fault: {exc!r})", request_id=req.rid)
            err.__cause__ = exc
            self._fail(req, err)
            return
        req.phase = "queued"
        req.seq = None
        req.chunks_done = 0
        req.cur_len = 0
        self._resume.append(req)
        self.metrics.record_recover(1)
        runlog.emit("request_recovered", rid=req.rid,
                    recoveries=req.recoveries, generated=len(req.generated),
                    engine=self.metrics.engine_label,
                    trace_id=req.trace.trace_id if req.trace else None)

    def _drain_packets(self) -> List[RescuePacket]:
        """Drain every live request's host state (active slots, parked
        queues, and the scheduler backlog) into RescuePackets. Slots are
        released and each rid closes in the journal with "migrated" so a
        replay of THIS engine's journal won't resurrect them — the
        adopting engine journals them afresh."""
        drained: List[_DecodeRequest] = []
        for req in list(self._active):
            self._release(req)
            drained.append(req)
        while self._resume:
            drained.append(self._resume.popleft())
        while self._pending_admit:
            drained.append(self._pending_admit.popleft())
        while self._pending_handoff:
            drained.append(self._pending_handoff.popleft()[0])
        while True:
            try:
                req, ok = self._queue.recv(timeout=0)
            except Exception:
                break
            if not ok:
                break
            drained.append(req)
        self._kv.release_all()
        packets: List[RescuePacket] = []
        for req in drained:
            self._j_fin(req, "migrated")
            packets.append(RescuePacket(
                rid=req.rid or "", prompt=req.prompt, mnt=req.mnt,
                generated=list(req.generated), tenant=req.tenant,
                cls=req.cls, deadline=req.deadline, t_submit=req.t_submit,
                n_preemptions=req.n_preemptions, handle=req.handle,
                trace=req.trace, cancelled=req.cancelled))
        return packets

    def _migrate_out(self, exc: BaseException) -> None:
        """Declare this engine unhealthy: trip the breaker (the fleet
        stops routing here until a half-open probe succeeds) and hand
        every live request to the rescue sink for adoption elsewhere."""
        self._breaker.trip()
        self._breaker_dirty = True
        self._flight_dump("breaker_trip")
        packets = self._drain_packets()
        runlog.emit("engine_unhealthy", engine=self.metrics.engine_label,
                    error=repr(exc), in_flight=len(packets),
                    consecutive=self._consec_faults)
        ptlog.error(
            "engine %s unhealthy after %d consecutive step faults; "
            "migrating %d request(s)", self.metrics.engine_label,
            self._consec_faults, len(packets))
        adopted = self._rescue_sink(self, packets) if packets else 0
        self.metrics.record_migrate(adopted)
        self._consec_faults = 0
        self._recover_prev_delay = 0.0
        self.metrics.set_consecutive_faults(0)

    def adopt_rescue(self, packet: RescuePacket,
                     from_engine: Optional[str] = None) -> DecodeHandle:
        """Adopt a request drained from an unhealthy engine (or rebuilt
        by journal replay): generation continues token-exactly from its
        ``prompt + generated`` host state through the resume path. The
        client's original handle — when the packet carries one — is
        repointed here, so ``result()``/``cancel()`` keep working across
        the migration. Returns the (possibly fresh) handle."""
        if self._closed:
            raise EngineClosedError("engine is closed")
        t0_rescue = time.perf_counter()
        prompt = np.asarray(packet.prompt, np.int32).reshape(-1)
        req = _DecodeRequest(
            prompt, int(packet.mnt),
            self._n_chunks(int(prompt.size) + len(packet.generated)),
            packet.deadline, packet.t_submit or time.monotonic(),
            tenant=packet.tenant, cls=packet.cls)
        req.generated = [int(t) for t in packet.generated]
        req.n_preemptions = packet.n_preemptions
        req.cancelled = packet.cancelled
        req.rid = packet.rid or (
            f"{self.metrics.engine_label}-{_RID_SALT}-"
            f"{next(self._rid_seq)}")
        if packet.handle is not None:
            req.handle = packet.handle
            packet.handle._req = req  # cancel() must target the new req
        req.trace = packet.trace
        if req.trace is None and tracing.tracing_enabled():
            req.trace = tracing.SpanContext.new_trace()
        if req.trace is not None:
            req.handle.trace = req.trace
            req.t_enqueue_pc = time.perf_counter()
        # already satisfied (e.g. crash landed between the last token and
        # its fin record): complete without re-decoding a single token
        eos = self.decode_config.eos_id
        done_eos = (eos is not None and req.generated
                    and req.generated[-1] == eos)
        if done_eos or len(req.generated) >= req.mnt:
            reason = "eos" if done_eos else "length"
            self._j_admit(req)
            self._j_fin(req, reason)
            req.handle._complete(DecodeOutput(
                tokens=np.asarray(req.generated, dtype=np.int32),
                finish_reason=reason, prompt_len=int(req.prompt.size),
                n_preemptions=req.n_preemptions))
            return req.handle
        self._j_admit(req)
        self.metrics.record_submit()
        if req.trace is not None:
            tracing.record_span(
                "serving.rescue", t0_rescue, time.perf_counter(),
                parent=req.trace, engine=self.metrics.engine_label,
                from_engine=from_engine, rid=req.rid,
                generated=len(req.generated))
        if from_engine is not None:
            runlog.emit(
                "request_migrated", rid=req.rid, from_engine=from_engine,
                to_engine=self.metrics.engine_label,
                generated=len(req.generated),
                trace_id=req.trace.trace_id if req.trace else None)
        # front-of-line with the resumed: the request already waited once
        self._resume.append(req)
        self._queue.poke()  # an idle loop is parked in recv(idle_poll_s)
        return req.handle

    # -- disaggregated prefill/decode handoff (serving.disagg) -------------

    def _publish_handoff(self, req: _DecodeRequest) -> None:
        """Prefill-role exit: prefill just completed, so the slot's pages
        hold the request's full context — gather them off-device, release
        the slot, and hand the payload to the router's sink. Durability
        (journal handoff record + receiver ack) is the router's job; a
        sink failure degrades to decoding locally through the resume
        path, so a broken transfer never loses the request."""
        import jax.numpy as jnp

        from paddle_tpu.serving.disagg import HandoffPayload

        dconf = self.decode_config
        n_pages = -(-req.cur_len // dconf.page_size)
        # gather BEFORE _release: freed pages can be rewritten immediately
        pages = self._kv.slot_pages(req.slot)[:n_pages]
        k_pages = [np.asarray(self._gather_page(self._cache[0],
                                                jnp.int32(p)))
                   for p in pages]
        v_pages = [np.asarray(self._gather_page(self._cache[1],
                                                jnp.int32(p)))
                   for p in pages]
        payload = HandoffPayload(
            rid=req.rid or "", prompt=req.prompt,
            generated=list(req.generated), mnt=req.mnt,
            tenant=req.tenant, cls=req.cls, deadline=req.deadline,
            t_submit=req.t_submit, n_preemptions=req.n_preemptions,
            cur_len=int(req.cur_len), last_tok=int(req.last_tok),
            page_size=dconf.page_size, k_pages=k_pages, v_pages=v_pages,
            src=self.metrics.engine_label, handle=req.handle,
            trace=req.trace, tp_degree=self.tp_degree)
        self._release(req)
        try:
            self._handoff_sink(self, payload)
        except Exception as e:
            ptlog.warning("KV handoff failed (%r); request %s continues "
                          "decoding locally", e, req.rid)
            req.phase = "queued"
            req.seq = None
            req.chunks_done = 0
            req.cur_len = 0
            self._resume.append(req)
            return
        self.metrics.record_handoff_out()
        # with a per-engine WAL, close the rid here — the adopting engine
        # journals it afresh, so a replay of THIS file cannot resurrect a
        # request that now lives elsewhere. With a journal SHARED across
        # the fleet the rid must stay open (the adopter keeps appending
        # under it); the handoff/ack records carry the transfer state.
        if self._journal_owned:
            self._j_fin(req, "migrated")
        runlog.emit("handoff_published", rid=req.rid, pages=n_pages,
                    engine=self.metrics.engine_label)

    def adopt_handoff(self, payload,
                      from_engine: Optional[str] = None) -> DecodeHandle:
        """Adopt a prefilled request handed off by a prefill-role engine
        (:class:`~paddle_tpu.serving.disagg.HandoffPayload`): its KV
        pages are implanted on the loop thread and decode continues from
        ``cur_len`` without re-prefilling. The client's original handle
        is repointed here, mirroring :meth:`adopt_rescue`. Thread-safe;
        returns the (possibly fresh) handle."""
        self._refuse_unless_kv_pair("disaggregated handoff")
        if self._closed:
            raise EngineClosedError("engine is closed")
        prompt = np.asarray(payload.prompt, np.int32).reshape(-1)
        req = _DecodeRequest(
            prompt, int(payload.mnt),
            self._n_chunks(int(prompt.size) + len(payload.generated)),
            payload.deadline, payload.t_submit or time.monotonic(),
            tenant=payload.tenant, cls=payload.cls)
        req.generated = [int(t) for t in payload.generated]
        req.n_preemptions = payload.n_preemptions
        req.rid = payload.rid or (
            f"{self.metrics.engine_label}-{_RID_SALT}-"
            f"{next(self._rid_seq)}")
        if payload.handle is not None:
            req.handle = payload.handle
            payload.handle._req = req  # cancel() must target the new req
        req.trace = payload.trace
        if req.trace is None and tracing.tracing_enabled():
            req.trace = tracing.SpanContext.new_trace()
        if req.trace is not None:
            req.handle.trace = req.trace
            req.t_enqueue_pc = time.perf_counter()
        # the prefill worker's final-chunk sample may already satisfy the
        # request: complete without decoding (same as adopt_rescue)
        eos = self.decode_config.eos_id
        done_eos = (eos is not None and req.generated
                    and req.generated[-1] == eos)
        if done_eos or len(req.generated) >= req.mnt:
            reason = "eos" if done_eos else "length"
            self._j_admit(req)
            self._j_fin(req, reason)
            req.handle._complete(DecodeOutput(
                tokens=np.asarray(req.generated, dtype=np.int32),
                finish_reason=reason, prompt_len=int(req.prompt.size),
                n_preemptions=req.n_preemptions))
            return req.handle
        self._j_admit(req)
        self.metrics.record_submit()
        if from_engine is not None:
            runlog.emit(
                "request_handed_off", rid=req.rid, from_engine=from_engine,
                to_engine=self.metrics.engine_label,
                generated=len(req.generated),
                trace_id=req.trace.trace_id if req.trace else None)
        self._pending_handoff.append((req, payload))
        self._queue.poke()  # an idle loop is parked in recv(idle_poll_s)
        return req.handle

    def kill(self) -> None:
        """Simulate abrupt engine death (chaos/testing): no drain, no
        journal fin records — exactly the state a crashed process leaves
        behind. In-flight handles fail with :class:`EngineUnhealthy`;
        the journal file still names every incomplete request, which is
        what ``recovery.resume_incomplete()`` rebuilds from."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        # the "crash" happens NOW: nothing more reaches the WAL (in
        # particular no fin records for in-flight requests)
        journal, self._journal = self._journal, None
        self._killed = True
        # post-mortem first, while slots/refcounts still show the crash
        # state the bundle exists to explain
        self._flight_dump("kill")
        self._queue.close()
        self._thread.join(5.0)
        if journal is not None and self._journal_owned:
            journal.close()  # release the fd; on-disk bytes stay as-is
        exc = EngineUnhealthy(
            f"engine {self.metrics.engine_label} killed")
        drained = (list(self._active) + list(self._resume)
                   + list(self._pending_admit)
                   + [item[0] for item in self._pending_handoff])
        self._active.clear()
        self._resume.clear()
        self._pending_admit.clear()
        self._pending_handoff.clear()
        while True:
            try:
                req, ok = self._queue.recv(timeout=0)
            except Exception:
                break
            if not ok:
                break
            drained.append(req)
        self._kv.release_all()
        if self._prefix is not None:
            self._prefix.clear()
        # the host tier is deliberately NOT cleared: a shared pool is the
        # crash-recovery substrate — the restarted engine repopulates its
        # radix tree from it (the recovery ladder's adopt-from-host-tier
        # rung, between "re-prefill locally" and "migrate")
        self._promote_jobs.clear()
        self._promote_keys.clear()
        for req in drained:
            if not req.handle.done():
                req.handle._fail(exc)
        if self._admission is not None:
            admission_mod.uninstall(self._admission)

    # -- shutdown ----------------------------------------------------------

    # grace period for the loop to notice _drain_abort at an iteration
    # boundary once the close() timeout has been overrun
    _DRAIN_ABORT_GRACE_S = 5.0

    def _force_drain(self) -> None:
        """The close() drain deadline passed: complete every in-flight
        request with the tokens it has (``finish_reason="drain_timeout"``)
        instead of leaving its handle hanging forever, then prove no KV
        page leaked."""
        drained = (list(self._active) + list(self._resume)
                   + list(self._pending_admit)
                   + [item[0] for item in self._pending_handoff])
        self._resume.clear()
        self._pending_admit.clear()
        self._pending_handoff.clear()
        while True:
            try:
                req, ok = self._queue.recv(timeout=0)
            except Exception:
                break
            if not ok:
                break
            drained.append(req)
        for req in drained:
            self._finish(req, "drain_timeout")
        if self._prefix is not None:
            self._prefix.clear()
        self._kv.assert_no_leaks()

    def close(self, timeout: Optional[float] = None) -> List[str]:
        """Graceful drain: stop intake, run every accepted request to
        completion, then stop the loop. The drain deadline is ENFORCED:
        when ``timeout`` is overrun, the loop force-finishes stragglers
        with ``finish_reason="drain_timeout"`` (partial tokens returned,
        no handle left waiting forever) and the page-leak check still
        runs. Returns unjoined thread names (empty = clean)."""
        with self._close_lock:
            if self._closed:
                return []
            self._closed = True
        self._queue.close()
        self._thread.join(timeout)
        if timeout is not None and self._thread.is_alive():
            ptlog.error(
                "DecodeEngine.close: drain exceeded %ss; force-finishing "
                "in-flight requests with finish_reason=drain_timeout",
                timeout)
            self._drain_abort = True
            self._thread.join(self._DRAIN_ABORT_GRACE_S)
        unjoined = [self._thread.name] if self._thread.is_alive() else []
        if unjoined:
            ptlog.error("DecodeEngine.close: loop failed to join within %s",
                        timeout)
        if self._journal is not None and self._journal_owned:
            self._journal.close()
        if self._admission is not None:
            admission_mod.uninstall(self._admission)
        return unjoined

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "DecodeEngine":
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> bool:
        self.close()
        return False
