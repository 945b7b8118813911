"""Batched serving engine: continuous batching over fixed decode slots.

The paper's deployment is single-stream edge decode (batch = 1, token by
token, weights in ROM). This engine generalizes it to the production mesh:

  * ``max_slots`` concurrent sequences share one jitted ``decode_step`` whose
    KV cache is the paper's "distributed SRAM" — context-sharded over the
    ``model`` axis, fp8 payload (C2/C3). Every tick decodes one token for
    every active slot (B = max_slots, static shapes — no recompiles).
  * **continuous batching**: slots free as sequences finish and are refilled
    from the queue mid-flight; per-slot positions drive the cache scatter and
    attention masks.
  * **KV backends** (``kv=`` a `serving.kv.KVBackend`): `DenseKV` reserves a
    contiguous (L, B, H, max_len, D) cache row per slot — the paper's fixed
    on-chip SRAM budget. `PagedKV` replaces it with the shared `PagePool`
    (serving/paged_kv.py): slots own block tables of fp8 pages and the
    backend hands the jitted decode a `PagedKVState`, so ``Model.decode_step``
    reads pages through the block tables directly — the Pallas
    ``paged_flash_decode`` kernel on TPU (scalar-prefetch block tables, pages
    stream HBM→VMEM), the XLA gather reference on CPU (op-for-op the dense
    math → dense and paged produce token-identical greedy outputs). Paged
    mode unlocks admission control, preemption and the prefix cache
    (gateway/). There is ONE tick/decode path; the backend only changes what
    state pytree crosses the jit boundary.
  * **scheduling** is delegated to a pluggable scheduler (default FIFO via
    `gateway.scheduler.Scheduler`): priority classes, per-request deadlines
    (EDF), admission control backed by the backend's page accounting and
    preemption of low-priority slots when the pool runs dry — the preempted
    request re-enters the queue with its generated tokens as prompt, so
    resumed decode replays prefill but loses no tokens.
  * **prefix cache**: with ``prefix_cache=True`` (paged only), committed
    prompt pages are shared copy-on-write across requests via a token trie
    (gateway/prefix_cache.py); shared spans skip prefill ticks entirely.
  * **prefill** is either ``token`` mode — feed the prompt through
    decode_step one token at a time (the paper's own prefill: "executes all
    operations token-by-token, eliminating the prefill/decoding
    distinction") — or ``batched`` mode, a bucketed full-sequence prefill
    per request that splices the resulting cache rows into the live batch
    (beyond-paper; amortizes long prompts).
  * **chunked prefill** (``prefill_chunk=C``, batched GQA only): a long
    prompt's batched prefill is split into ≤C-token segments, at most one
    segment per tick while anything is decoding (the scheduler's
    ``plan_prefill`` budget, most-urgent first), so co-resident decode slots
    keep emitting during another request's prefill — SLO isolation against
    head-of-line blocking. Chunk i resumes at ``pos_offset = i·C`` with the
    previously committed chunks as ``prefix_kv`` (the same resume path a
    prefix-cache hit uses), on both KV backends; outputs are token-identical
    to unchunked prefill.
  * **sampling** comes from each request's frozen `SamplingParams`
    (serving/api.py): greedy, temperature, per-slot top-k, top-p nucleus
    mass and an optional per-request seed whose draws depend only on
    (seed, tokens generated) — reproducible regardless of co-scheduled
    traffic. All vector arguments, so one request's narrow top-k/top-p
    never leaks into its batch neighbours.
  * **speculative decoding** (``spec_decode=True`` + per-request
    ``SamplingParams.spec_k``): eligible slots (greedy or seeded) draft up
    to k tokens per tick from their own history (cycle extrapolation +
    n-gram prompt lookup, serving/spec.py) and one jitted
    ``Model.verify_step`` — a ``lax.scan`` of the exact ``decode_step``
    graph — scores all k+1 positions with bit-identical logits. The engine
    commits only the accepted span (``PagePool.write_span`` / sliced dense
    writes), so rejected drafts never reach storage and outputs are
    token-identical to ``spec_decode=False``. Draft memory is
    opportunistic: widths trim before they would evict a prefix page or
    preempt a neighbour.
  * **events**: ``on_token / on_done / on_admit / on_preempt / on_expire``
    hooks fire inline; the gateway (gateway/gateway.py) wires them to
    streaming callbacks and the metrics registry.
  * **multi-tenant adapters** (``adapters=`` an `serving/adapters/
    AdapterServing`): each request may name an ``adapter_id`` — a frozen
    ternary QLoRA fine-tune from the registry. Resident adapters are stacked
    on device and gathered per slot inside the jitted decode (SGMV), so one
    tick serves slots running different fine-tunes; the scheduler prefers
    co-scheduling warm-adapter requests (never violating priority/EDF) and
    the SRAM-budget cache pins adapters while their requests are in flight.

SSM/hybrid archs serve through the same interface (their "cache" is the
recurrent state; positions only gate the attention blocks, if any). Paged KV
requires a GQA KV cache — ssm/hybrid/MLA families use `DenseKV`.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import Model
from repro.obs.names import SERVE_SPANS
from repro.serving.api import RequestSpec, SamplingParams, coerce_submit
from repro.serving.kv import KVBackend, as_backend
from repro.serving.obs.tracer import NULL_TRACER, CompileWatch, Tracer
from repro.serving.spec import (AdaptiveSpecK, accepted_prefix, plan_emit,
                                propose, quantize_width)

Params = Any
NEG_INF = -1e30


# Jitted prefill entry points, module-level so the compile cache is shared
# across engines of the same model (tests/benches build many). The resume
# variant takes ``off`` as a *traced* scalar and the prefix padded to a
# power-of-two bucket, so every chunk of a chunked prefill with the same
# (token-bucket, prefix-bucket) shape pair reuses one compiled graph —
# without this, each chunk's unique prefix length recompiles the prefill
# and a "chunk" costs more than the monolithic prompt it replaced.
def _fresh_prefill(model, params, toks, max_len, aidx):
    kwargs = {} if aidx is None else {"adapter_idx": aidx}
    return model.prefill(params, {"tokens": toks}, max_len, **kwargs)


def _resume_prefill(model, params, toks, max_len, off, prefix_kv, aidx):
    kwargs = {} if aidx is None else {"adapter_idx": aidx}
    return model.prefill(params, {"tokens": toks}, max_len, pos_offset=off,
                         prefix_kv=prefix_kv, **kwargs)


def _prefill_jits(model):
    """(fresh, resume) jitted wrappers, cached on the model instance (Model
    is an unhashable dataclass, so it can't ride as a jit static arg)."""
    fns = getattr(model, "_serving_prefill_jits", None)
    if fns is None:
        import functools
        fns = (jax.jit(functools.partial(_fresh_prefill, model),
                       static_argnums=(2,)),
               jax.jit(functools.partial(_resume_prefill, model),
                       static_argnums=(2,)))
        model._serving_prefill_jits = fns
    return fns


def _specs(args):
    """``args`` with each array replaced by its shape, dtype and placement
    (committed arrays only: an uncommitted one goes wherever the call puts
    it): enough to lower the call again after it has consumed its arrays."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None)
        if isinstance(a, jax.Array) else a, args)


class _AotCall:
    """An ahead-of-time compiled executable behind the dispatch interface.

    ``name``/``last_compiled`` mirror `CompileWatch`, so `_dispatch`'s
    profiler probe attributes wall time to the same record
    `ProfileRegistry.register_compiled` created at warmup and never flags
    the call as a compile. ``drop`` names argument positions that were
    static at lower time — an AOT executable is called *without* its baked
    statics, while the jit path the caller may fall back to still wants
    them, so both paths share one argument tuple."""
    __slots__ = ("_compiled", "name", "last_compiled", "_drop")

    def __init__(self, compiled, name: str, drop=()):
        self._compiled = compiled
        self.name = name
        self.last_compiled = False
        self._drop = frozenset(drop)

    def __call__(self, *args, **kwargs):
        live = [a for i, a in enumerate(args) if i not in self._drop]
        return self._compiled(*live, **kwargs)


@dataclasses.dataclass
class Request:
    """A submitted request: the immutable `RequestSpec`/`SamplingParams`
    pair plus the engine's mutable bookkeeping. ``deadline_s`` is the
    absolute wall-clock deadline the scheduler orders by, derived once from
    ``spec.deadline_ms`` (relative to submit) — the only place the deadline
    unit conversion happens."""
    uid: int
    prompt: List[int]
    spec: RequestSpec = dataclasses.field(default_factory=RequestSpec)
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    deadline_s: Optional[float] = None   # absolute time.time() deadline (SLO)
    # filled by the engine
    max_new_tokens: int = -1             # mutable budget (clamped to max_len)
    state: str = "queued"  # queued|running|preempted|done|cancelled|expired|rejected
    output: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_last: float = 0.0
    t_done: float = 0.0
    n_preempts: int = 0
    stall_s: float = 0.0            # wall time this slot's decode sat blocked
                                    # behind another slot's prefill (SLO
                                    # attribution carves it out of decode)
    prefix_hit_tokens: int = 0      # prompt tokens served from the prefix cache
    prefill_ticks: int = 0          # decode ticks spent consuming the prompt
    prefill_chunks: int = 0         # chunked-prefill segments run for this req
    spec_drafted: int = 0           # draft tokens proposed for this request
    spec_accepted: int = 0          # draft tokens accepted (free extra tokens)
    _seq: int = 0                   # scheduler arrival order

    def __post_init__(self):
        if self.max_new_tokens < 0:
            self.max_new_tokens = self.spec.max_new_tokens
        if (self.deadline_s is None and self.spec.deadline_ms is not None
                and self.t_submit):
            self.deadline_s = self.t_submit + self.spec.deadline_ms / 1e3

    # spec/sampling views (kept as properties so engine internals and the
    # scheduler read one field of truth)
    @property
    def temperature(self) -> float:
        return self.sampling.temperature

    @property
    def top_k(self) -> int:
        return self.sampling.top_k

    @property
    def top_p(self) -> float:
        return self.sampling.top_p

    @property
    def seed(self) -> Optional[int]:
        return self.sampling.seed

    @property
    def eos_id(self) -> Optional[int]:
        return self.spec.eos_id

    @property
    def priority(self) -> int:
        return self.spec.priority

    @property
    def adapter_id(self) -> Optional[str]:
        return self.spec.adapter_id

    @property
    def ttft_s(self) -> float:
        return self.t_first - self.t_submit

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


@dataclasses.dataclass
class EngineStats:
    ticks: int = 0
    tokens_out: int = 0
    completed: int = 0
    preemptions: int = 0
    cancelled: int = 0
    expired: int = 0
    prefix_hit_tokens: int = 0
    prefill_chunks: int = 0       # chunked-prefill segments run
    decode_stall_s: float = 0.0   # wall time decode slots waited on prefill
    spec_ticks: int = 0           # ticks that ran the multi-token verify
    decode_steps: int = 0         # decode-executable dispatches (``ticks``
                                  # also counts prefill-only and verify ticks)
    spec_drafted: int = 0         # draft tokens proposed across all requests
    spec_accepted: int = 0        # draft tokens accepted (extra tokens/tick)
    wall_s: float = 0.0
    # observability: per-phase self-time (ms) accumulated across ticks —
    # the phases of ``repro.obs.names.PHASES`` (schedule / admit / prefill /
    # kv_write / decode / sample / commit / emit / wait_device, ...);
    # nested phases subtract, so values sum to the timed wall
    phase_ms: Dict[str, float] = dataclasses.field(default_factory=dict)
    tick_gap_ms_sum: float = 0.0  # host time between device dispatches
    tick_gaps: int = 0
    # host gaps observed while a previous tick's dispatched work was still
    # unmaterialized (async runtime pipelining): the device queue is
    # non-empty, so this host time is *overlapped* with device compute and
    # excluded from the idle-gap numerator above
    tick_gap_overlap_ms_sum: float = 0.0
    tick_gaps_overlap: int = 0
    tick_wall_ms_sum: float = 0.0  # total tick() wall time (gap denominator)
    jit_compiles: int = 0         # jit cache growth events (CompileWatch)
    warmup_compiles: int = 0      # executables built ahead of traffic by
                                  # warmup_aot (jit_compiles resets to 0 after
                                  # warmup, so serve-time recompiles stand out)
    aot_fallbacks: int = 0        # AOT prefill calls that fell back to the
                                  # jit path on an input-placement mismatch
    # tiered memory hierarchy (ServeEngine(tiered=...)): spilled-then-
    # re-admitted prefix KV and scheduler-prefetch effectiveness
    prefix_readmits: int = 0      # spilled prefix spans pulled back on-device
    prefix_readmit_tokens: int = 0
    prefetch_hits: int = 0        # prefetched adapters/prefixes a placement used
    kv_spilled_pages: int = 0     # prefix KV pages demoted to host instead of dropped

    @property
    def tps(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s else 0.0

    @property
    def spec_accept_rate(self) -> float:
        """Draft hit rate: accepted / proposed (0.0 when nothing drafted)."""
        return self.spec_accepted / self.spec_drafted if self.spec_drafted \
            else 0.0

    @property
    def tick_gap_ms_mean(self) -> float:
        """Mean host-side bubble between device dispatches — the feedback
        signal the ROADMAP's async disaggregated runtime will shrink."""
        return self.tick_gap_ms_sum / self.tick_gaps if self.tick_gaps \
            else 0.0

    @property
    def host_overhead_frac(self) -> float:
        """Host-side dispatch gaps as a fraction of total tick wall time —
        the %-of-tick the device sits idle on host bookkeeping. This is the
        single number the async disaggregated runtime has to drive to ~0."""
        return self.tick_gap_ms_sum / self.tick_wall_ms_sum \
            if self.tick_wall_ms_sum else 0.0

    def phase_breakdown_ms(self) -> Dict[str, float]:
        """Mean self-time per phase per tick (ms)."""
        n = max(self.ticks, 1)
        return {k: round(v / n, 4) for k, v in sorted(self.phase_ms.items())}


class _Phase:
    """Phase timer + trace spans. Accumulates *self-time* into
    ``stats.phase_ms`` — a nested phase's time is subtracted from its
    parent (via the engine's running self-time total), so the per-phase
    breakdown sums to tick wall time instead of double-counting. Opens a
    ``serve.<phase>`` span on the ``jax.profiler`` clock (nothing is
    recorded unless a profiler trace is running) and, when the engine has
    an enabled ``Tracer``, a span in its Chrome trace."""
    __slots__ = ("eng", "name", "t0", "self0", "span", "ann")

    def __init__(self, eng: "ServeEngine", name: str):
        self.eng = eng
        self.name = name

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(SERVE_SPANS[self.name])
        self.ann.__enter__()
        self.span = self.eng.trace.span(self.name, pid=self.eng._tpid)
        self.span.__enter__()
        self.self0 = self.eng._phase_self_total
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = (time.perf_counter() - self.t0) * 1e3
        nested = self.eng._phase_self_total - self.self0
        own = max(dt - nested, 0.0)
        pm = self.eng.stats.phase_ms
        pm[self.name] = pm.get(self.name, 0.0) + own
        self.eng._phase_self_total = self.self0 + nested + own
        self.span.__exit__(*exc)
        self.ann.__exit__(*exc)
        return False


@dataclasses.dataclass
class PendingTick:
    """One dispatched-but-unmaterialized tick: the device-side sample array
    plus the host bookkeeping deferred until ``tick_finish``. Produced by
    ``tick_begin``; the async runtime holds at most ``depth`` of these so the
    device stays a tick ahead, while the sync ``tick()`` finishes each one
    immediately (the deque is empty between ticks — zero behavior change).

    ``emits`` lists (slot, request, begin-time position) triples whose token
    for this tick lives in ``nxt_dev`` — the position is captured at begin
    because a later pipelined begin advances ``pos`` before this tick's
    finish runs, and the max_len done-check must see this tick's value.
    ``done_slots`` are slots whose request is predictably complete after
    that emission (budget / max_len — eos is only discovered at finish), so
    the next ``tick_begin`` must not decode them again."""
    active: List[int] = dataclasses.field(default_factory=list)
    emits: List[Tuple[int, "Request", int]] = dataclasses.field(
        default_factory=list)
    done_slots: set = dataclasses.field(default_factory=set)
    nxt_dev: Optional[jax.Array] = None
    gap_ms: Optional[float] = None
    verify_width: int = 1
    begin_s: float = 0.0          # host wall spent inside tick_begin
    busy0: float = 0.0
    tokens0: int = 0
    ticks0: int = 0


class ServeEngine:
    def __init__(self, model: Model, params: Params, *, max_slots: int = 8,
                 max_len: int = 1024, prefill: str = "token", seed: int = 0,
                 prefill_chunk: Optional[int] = None,
                 kv: Union[str, KVBackend, None] = None, page: int = 64,
                 n_pages: Optional[int] = None, prefix_cache: bool = False,
                 spec_decode: bool = False, spec_ngram: int = 3,
                 spec_adaptive: bool = False,
                 scheduler=None, adapters=None, tiered=None,
                 prefetch: bool = False,
                 tracer: Optional[Tracer] = None, profiler=None):
        assert model.mode in ("serve", "qlora")
        assert prefill_chunk is None or prefill_chunk >= 1, \
            "prefill_chunk must be >= 1 tokens (or None for monolithic prefill)"
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.prefill_mode = prefill
        # chunked prefill (SLO isolation): batched prefill of a long prompt is
        # split into <= prefill_chunk-token segments, one per tick, so decode
        # slots keep emitting while another request's prompt is in flight.
        # Chunk i resumes at pos_offset = i*C with the previous chunks'
        # committed cache as prefix_kv (the prefix-cache resume path). Only
        # meaningful with prefill="batched" on GQA families — token mode is
        # already maximally chunked (one prompt token per tick).
        self.prefill_chunk = prefill_chunk
        # speculative decoding (master switch; per-request width is
        # SamplingParams.spec_k): each eligible slot drafts up to spec_k
        # tokens per tick by n-gram prompt lookup over its own history and a
        # single jitted multi-token verify scores all of them — accepted
        # drafts commit in bulk (PagePool.write_span / sliced dense writes),
        # rejected ones never touch the cache, so greedy outputs are
        # token-identical to spec_decode=False. GQA families only (the
        # verify shares the mid-sequence prefill's attention restriction).
        self.spec_decode = spec_decode
        self.spec_ngram = spec_ngram
        # adaptive draft width (spec_adaptive=True): a per-slot EWMA of the
        # live accept rate shrinks/grows the next tick's draft width within
        # [0, SamplingParams.spec_k] — width never changes *which* tokens
        # are emitted (rejected drafts are discarded), only how many drafts
        # each verify tick risks, so token identity is preserved.
        self.spec_adaptive = spec_adaptive
        if spec_decode:
            assert model.cfg.attention_kind == "gqa" \
                and model.cfg.family not in ("ssm", "hybrid"), \
                "spec_decode needs a GQA KV cache"
        self.key = jax.random.PRNGKey(seed)
        # multi-tenant adapters (serving/adapters/AdapterServing): per-request
        # adapter_id selects a frozen ternary LoRA; resident adapters ride in
        # the param tree as lora_mt stacks, gathered per slot each tick.
        self.adapters = adapters
        self._mt_params: Optional[Params] = None
        self._mt_version = -1

        if scheduler is None:
            from repro.serving.gateway.scheduler import Scheduler
            scheduler = Scheduler()
        self.scheduler = scheduler

        # the KV backend owns cache init/alloc/commit/free; `page`/`n_pages`
        # only apply to the deprecated kv="paged" string shim
        self.kv = as_backend(kv, page=page, n_pages=n_pages)
        self.kv.bind(model, max_slots, max_len)
        self.pool = self.kv.pool
        self.prefix = None
        if prefix_cache:
            assert self.kv.supports_paging, \
                "prefix_cache requires a paged KV backend (kv=PagedKV(...))"
            from repro.serving.gateway.prefix_cache import PrefixCache
            self.prefix = PrefixCache(self.pool.cfg.page)

        # tiered memory hierarchy (serving/memory/TieredStore): device-tier
        # accounting for resident adapters + committed prefix pages, host/disk
        # spill for evicted ones (a popular prefix re-admits from host instead
        # of re-prefilling), and — with prefetch=True — a scheduler hook that
        # warms upcoming adapter/prefix needs up the hierarchy before their
        # tick. None keeps every legacy eviction path byte-identical.
        self.tiered = tiered
        self.prefetch = prefetch
        self._prefetched: set = set()        # warmed keys awaiting first use
        # feed lengths with a host-spilled dense prefix (DenseKV has no page
        # table to key re-admission off, so placements probe these lengths)
        self._dense_spill_lens: set = set()
        self._dense_spill_ok = (
            tiered is not None and not self.kv.supports_paging
            and self.cfg.attention_kind == "gqa"
            and self.cfg.family not in ("ssm", "hybrid"))
        if tiered is not None and adapters is not None:
            adapters.attach_tiered(tiered)

        self.pos = np.zeros((max_slots,), np.int32)       # next write position
        self.slot_adapter = np.zeros((max_slots,), np.int32)  # device slot (0=none)
        # version-pinned adapter cache key per slot: a hot-swap (re-register)
        # mid-stream must not steal an in-flight request's weights, so the
        # slot releases exactly the version it acquired
        self.slot_adapter_key: List[Optional[str]] = [None] * max_slots
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        self.pending_prompt: List[List[int]] = [[] for _ in range(max_slots)]
        # chunked-prefill state machine: a slot with a non-empty todo list is
        # *prefilling* (admitted, pages reserved, excluded from decode) until
        # the tick loop has prefilled all but its last prompt token
        self.slot_prefill_todo: List[List[int]] = [[] for _ in range(max_slots)]
        self.slot_feed: List[List[int]] = [[] for _ in range(max_slots)]
        self.slot_keys: List[List] = [[] for _ in range(max_slots)]
        self.slot_cached: List[int] = [0] * max_slots     # cache-owned lead pages
        # per-slot adaptive-width controller (spec_adaptive only; created at
        # placement, dropped with the slot so each request starts fresh)
        self.slot_spec_adapt: List[Optional[AdaptiveSpecK]] = \
            [None] * max_slots
        self.stats = EngineStats()
        self._uid = 0

        # split-tick pipeline (async runtime): tick_begin() dispatches the
        # device work for one tick and parks the unmaterialized sample array
        # in a PendingTick; tick_finish() materializes the oldest pending
        # tick and runs its emit/eos/release bookkeeping. The sync tick()
        # finishes immediately, so the deque is empty outside tick() and
        # every legacy behavior is unchanged.
        self._pending: "collections.deque[PendingTick]" = collections.deque()

        # observability: the tracer records per-tick phase spans, request
        # lifecycle tracks and jit-compile instants (disabled by default —
        # a null object that allocates nothing per span); phase self-times
        # and the tick-gap clock accumulate in stats either way.
        self.trace = tracer if tracer is not None else NULL_TRACER
        # roofline profiler (obs/profile.ProfileRegistry, opt-in): every
        # _dispatch is blocked-and-timed per (fn, shape-signature) and each
        # compiled executable's cost/memory analysis is captured once —
        # None keeps dispatches async and adds zero per-call work.
        self.profiler = profiler
        self._tpid = (self.trace.register(f"engine[{self.kv.name}]")
                      if self.trace.enabled else 1)
        self._phase_self_total = 0.0
        self._t_dev_end: Optional[float] = None  # last device-dispatch return
        self._dispatch_tid: Optional[int] = None  # thread of that dispatch
        self._tick_gap_ms: Optional[float] = None  # gap observed this tick
        self._last_verify_width = 1
        self._prefill_watch = None
        # AOT prefill executables by (kind, token-bucket, has-adapter-idx):
        # warmup_aot fills this with `.lower(...).compile()` products (the
        # maxtext offline_inference warmup idiom) and _prefill_span prefers
        # them over the jit path — a served bucket never trips a trace-time
        # compile stall. Empty until warmup runs; always safe to ignore.
        self._cached_pref: Dict[Tuple, _AotCall] = {}
        # sharded serving (serving/sharded.py) stamps the replica's Mesh here
        # after device_put-ing params/pool; None = single-device placement
        self.mesh = None

        def _watch(fn, name):
            return CompileWatch(fn, name, self.trace,
                                on_compile=self._note_compile, pid=self._tpid)

        # ONE decode path: the backend's state pytree picks the model's
        # dense or paged decode inside decode_step — no engine branches.
        # Every jitted entry point rides a CompileWatch: cache growth bumps
        # stats.jit_compiles and emits a jit_compile instant naming the
        # offending shape bucket (recompile stalls become visible in-trace).
        # The decode step consumes the KV state it is handed: commit()
        # replaces the state wholesale right after the dispatch, so the
        # engine never reads a donated buffer again, and the step's new
        # pool (or dense cache) is written into the buffer it came in —
        # the paged kernel path then updates the pool in place and never
        # holds a second copy of it.
        self._decode_jit = jax.jit(self._decode_fn,
                                   donate_argnames=("kv_state",))
        self._decode = _watch(self._decode_jit, "decode_step")
        self._sample = _watch(jax.jit(self._sample_fn,
                                      static_argnames=("use_topp",
                                                       "use_seeds")),
                              "sample")
        # multi-token verify (speculative decoding): compiled per
        # (draft-width bucket, table-view bucket) pair — widths are padded to
        # powers of two so the compile cache stays small; warm every bucket
        # the workload will hit before timing anything
        self._verify = _watch(jax.jit(self._verify_fn), "verify_step")
        self._verify_sample = _watch(
            jax.jit(self._verify_sample_fn,
                    static_argnames=("use_topp", "use_seeds")),
            "verify_sample")

        # event hooks (wired by the gateway; req-first signatures)
        self.on_token: Optional[Callable[[Request, int, float], None]] = None
        self.on_done: Optional[Callable[[Request], None]] = None
        self.on_admit: Optional[Callable[[Request, int], None]] = None
        self.on_preempt: Optional[Callable[[Request], None]] = None
        self.on_expire: Optional[Callable[[Request], None]] = None
        # per-tick summary hook (gateway → tick_gap histogram + energy
        # monitor): fires after every tick() with wall/busy/token counts
        self.on_tick: Optional[Callable[[Dict[str, Any]], None]] = None

    @property
    def kv_mode(self) -> str:
        """Back-compat view of the backend kind ("dense"/"paged")."""
        return self.kv.name

    @property
    def cache(self):
        """Back-compat view of DenseKV's contiguous cache (None if paged)."""
        return getattr(self.kv, "cache", None)

    # -- observability helpers -------------------------------------------------
    def _phase(self, name: str) -> _Phase:
        """Tick-phase timer and ``serve.<name>`` span; ``name`` is one of
        ``repro.obs.names.PHASES``."""
        return _Phase(self, name)

    def _note_compile(self, name: str, shapes: str) -> None:
        self.stats.jit_compiles += 1

    def _dispatch(self, fn, *args, **kwargs):
        """Run one device dispatch, recording the host-side gap since the
        previous dispatch returned (``tick_gap_ms``): sampling, scheduling
        and bookkeeping time during which the device sits idle — the named
        feedback signal for the ROADMAP's async disaggregated runtime.

        Threaded-dispatch semantics: the gap clock is *per dispatch thread*
        — a dispatch issued from a different thread than the previous one
        (warmup on the main thread, then the async runtime's dispatch
        thread) records no gap and just re-arms the clock, so cross-thread
        wall time never pollutes ``host_overhead_frac``. While the split-
        tick pipeline holds an unfinished tick the device queue is
        non-empty, so gaps observed then are *overlapped* host time and
        land in ``tick_gap_overlap_ms_sum`` instead of the idle-gap sum."""
        t = time.perf_counter()
        tid = threading.get_ident()
        if self._t_dev_end is not None and tid == self._dispatch_tid:
            gap = (t - self._t_dev_end) * 1e3
            self._tick_gap_ms = gap
            if self._pending:
                self.stats.tick_gap_overlap_ms_sum += gap
                self.stats.tick_gaps_overlap += 1
            else:
                self.stats.tick_gap_ms_sum += gap
                self.stats.tick_gaps += 1
                self.trace.counter("tick_gap_ms", gap, pid=self._tpid)
        if self.profiler is not None:
            # the probe lowers from shapes taken now: the call may consume
            # (donate) the arrays it is handed
            probe = _specs(args)
        out = fn(*args, **kwargs)
        if self.profiler is not None:
            # profiling blocks the dispatch so the measured wall is real
            # device time per compiled executable, not async enqueue time
            out = jax.block_until_ready(out)
            self.profiler.observe_call(
                getattr(fn, "name", getattr(fn, "__name__", "fn")),
                fn, probe, kwargs, time.perf_counter() - t,
                compiled=getattr(fn, "last_compiled", False))
        self._t_dev_end = time.perf_counter()
        self._dispatch_tid = tid
        return out

    #: phases counted as device-execution time for the energy monitor
    _BUSY_PHASES = ("prefill", "prefill_chunk", "kv_write", "decode",
                    "spec_verify", "sample", "commit")

    def _busy_ms(self) -> float:
        pm = self.stats.phase_ms
        return sum(pm.get(k, 0.0) for k in self._BUSY_PHASES)

    # -- jitted kernels --------------------------------------------------------
    def _decode_fn(self, params, kv_state, tokens, pos, adapter_idx=None):
        logits, kv_state = self.model.decode_step(params, kv_state, tokens,
                                                  pos, adapter_idx)
        return logits, kv_state

    def _sample_fn(self, logits, key, temperature, top_k, top_p, seeds,
                   has_seed, steps, *, use_topp=True, use_seeds=True):
        """Per-slot sampling, all array arguments (B,) vectors: temperature
        f32, top_k int32 (0 = full softmax), top_p f32 nucleus mass (1.0 =
        off), plus per-request seeded streams (draws keyed by (seed, step)
        only). ``use_topp``/``use_seeds`` are static: the tick passes False
        when no slot uses the feature, so the common greedy/top-k graph pays
        no nucleus sort or per-row seeded draws. With top_p=1.0 and no seeds
        the output is bit-identical to the historical temperature/top-k
        sampler either way (the masks are exact no-ops)."""
        greedy = jnp.argmax(logits, axis=-1)
        vocab = logits.shape[-1]
        sorted_desc = -jnp.sort(-logits, axis=-1)
        k_idx = jnp.clip(top_k - 1, 0, vocab - 1)
        thresh = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
        masked = jnp.where((top_k[:, None] > 0) & (logits < thresh),
                           NEG_INF, logits)
        final = masked / jnp.maximum(temperature[:, None], 1e-6)
        if use_topp:
            # top-p (nucleus): keep the smallest prefix of the sorted
            # distribution whose cumulative probability reaches top_p; ties
            # at the cutoff stay.
            sorted_scaled = -jnp.sort(-final, axis=-1)
            probs = jax.nn.softmax(sorted_scaled, axis=-1)
            csum = jnp.cumsum(probs, axis=-1)
            keep = (csum - probs) < top_p[:, None]     # prefix-exclusive mass
            n_keep = jnp.maximum(jnp.sum(keep, axis=-1), 1)
            cutoff = jnp.take_along_axis(sorted_scaled, n_keep[:, None] - 1,
                                         axis=-1)
            apply_p = (top_p < 1.0)[:, None]
            final = jnp.where(apply_p & (final < cutoff), NEG_INF, final)
        sampled = jax.random.categorical(key, final, axis=-1)
        if use_seeds:
            def seeded_draw(seed, step, row):
                k = jax.random.fold_in(jax.random.PRNGKey(seed), step)
                return jax.random.categorical(k, row)

            seeded = jax.vmap(seeded_draw)(seeds, steps, final)
            sampled = jnp.where(has_seed, seeded, sampled)
        use_greedy = temperature <= 0.0
        return jnp.where(use_greedy, greedy, sampled).astype(jnp.int32)

    def _verify_fn(self, params, kv_state, tokens, pos, adapter_idx=None):
        return self.model.verify_step(params, kv_state, tokens, pos,
                                      adapter_idx)

    def _verify_sample_fn(self, logits, key, temperature, top_k, top_p,
                          seeds, has_seed, steps0, *, use_topp=True,
                          use_seeds=True):
        """Per-position sampling over a verify tick's (B, S, V) logits. Row
        (b, j) runs exactly `_sample_fn`'s math at output step
        ``steps0[b] + j``, so greedy picks and seeded draws match the
        single-token sampler token for token — the accept/reject identity
        contract reduces to "does the draft equal this row's choice"."""
        b, s, v = logits.shape

        def rep(a):
            return jnp.repeat(a, s)

        steps = (steps0[:, None] + jnp.arange(s)[None, :]).reshape(-1)
        flat = self._sample_fn(logits.reshape(b * s, v), key,
                               rep(temperature), rep(top_k), rep(top_p),
                               rep(seeds), rep(has_seed), steps,
                               use_topp=use_topp, use_seeds=use_seeds)
        return flat.reshape(b, s)

    # -- public API ---------------------------------------------------------------
    def submit(self, prompt: List[int], spec: Optional[RequestSpec] = None,
               sampling: Optional[SamplingParams] = None,
               **legacy) -> Request:
        """Enqueue a request described by a `RequestSpec` (+ optional
        `SamplingParams`). Old keyword arguments (max_new_tokens=...,
        temperature=..., deadline_s=<absolute>, ...) are accepted behind a
        DeprecationWarning."""
        spec, sampling, deadline_s = coerce_submit(spec, sampling, legacy)
        self._uid += 1
        req = Request(self._uid, list(prompt), spec=spec, sampling=sampling,
                      deadline_s=deadline_s, t_submit=time.time())
        if req.adapter_id is not None and not self._adapter_servable(req.adapter_id):
            # unknown tenant, no adapter runtime, or an adapter bigger than
            # the whole SRAM budget: it could never be scheduled
            req.state = "rejected"
        elif not self.scheduler.push(req):
            req.state = "rejected"
        self.trace.lifecycle(req.uid, "rejected" if req.state == "rejected"
                             else "queued", pid=self._tpid)
        return req

    def _adapter_servable(self, adapter_id: str) -> bool:
        return self.adapters is not None and self.adapters.servable(adapter_id)

    def _adapter_warm(self, req: Request) -> bool:
        """Affinity predicate: True when serving ``req`` costs no adapter
        load (no adapter, or already resident)."""
        return (self.adapters is None or req.adapter_id is None
                or self.adapters.is_resident(req.adapter_id))

    def _effective_params(self) -> Params:
        """Base params, with the current multi-tenant adapter stacks grafted
        in (rebuilt only when the runtime loads/evicts an adapter)."""
        if self.adapters is None:
            return self.params
        if self._mt_version != self.adapters.version:
            self._mt_params = self.adapters.install(self.params)
            self._mt_version = self.adapters.version
        return self._mt_params

    def cancel(self, uid: int) -> bool:
        """Cancel a queued or running request. Returns False if unknown."""
        # settle any in-flight pipelined tick first: its deferred emissions
        # may finish (or release) the very request being cancelled, and a
        # cancel must never race a pending emit for the same slot
        self._settle_pipeline()
        req = self.scheduler.remove(uid)
        if req is not None:
            req.state = "cancelled"
            self.stats.cancelled += 1
            self.trace.lifecycle(uid, "cancelled", pid=self._tpid)
            return True
        for slot, r in enumerate(self.slot_req):
            if r is not None and r.uid == uid:
                r.state = "cancelled"
                self.stats.cancelled += 1
                self._release_slot(slot)
                self.trace.lifecycle(uid, "cancelled", pid=self._tpid)
                return True
        return False

    def run_until_drained(self, max_ticks: int = 100_000) -> EngineStats:
        t0 = time.time()
        while (len(self.scheduler) or any(r is not None for r in self.slot_req)) \
                and self.stats.ticks < max_ticks:
            before = self.stats.ticks
            self.tick()
            if self.stats.ticks == before \
                    and not any(r is not None for r in self.slot_req):
                # nothing running and nothing admissible (e.g. a queued
                # request larger than the page pool): no tick will ever
                # change that, so bail instead of spinning — callers can
                # inspect the still-queued requests
                break
        self.stats.wall_s += time.time() - t0
        return self.stats

    # -- AOT bucket warmup -----------------------------------------------------
    def warmup_aot(self, *, max_prompt_len: Optional[int] = None,
                   spec_widths: Tuple[int, ...] = (1, 3, 7, 15),
                   resume_starts=(), profiler=None) -> Dict[str, Any]:
        """Compile every executable the serving workload can hit *before*
        traffic arrives (the maxtext ``offline_inference`` warmup idiom), so
        no request ever stalls behind a trace+compile.

        Two mechanisms, matched to how each entry point is dispatched:

          * **fresh prefill** — genuine AOT products: ``fn.lower(...)
            .compile()`` per pow2 token bucket (× adapter-idx variant),
            parked in ``_cached_pref`` and *invoked* by ``_prefill_span``;
            each executable is registered with the profiler so roofline
            attribution keeps working without a live ``.lower`` probe.
          * **decode / sample / verify / resume-prefill** — dummy-executed
            through the engine's CompileWatch-wrapped jits with throwaway
            states from the KV backend (`warmup_decode_states` /
            `warmup_verify_states`; every block-table view bucket, every
            draft-width bucket, all four sampler static combos), populating
            the jit dispatch caches and the watches' seen-shape sets. The
            dummies alias no live storage, so a donated decode may consume
            them freely, and the engine's sampling ``self.key`` is never
            advanced — a warmed engine stays token-identical to a cold one.

        ``max_prompt_len`` bounds the prefill buckets (default: ``max_len``);
        ``resume_starts`` adds explicit ``(n_tokens, start)`` resume shapes
        beyond the chunk/page-boundary enumeration. On return,
        ``stats.warmup_compiles`` records the executables built here and
        ``stats.jit_compiles`` resets to **0**, so any nonzero value after
        serving is a real recompile stall (the zero-recompile contract the
        sharded test lane asserts). The returned ``decode_alias_bytes`` and
        ``decode_temp_bytes`` are the compiled decode step's memory analysis
        at the largest table view: the argument bytes its outputs alias (the
        donated KV state, when the step updates it in place) and the bytes
        it holds besides its arguments and outputs.

        Must run on an idle engine (no pending pipelined ticks)."""
        assert not self._pending, "warmup_aot needs an idle engine"
        t0 = time.perf_counter()
        prof = profiler if profiler is not None else self.profiler
        compiles0 = self.stats.jit_compiles
        params = self._effective_params()
        B = self.max_slots
        n_max = min(max_prompt_len or self.max_len, self.max_len)
        use_jit = self.cfg.attention_kind == "gqa" \
            and self.cfg.family not in ("ssm", "hybrid")
        aidx_variants: List[Optional[jax.Array]] = [None]
        if self.adapters is not None:
            aidx_variants.append(jnp.zeros((1,), jnp.int32))

        # -- fresh prefill: real AOT executables per bucket ---------------------
        buckets: List[int] = []
        n_aot = 0
        if use_jit and self.prefill_mode == "batched":
            e = 4
            while True:
                b = min(1 << e, self.max_len)
                buckets.append(b)
                if (1 << e) >= n_max or b >= self.max_len:
                    break
                e += 1
            buckets = sorted(set(buckets))
            fresh_jit, _ = _prefill_jits(self.model)
            for b in buckets:
                toks = jnp.asarray(np.zeros((1, b), np.int32))
                for aidx in aidx_variants:
                    args = (params, toks, self.max_len, aidx)
                    compiled = fresh_jit.lower(*args).compile()
                    self._cached_pref[("fresh", b, aidx is not None)] = \
                        _AotCall(compiled, "prefill_fresh", drop=(2,))
                    n_aot += 1
                    if prof is not None:
                        prof.register_compiled("prefill_fresh", args, compiled)

        # -- resume prefill: dummy-exec the (bucket, prefix-bucket) shape set ---
        resume_pairs = set()

        def note_resume(n: int, start: int) -> None:
            if n <= 0 or start <= 0 or start >= self.max_len:
                return
            b = 1 << max(4, (n - 1).bit_length())
            b = min(b, self.max_len - start)
            pb = min(1 << max(4, (start - 1).bit_length()), self.max_len)
            if b > 0:
                resume_pairs.add((b, pb))

        def add_start(start: int, n_cap: int) -> None:
            e = 4
            while True:
                note_resume(min(1 << e, n_cap), start)
                if (1 << e) >= n_cap:
                    break
                e += 1

        if use_jit and self.prefill_mode == "batched":
            if self.prefill_chunk:
                for s in range(self.prefill_chunk, n_max, self.prefill_chunk):
                    add_start(s, min(self.prefill_chunk, max(n_max - s, 1)))
            if self.prefix is not None:
                page = self.pool.cfg.page
                for s in range(page, n_max, page):
                    add_start(s, max(n_max - s - 1, 1))
            for n, s in resume_starts:
                note_resume(int(n), int(s))
            if resume_pairs:
                src = self.pool.k if self.kv.supports_paging \
                    else self.cache["k"]
                L, _, H, _, D = src.shape
                resume_watch = self._prefill_fns()[1]
                for b, pb in sorted(resume_pairs):
                    z = jnp.zeros((L, 1, H, pb, D), src.dtype)
                    pref = {"k": z, "v": z}
                    toks = jnp.asarray(np.zeros((1, b), np.int32))
                    for aidx in aidx_variants:
                        resume_watch(params, toks, self.max_len,
                                     jnp.int32(pb), pref, aidx)

        # -- decode tick + samplers (all static combos) -------------------------
        fed = jnp.asarray(np.zeros((B,), np.int32))
        posv = jnp.asarray(np.zeros((B,), np.int32))
        aidx_dec = self._adapter_idx()
        # throwaway key: warmup must not advance self.key (token identity)
        sub = jax.random.split(jax.random.PRNGKey(0))[1]
        z_f = jnp.asarray(np.zeros((B,), np.float32))
        one_f = jnp.asarray(np.ones((B,), np.float32))
        z_i = jnp.asarray(np.zeros((B,), np.int32))
        z_b = jnp.asarray(np.zeros((B,), bool))
        last = None
        logits = None
        largest = None
        for state in self.kv.warmup_decode_states():
            args = (params, state, fed, posv, aidx_dec)
            # shapes taken before the call consumes the state; the states
            # come smallest view first
            largest = _specs(args)
            logits, _ = self._decode(*args)
        decode_mem = None
        if largest is not None:
            decode_mem = self._decode_jit.lower(*largest).compile() \
                .memory_analysis()
        if logits is not None:
            for use_topp in (False, True):
                for use_seeds in (False, True):
                    last = self._sample(logits, sub, z_f, z_i, one_f, z_i,
                                        z_b, z_i, use_topp=use_topp,
                                        use_seeds=use_seeds)

        # -- multi-token verify (spec decode) per draft-width bucket ------------
        sbs: List[int] = []
        if self.spec_decode:
            sbs = sorted({1 << int(w).bit_length()
                          for w in spec_widths if int(w) >= 1})
            for s in sbs:
                vtok = jnp.asarray(np.zeros((B, s), np.int32))
                vlogits = None
                for vstate in self.kv.warmup_verify_states(s):
                    vlogits, _ = self._verify(params, vstate, vtok, posv,
                                              aidx_dec)
                if vlogits is None:
                    continue
                for use_topp in (False, True):
                    for use_seeds in (False, True):
                        last = self._verify_sample(
                            vlogits, sub, z_f, z_i, one_f, z_i, z_b, z_i,
                            use_topp=use_topp, use_seeds=use_seeds)

        if last is not None:
            jax.block_until_ready(last)
        jit_warmed = self.stats.jit_compiles - compiles0
        self.stats.warmup_compiles += jit_warmed + n_aot
        # post-warmup, the compile counter reports *serve-time* recompiles
        # only — the quantity the zero-recompile sweep asserts is exactly 0
        self.stats.jit_compiles = 0
        return {
            "prefill_buckets": buckets,
            "resume_pairs": sorted(resume_pairs),
            "verify_buckets": sbs,
            "aot_executables": n_aot,
            "jit_warmed": jit_warmed,
            "compiles": jit_warmed + n_aot,
            "wall_s": round(time.perf_counter() - t0, 3),
            "decode_alias_bytes": getattr(decode_mem, "alias_size_in_bytes",
                                          None),
            "decode_temp_bytes": getattr(decode_mem, "temp_size_in_bytes",
                                         None),
        }

    # -- engine internals ------------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _is_decoding(self, slot: int) -> bool:
        """True when the slot belongs in the decode batch. A slot whose
        chunked prefill is still in flight is NOT decoding even if the
        request already has output tokens (a preempted-while-decoding
        request replays prompt+output through chunked prefill — feeding it
        to decode mid-prefill would shift its KV positions)."""
        req = self.slot_req[slot]
        return (req is not None and not self.slot_prefill_todo[slot]
                and bool(self.pending_prompt[slot] or req.output
                         or self._inflight_emits(slot)))

    # -- split-tick pipeline helpers -------------------------------------------
    def _inflight_emits(self, slot: int) -> int:
        """Deferred emissions queued for ``slot`` across pending ticks —
        tokens the device has (logically) produced but tick_finish() has not
        yet materialized into ``req.output``. The request-identity guard
        drops stale entries for a slot that was re-assigned underneath a
        pending tick (possible only after an early release)."""
        if not self._pending:
            return 0
        req = self.slot_req[slot]
        return sum(1 for p in self._pending
                   for i, r, _ in p.emits if i == slot and r is req)

    def _slot_done_inflight(self, slot: int) -> bool:
        """True when a pending tick already predicted this slot's request
        will be complete once finished (budget / max_len) — the slot stays
        occupied but must not decode again before tick_finish releases it."""
        return any(slot in p.done_slots for p in self._pending)

    def _settle_pipeline(self) -> None:
        """Finish every pending tick (materialize + emit). State-mutating
        paths that need host-visible history — cancel, preemption, draft
        planning, admission under pressure — call this before acting."""
        while self._pending:
            self.tick_finish()

    def _active_pairs(self) -> List[Tuple[int, Request]]:
        return [(i, r) for i, r in enumerate(self.slot_req) if r is not None]

    def _feed_tokens(self, req: Request) -> List[int]:
        """Token history a (re-)admitted request must replay: the prompt
        plus anything generated before a preemption."""
        return list(req.prompt) + list(req.output)

    def _clamped_feed(self, req: Request) -> Tuple[List[int], int]:
        """(feed, remaining_new) after the max_len truncation clamp — the
        single source of truth shared by admission accounting and _place:
        the generation budget is clamped first (a request can never produce
        more than max_len - 1 new tokens), then the prompt keeps its tail."""
        feed = self._feed_tokens(req)
        remaining_new = max(1, req.max_new_tokens - len(req.output))
        if len(feed) + remaining_new > self.max_len:
            remaining_new = min(remaining_new, self.max_len - 1)
            feed = feed[-(self.max_len - remaining_new):]
        return feed, remaining_new

    def _pages_needed(self, req: Request) -> int:
        """Free pages required to *start* the request (prompt + 1 token)."""
        feed, _ = self._clamped_feed(req)
        hit = self.prefix.lookup(feed) if self.prefix is not None else 0
        return self.kv.pages_for(len(feed) + 1) - hit

    def _pages_lifetime(self, req: Request) -> int:
        """Backend pages the request's slot will hold at its *final* context
        length (prefix hits included — shared pages still occupy the pool).
        Must fit total capacity or the request can never complete."""
        feed, remaining_new = self._clamped_feed(req)
        return self.kv.pages_for(min(len(feed) + remaining_new, self.max_len))

    def _can_admit(self, req: Request) -> bool:
        if (self.adapters is not None and req.adapter_id is not None
                and not self.adapters.can_serve(req.adapter_id)):
            # every budget byte is pinned by in-flight adapters — the request
            # waits until a slot drains and unpins one
            return False
        # a request whose final context exceeds the whole pool would only
        # crash mid-flight — keep it queued instead of admitting it
        # (DenseKV reports zero cost / unbounded capacity: always admissible)
        if self._pages_lifetime(req) > self.kv.capacity_pages:
            return False
        return self.kv.pages_free >= self._pages_needed(req)

    # -- tiered memory hierarchy ----------------------------------------------
    def _kv_key(self, key) -> str:
        """TieredStore key of a prefix-KV span (tuple of prompt tokens)."""
        return "kv:" + ",".join(map(str, key))

    def _dense_key(self, adapter_key, feed) -> str:
        """Dense-spill store key. Unlike the paged trie (which shares
        committed pages across tenants by token identity — the baseline
        semantic), the dense path is new reuse, so it must not hand one
        adapter's KV to another: the slot's version-pinned adapter key
        namespaces the entry."""
        tag = f"{adapter_key}|" if adapter_key else ""
        return "kv:" + tag + ",".join(map(str, feed))

    @property
    def _page_nbytes(self) -> int:
        """Device footprint of one k+v pool page (fp8 cache encoding)."""
        c = self.pool.cfg
        return (2 * c.n_layers * c.n_kv_heads * c.page * c.head_dim
                * np.dtype(self.pool.k.dtype).itemsize)

    def _evict_prefix(self, n: int) -> None:
        """Evict up to ``n`` resident prefix pages. With a tiered store each
        page's KV is exported and demoted to the host tier (keyed by its
        token prefix) before the page returns to the pool — a later request
        for the same prefix re-admits the bytes instead of re-prefilling."""
        if self.tiered is None:
            self.kv.free_pages(self.prefix.evict(n))
            return
        freed = []
        for key, pid in self.prefix.evict_detailed(n):
            self.tiered.demote(self._kv_key(key), self.kv.export_page(pid),
                               remat_cost=float(len(key)))
            self.stats.kv_spilled_pages += 1
            freed.append(pid)
        self.kv.free_pages(freed)

    def _readmit_prefix(self, feed: List[int], keep_free: int = 0,
                        record: bool = False) -> int:
        """Extend ``feed``'s cached prefix span by re-importing spilled
        pages from the tiered store back into freshly allocated pool pages
        and re-inserting their trie nodes (shortest-first, so parents exist
        before children). Returns pages re-admitted. ``keep_free`` leaves
        pool headroom (the prefetch hook must not starve admissions);
        ``record`` marks the keys as prefetched so the placement that uses
        them counts a prefetch hit."""
        if self.tiered is None or self.prefix is None:
            return 0
        page = self.pool.cfg.page
        limit = max(0, (len(feed) - 1) // page)
        n = self.prefix.lookup(feed)
        readmitted = 0
        while n < limit and self.pool.pages_free > keep_free:
            key = tuple(feed[: (n + 1) * page])
            kv_key = self._kv_key(key)
            if self.tiered.tier_of(kv_key) in (None, "device"):
                break
            payload = self.tiered.take(kv_key)
            if payload is None:
                break              # corrupt disk copy degraded to a miss
            pid = self.pool.alloc_page()
            self.kv.import_page(pid, payload)
            self.prefix.readmit(key, pid)
            self.tiered.note_device(kv_key, self._page_nbytes,
                                    remat_cost=float(len(key)))
            self.stats.prefix_readmits += 1
            self.stats.prefix_readmit_tokens += page
            if record:
                self._prefetched.add(kv_key)
            n += 1
            readmitted += 1
        return readmitted

    def _readmit_dense(self, slot: int, feed: List[int]) -> int:
        """DenseKV re-admission: probe spilled feed lengths (longest first)
        for a host copy of ``feed``'s prefix KV and import it into the
        slot's rows. Returns matched token count (≥1 token always left for
        decode). The host entry is read, not consumed — other placements
        can reuse it until the store's budget evicts it."""
        akey = self.slot_adapter_key[slot]
        for n in sorted(self._dense_spill_lens, reverse=True):
            if n > len(feed):
                continue
            key = self._dense_key(akey, feed[:n])
            payload = self.tiered.get(key)
            if payload is None:
                continue
            upto = min(n, len(feed) - 1)
            if upto <= 0:
                continue
            if upto < n:
                payload = {k: v[:, :, :upto] for k, v in payload.items()}
            self.kv.import_prefix(slot, payload)
            self.stats.prefix_readmits += 1
            self.stats.prefix_readmit_tokens += upto
            if key in self._prefetched:
                self._prefetched.discard(key)
                self.stats.prefetch_hits += 1
            return upto
        return 0

    def _prefetch_queue(self) -> None:
        """Scheduler prefetch hook: walk the head of the pending queue and
        warm each request's adapter and prefix KV *up* the hierarchy before
        its admission tick — disk→host staging always, host→device only
        into spare capacity (free adapter slots / pool headroom), so
        prefetch never evicts hotter state."""
        if not self.prefetch or self.tiered is None:
            return
        upcoming = getattr(self.scheduler, "upcoming", None)
        if upcoming is None:
            return          # custom scheduler without a queue peek
        for req in upcoming(2 * self.max_slots):
            if (self.adapters is not None and req.adapter_id is not None
                    and req.adapter_id in self.adapters.registry):
                key = "adapter:" + self.adapters._vkey(req.adapter_id)
                if self.adapters.prefetch(req.adapter_id):
                    self._prefetched.add(key)
            feed, _ = self._clamped_feed(req)
            if self.prefix is not None:
                page = self.pool.cfg.page
                for i in range(1, max(0, (len(feed) - 1) // page) + 1):
                    kk = self._kv_key(tuple(feed[: i * page]))
                    if self.tiered.tier_of(kk) == "disk":
                        self.tiered.promote_host(kk)
                self._readmit_prefix(
                    feed, keep_free=self.kv.pages_for(self.max_len),
                    record=True)
            elif self._dense_spill_ok:
                akey = None
                if (self.adapters is not None and req.adapter_id is not None
                        and req.adapter_id in self.adapters.registry):
                    akey = self.adapters._vkey(req.adapter_id)
                for n in sorted(self._dense_spill_lens, reverse=True):
                    if n > len(feed):
                        continue
                    kk = self._dense_key(akey, feed[:n])
                    if self.tiered.promote_host(kk):
                        self._prefetched.add(kk)
                    break

    def _admit(self) -> None:
        now = time.time()
        for req in self.scheduler.drop_expired(now):
            req.state = "expired"
            self.stats.expired += 1
            self.trace.lifecycle(req.uid, "expired", pid=self._tpid)
            if self.on_expire:
                self.on_expire(req)
        for slot in self._free_slots():
            if not len(self.scheduler):
                break
            req = self.scheduler.pop_next(self._can_admit,
                                          prefer=self._adapter_warm)
            if req is None and self.kv.supports_paging:
                req = self._admit_under_pressure()
            if req is None:
                break
            self._place(slot, req, now)

    def _admit_under_pressure(self) -> Optional[Request]:
        """Nothing fits the pool: evict resident prefix pages, then preempt
        lower-priority active slots for the most urgent queued request —
        but only if the reclaimed pages actually make it admissible.
        Preempting without that check livelocks: the victim is re-admitted
        by the very next pop and zero progress is made every tick."""
        # preemption replays prompt+output — settle pending emissions first
        # so a victim's replay feed includes every token it already earned
        self._settle_pipeline()
        head = self.scheduler.peek(
            lambda r: self._pages_lifetime(r) <= self.kv.capacity_pages
            and (self.adapters is None or r.adapter_id is None
                 or self.adapters.can_serve(r.adapter_id)))
        if head is None:
            return None
        needed = self._pages_needed(head)
        short = needed - self.kv.pages_free
        if short > 0 and self.prefix is not None:
            self._evict_prefix(short)
        if not self._can_admit(head):
            # plan the victim set first: count only pages release() actually
            # frees (owned pages — cache-shared ones stay resident)
            budget = self.kv.pages_free
            pairs = self._active_pairs()
            victims: List[int] = []
            while budget < needed:
                slot = self.scheduler.pick_victim(
                    pairs, below_priority=head.priority)
                if slot is None:
                    return None          # preemption can't help → no thrash
                budget += self.kv.slot_pages(slot) - self.slot_cached[slot]
                victims.append(slot)
                pairs = [(i, r) for i, r in pairs if i != slot]
            for slot in victims:
                self._preempt(slot)
        return self.scheduler.pop_next(self._can_admit,
                                       prefer=self._adapter_warm)

    def _place(self, slot: int, req: Request, now: float) -> None:
        req.state = "running"
        req.t_admit = now
        if self.adapters is not None and req.adapter_id is not None:
            # load (evicting LRU unpinned if needed) + pin for the slot's
            # life. The pin is *version-resolved* at placement: a hot-swap
            # (re-register) while this request streams must not move its
            # weights, so release targets the exact pinned version below.
            dev_slot, key = self.adapters.acquire_versioned(req.adapter_id)
            self.slot_adapter[slot] = dev_slot
            self.slot_adapter_key[slot] = key
            if "adapter:" + key in self._prefetched:
                self._prefetched.discard("adapter:" + key)
                self.stats.prefetch_hits += 1
        feed, remaining_new = self._clamped_feed(req)
        req.max_new_tokens = len(req.output) + remaining_new
        self.slot_req[slot] = req
        self.slot_feed[slot] = feed
        if self.spec_adaptive and req.sampling.spec_k > 0:
            self.slot_spec_adapt[slot] = AdaptiveSpecK()
        self.pos[slot] = 0
        matched = 0
        if self.prefix is not None:
            # pull any spilled pages of this feed's prefix back on-device
            # first, so the trie match below sees the re-admitted span too
            self._readmit_prefix(feed)
            ids, keys = self.prefix.match(feed)
            self.slot_keys[slot] = keys
            self.slot_cached[slot] = len(ids)
            for k in keys:
                kk = self._kv_key(k)
                if kk in self._prefetched:
                    self._prefetched.discard(kk)
                    self.stats.prefetch_hits += 1
            if ids:
                self.pool.append_shared(slot, ids)
                matched = len(ids) * self.pool.cfg.page
                self.pos[slot] = matched
                self.pool.lengths[slot] = matched
                req.prefix_hit_tokens = matched
                self.stats.prefix_hit_tokens += matched
        elif self._dense_spill_ok and self._dense_spill_lens:
            matched = self._readmit_dense(slot, feed)
            if matched:
                self.pos[slot] = matched
                req.prefix_hit_tokens = matched
                self.stats.prefix_hit_tokens += matched
        # eager reservation: claim the prompt's pages (plus the first output
        # token) now, so admission control sees the true footprint of
        # already-placed requests instead of racing lazy allocation.
        # (DenseKV: no-op — the slot's max_len row is always reserved.)
        self.kv.reserve(slot, len(feed) + 1)
        remainder = feed[matched:]
        # SSM/hybrid prefill must thread recurrent state → token mode
        # (model.prefill fills the KV cache only; see models/transformer).
        # After a prefix hit the remainder starts at ``matched``: GQA prefill
        # resumes mid-sequence (position offset + attention over the cached
        # prefix pages); other attention kinds fall back to token mode.
        batched_ok = (self.cfg.family not in ("ssm", "hybrid")
                      and len(remainder) > 1
                      and (matched == 0 or self.cfg.attention_kind == "gqa"))
        chunkable = (self.prefill_chunk is not None
                     and self.cfg.attention_kind == "gqa"
                     and len(remainder) - 1 > self.prefill_chunk)
        if self.prefill_mode == "batched" and batched_ok:
            if chunkable:
                # chunked: defer to the tick loop's chunk planner — the slot
                # holds its reserved pages but stays out of decode until the
                # last chunk commits
                self.slot_prefill_todo[slot] = list(remainder)
                self.pending_prompt[slot] = []
            else:
                self._batched_prefill(slot, remainder, matched)
                self.pending_prompt[slot] = [remainder[-1]]
        else:
            # paper mode: prompt tokens stream through decode_step
            self.pending_prompt[slot] = list(remainder)
        if self.trace.enabled:
            state = ("prefilling" if (self.slot_prefill_todo[slot]
                                      or len(self.pending_prompt[slot]) > 1)
                     else "decoding")
            self.trace.lifecycle(req.uid, state, pid=self._tpid)
        if self.on_admit:
            self.on_admit(req, slot)

    def _batched_prefill(self, slot: int, feed: List[int],
                         matched: int = 0) -> None:
        """Run full-sequence prefill for one request (bucketed length) and
        hand the resulting cache rows to the backend — spliced into the live
        batch cache (dense) or written into the slot's pages (paged).
        ``matched`` > 0 resumes after a prefix-cache hit: positions offset by
        the cached span and the remainder attends the already-committed
        prefix pages."""
        # last prompt token goes through decode
        self._prefill_span(slot, feed[:-1], matched)

    def _prefill_fns(self) -> Tuple[CompileWatch, CompileWatch]:
        """The (fresh, resume) prefill jits behind this engine's compile
        watches (the jits themselves stay shared on the model)."""
        if self._prefill_watch is None:
            fresh, resume = _prefill_jits(self.model)
            self._prefill_watch = (
                CompileWatch(fresh, "prefill_fresh", self.trace,
                             on_compile=self._note_compile, pid=self._tpid),
                CompileWatch(resume, "prefill_resume", self.trace,
                             on_compile=self._note_compile, pid=self._tpid))
        return self._prefill_watch

    def _prefill_span(self, slot: int, tokens: List[int], start: int,
                      phase: str = "prefill") -> None:
        """Prefill ``tokens`` into positions ``start .. start+n`` of the
        slot's cache (bucketed length). ``start`` > 0 resumes mid-sequence:
        positions offset by the committed span (prefix-cache pages and/or
        earlier chunks) and the new tokens attend the committed k/v via
        ``prefix_kv``. Wall time spent here while other slots were mid-decode
        is charged to ``stats.decode_stall_s`` — the decode-starvation signal
        chunking exists to shrink."""
        n = len(tokens)
        if n <= 0:
            return
        t0 = time.time()
        with self._phase(phase):
            bucket = 1 << max(4, (n - 1).bit_length())
            bucket = min(bucket, self.max_len - start)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = tokens
            aidx = None
            if self.adapters is not None and self.slot_adapter[slot]:
                aidx = jnp.asarray([self.slot_adapter[slot]], jnp.int32)
            use_jit = self.cfg.attention_kind == "gqa" \
                and self.cfg.family not in ("ssm", "hybrid")
            if start:
                # pad the committed prefix to a power-of-two bucket (the
                # padded tail is masked by position inside the model) so
                # consecutive chunks hit the same compiled resume graph
                pref = self.kv.prefix_kv(slot, start)
                pbucket = min(1 << max(4, (start - 1).bit_length()),
                              self.max_len)
                if pbucket > start:
                    pad = [(0, 0)] * 5
                    pad[3] = (0, pbucket - start)
                    pref = {k: jnp.pad(v, pad) for k, v in pref.items()}
                _, sub_cache = self._dispatch(
                    self._prefill_fns()[1], self._effective_params(),
                    jnp.asarray(toks), self.max_len, jnp.int32(start), pref,
                    aidx)
            elif use_jit:
                args = (self._effective_params(), jnp.asarray(toks),
                        self.max_len, aidx)
                aot = self._cached_pref.get(("fresh", bucket, aidx is not None))
                if aot is not None:
                    try:
                        _, sub_cache = self._dispatch(aot, *args)
                    except ValueError:
                        # an input's placement drifted from the shardings the
                        # executable was lowered with (e.g. an adapter upload
                        # re-committed a leaf): the jit path re-canonicalizes
                        # placement, so fall back rather than fail the request
                        self.stats.aot_fallbacks += 1
                        aot = None
                if aot is None:
                    _, sub_cache = self._dispatch(self._prefill_fns()[0],
                                                  *args)
            else:
                kwargs = {} if aidx is None else {"adapter_idx": aidx}
                _, sub_cache = self.model.prefill(
                    self._effective_params(),
                    {"tokens": jnp.asarray(toks)}, self.max_len, **kwargs)
            with self._phase("kv_write"):
                self.kv.write_prefill(slot, start, sub_cache, n)
            self.pos[slot] = start + n
            stalled = [i for i in range(self.max_slots)
                       if i != slot and self._is_decoding(i)]
            if stalled:
                # charge real prefill compute, not just async dispatch time —
                # without the sync, the stall gauge under-reports on async
                # backends and the monolithic-vs-chunked A/B inverts
                jax.block_until_ready(sub_cache)
                dt = time.time() - t0
                self.stats.decode_stall_s += dt
                # each blocked decode slot experienced the full stall; SLO
                # attribution carves it out of that request's decode time
                for i in stalled:
                    self.slot_req[i].stall_s += dt

    def _advance_prefill(self) -> int:
        """Run the prefill chunks the scheduler planned for this tick.
        Returns the number of chunks advanced (each is one
        ``prefill_chunk``-token ``model.prefill`` segment); a slot whose todo
        list drops to its final prompt token transitions to decoding and
        joins this very tick's batch."""
        prefilling = [(i, self.slot_req[i]) for i in range(self.max_slots)
                      if self.slot_req[i] is not None
                      and self.slot_prefill_todo[i]]
        if not prefilling:
            return 0
        n_decoding = sum(1 for i in range(self.max_slots)
                         if self._is_decoding(i))
        advanced = 0
        for slot in self.scheduler.plan_prefill(prefilling, n_decoding):
            todo = self.slot_prefill_todo[slot]
            n = min(self.prefill_chunk, len(todo) - 1)
            self._prefill_span(slot, todo[:n], int(self.pos[slot]),
                               phase="prefill_chunk")
            req = self.slot_req[slot]
            req.prefill_chunks += 1
            self.stats.prefill_chunks += 1
            todo = todo[n:]
            if len(todo) == 1:
                self.pending_prompt[slot] = [todo[0]]
                self.slot_prefill_todo[slot] = []
            else:
                self.slot_prefill_todo[slot] = todo
            advanced += 1
        return advanced

    # -- capacity / preemption ------------------------------------------------------
    def _ensure_capacity(self, active: List[int]) -> List[int]:
        """Guarantee every active slot can write its next token. Evicts
        resident prefix pages first, then preempts victims (pages released,
        request re-queued with its generated tokens as prompt). DenseKV
        reports zero page cost, so this is a no-op there."""
        while True:
            need = sum(
                max(0, self.kv.pages_for(int(self.pos[i]) + 1)
                    - self.kv.slot_pages(i))
                for i in active)
            short = need - self.kv.pages_free
            if short <= 0:
                return active
            if self._pending:
                # under pressure with a tick in flight: finishing it may
                # release completed slots (freeing pages) and must precede
                # any preemption (the victim's replay needs its tokens)
                self._settle_pipeline()
                active = [i for i in active if self._is_decoding(i)]
                continue
            if self.prefix is not None:
                self._evict_prefix(short)
                if need <= self.kv.pages_free:
                    return active
            # victims may also be mid-chunked-prefill slots (not in the
            # decode ``active`` list) — their reserved pages are reclaimable
            pairs = self._active_pairs()
            victim = self.scheduler.pick_victim(pairs)
            if victim is None or len(pairs) <= 1:
                raise MemoryError(
                    "page pool exhausted: a single request's context exceeds "
                    "pool capacity (grow n_pages)")
            self._preempt(victim)
            active = [i for i in active if i != victim]

    def _preempt(self, slot: int) -> None:
        req = self.slot_req[slot]
        req.state = "preempted"
        req.n_preempts += 1
        self.stats.preemptions += 1
        self.trace.lifecycle(req.uid, "preempt", pid=self._tpid)
        self._release_slot(slot)
        self.scheduler.requeue(req)
        if self.on_preempt:
            self.on_preempt(req)

    def _release_slot(self, slot: int) -> None:
        req = self.slot_req[slot]
        # dense spill: the contiguous backend has no page trie, so a slot
        # whose prompt KV is fully committed parks a host copy in the tiered
        # store at release — the next request with the same prompt prefix
        # imports it instead of re-prefilling (the store's budget, not this
        # engine, decides how long it survives)
        if (self._dense_spill_ok and len(self.slot_feed[slot]) > 1
                and int(self.pos[slot]) >= len(self.slot_feed[slot])):
            feed = self.slot_feed[slot]
            key = self._dense_key(self.slot_adapter_key[slot], feed)
            if self.tiered.tier_of(key) != "host":
                self.tiered.put(key, self.kv.export_prefix(slot, len(feed)),
                                remat_cost=float(len(feed)))
                self.stats.kv_spilled_pages += 1
            self._dense_spill_lens.add(len(feed))
        if self.slot_adapter_key[slot] is not None:
            # unpin the exact version this slot acquired (hot-swap safe)
            self.adapters.release_key(self.slot_adapter_key[slot])
            self.slot_adapter_key[slot] = None
        self.slot_adapter[slot] = 0
        if self.prefix is not None:
            self.prefix.decref(self.slot_keys[slot])
        self.kv.release(slot, keep=self.slot_cached[slot])
        self.slot_req[slot] = None
        self.pending_prompt[slot] = []
        # preemption-safe partial-prefill release: committed chunk pages go
        # back to the pool (prefix-cache-owned lead pages excluded via keep=),
        # and a requeued request replays prefill from scratch on re-admission
        self.slot_prefill_todo[slot] = []
        self.slot_feed[slot] = []
        self.slot_keys[slot] = []
        self.slot_cached[slot] = 0
        self.slot_spec_adapt[slot] = None
        self.pos[slot] = 0

    # -- decode ---------------------------------------------------------------------
    def _adapter_idx(self) -> Optional[jax.Array]:
        """Per-slot device adapter index for the jitted decode (None when the
        engine serves a single personality — keeps the graph byte-identical
        to the pre-adapter path)."""
        if self.adapters is None:
            return None
        # copy: the async pipeline mutates slot_adapter (place/release)
        # while a dispatched tick may still read an aliased host buffer
        return jnp.asarray(self.slot_adapter.copy())

    def _sampling_vectors(self, active):
        """Per-slot sampling parameter vectors for the jitted samplers."""
        temps = np.zeros((self.max_slots,), np.float32)
        topks = np.zeros((self.max_slots,), np.int32)
        topps = np.ones((self.max_slots,), np.float32)
        seeds = np.zeros((self.max_slots,), np.int32)
        has_seed = np.zeros((self.max_slots,), bool)
        steps = np.zeros((self.max_slots,), np.int32)
        for i in active:
            req = self.slot_req[i]
            temps[i] = req.temperature
            topks[i] = req.top_k
            topps[i] = req.top_p
            if req.seed is not None:
                seeds[i] = req.seed
                has_seed[i] = True
            # seeded draws depend on (seed, tokens generated): count tokens
            # still in flight in pending ticks so a pipelined seeded slot
            # samples the exact step index the sequential engine would
            steps[i] = len(req.output) + self._inflight_emits(i)
        return temps, topks, topps, seeds, has_seed, steps

    def _fed_token(self, i: int) -> int:
        """The token decode consumes for slot ``i`` this tick: the next
        pending prompt token, else the last emitted one."""
        if self.pending_prompt[i]:
            return self.pending_prompt[i][0]
        return self.slot_req[i].output[-1]

    def _pop_pending(self, i: int) -> bool:
        """Consume the fed prompt token; True while the prompt is still
        being consumed (no emission this tick). When the prompt empties, its
        full pages are donated to the prefix trie — callers on the verify
        path must have committed the fed token's KV *first*, since a
        page-aligned prompt's last page is donated here."""
        req = self.slot_req[i]
        if not self.pending_prompt[i]:
            return False
        self.pending_prompt[i].pop(0)
        req.prefill_ticks += 1
        if self.pending_prompt[i]:
            return True
        self.trace.lifecycle(req.uid, "decoding", pid=self._tpid)
        if self.prefix is not None:
            keys = self.prefix.commit(self.slot_feed[i],
                                      self.pool.tables[i],
                                      self.slot_cached[i])
            self.slot_keys[i].extend(keys)
            self.slot_cached[i] += len(keys)
            if self.tiered is not None:
                for k in keys:
                    self.tiered.note_device(self._kv_key(k),
                                            self._page_nbytes,
                                            remat_cost=float(len(k)))
        return False

    def _emit_token(self, i: int, req: Request, tok: int, now: float,
                    pos_now: Optional[int] = None) -> bool:
        """Output-token bookkeeping shared by the single-token and verify
        ticks; returns True when the request finished (or vanished — an
        on_token callback may cancel requests mid-tick, so re-check slot
        ownership after it fires rather than double-releasing).
        ``pos_now`` overrides the live slot position for the max_len check —
        a deferred (pipelined) emission must judge completion at the
        position its own tick reached, not one a later begin advanced to."""
        if not req.output:
            req.t_first = now
        req.output.append(tok)
        self.stats.tokens_out += 1
        if self.on_token:
            self.on_token(req, tok, now)
        if self.slot_req[i] is not req:
            return True     # cancelled/released from inside the callback
        req.t_last = now
        pos_i = int(self.pos[i]) if pos_now is None else pos_now
        done = (len(req.output) >= req.max_new_tokens
                or (req.eos_id is not None and req.output[-1] == req.eos_id)
                or pos_i >= self.max_len)
        if done:
            req.t_done = now
            req.state = "done"
            self.stats.completed += 1
            self._release_slot(i)
            self.trace.lifecycle(req.uid, "done", pid=self._tpid)
            if self.on_done:
                self.on_done(req)
        return done

    # -- speculative decoding --------------------------------------------------
    def _spec_eligible(self, i: int) -> bool:
        """Drafting is worthwhile only when acceptance is decidable without
        perturbing the request's sampling contract: greedy (accept iff the
        draft is the argmax) or seeded (draws depend only on (seed, step),
        so the verify row reproduces the exact token the sequential sampler
        would emit). Unseeded stochastic slots keep one token per tick."""
        req = self.slot_req[i]
        s = req.sampling
        return (s.spec_k > 0
                and (s.temperature <= 0.0 or s.seed is not None)
                and len(self.pending_prompt[i]) <= 1)

    def _plan_drafts(self, active: List[int]) -> List[List[int]]:
        """Per-slot draft tokens for this tick (empty = plain decode).
        Width is capped by the request's remaining budget and cache room,
        then drafts are trimmed (longest first) until the worst-case commit
        fits the page pool — speculation is opportunistic and must never
        evict a prefix page or preempt a neighbour to make room."""
        drafts: List[List[int]] = [[] for _ in range(self.max_slots)]
        for i in active:
            req = self.slot_req[i]
            if not self._spec_eligible(i):
                continue
            k = min(req.sampling.spec_k,
                    req.max_new_tokens - len(req.output) - 1,
                    self.max_len - int(self.pos[i]) - 1)
            adapt = self.slot_spec_adapt[i]
            if adapt is not None:
                # adaptive width: the slot's live accept-rate EWMA names how
                # much of the request's spec_k ceiling is worth risking
                k = min(k, adapt.suggest(req.sampling.spec_k))
            # quantize to a pow2-minus-one width (1, 3, 7, 15): the verify
            # scan runs s_bucket sequential steps whatever the true draft
            # length, so a k=4 draft would pay for an 8-wide bucket — 3
            # steps of pure padding waste
            k = quantize_width(k)
            if k <= 0:
                continue
            proposed = propose(self._feed_tokens(req), k, self.spec_ngram)
            drafts[i] = proposed[:quantize_width(len(proposed))]
        if self.kv.supports_paging and any(drafts[i] for i in active):
            # _ensure_capacity already guaranteed the +1 pages; drafts may
            # only spend what is left beyond that baseline
            base_need = sum(
                max(0, self.kv.pages_for(int(self.pos[i]) + 1)
                    - self.kv.slot_pages(i))
                for i in active)
            budget = self.kv.pages_free - base_need

            def extra(i):
                return (self.kv.pages_for(int(self.pos[i]) + 1
                                          + len(drafts[i]))
                        - self.kv.pages_for(int(self.pos[i]) + 1))

            while sum(extra(i) for i in active) > budget:
                victim = max((i for i in active if drafts[i]),
                             key=lambda i: len(drafts[i]), default=None)
                if victim is None:
                    break
                drafts[victim] = []
        return drafts

    def _tick_verify(self, active: List[int],
                     drafts: List[List[int]]) -> None:
        """The speculative tick: one jitted ``verify_step`` scores every
        slot's fed token plus its drafts (width padded to a power of two),
        the per-position sampler names the token the sequential engine would
        have emitted at each step, and each slot commits exactly the
        accepted span — ``plan_emit`` truncates where the sequential engine
        would have stopped (budget / eos / max_len), so rejected drafts
        never reach the KV store and bookkeeping is step-identical."""
        with self._phase("spec_verify"):
            n_in = np.ones((self.max_slots,), np.int32)
            for i in active:
                n_in[i] = 1 + len(drafts[i])
            s_bucket = 1 << int(max(int(n_in[i])
                                    for i in active) - 1).bit_length()
            self._last_verify_width = s_bucket
            tokens = np.zeros((self.max_slots, s_bucket), np.int32)
            for i in active:
                row = [self._fed_token(i)] + drafts[i]
                tokens[i, :len(row)] = row
            temps, topks, topps, seeds, has_seed, steps = \
                self._sampling_vectors(active)

            state = self.kv.verify_state(active, self.pos, n_in, s_bucket)
            logits, spans = self._dispatch(
                self._verify, self._effective_params(), state,
                jnp.asarray(tokens), jnp.asarray(self.pos),
                self._adapter_idx())
        with self._phase("sample"):
            self.key, sub = jax.random.split(self.key)
            choice = np.asarray(self._dispatch(
                self._verify_sample,
                logits, sub, jnp.asarray(temps), jnp.asarray(topks),
                jnp.asarray(topps), jnp.asarray(seeds),
                jnp.asarray(has_seed), jnp.asarray(steps),
                use_topp=bool(np.any(topps < 1.0)),
                use_seeds=bool(np.any(has_seed))))

        now = time.time()
        self.stats.ticks += 1
        self.stats.spec_ticks += 1
        with self._phase("emit"):
            for i in active:
                req = self.slot_req[i]
                if req is None:
                    continue    # released by a callback earlier in the loop
                if len(self.pending_prompt[i]) > 1:
                    # mid-prompt (token-mode prefill): commit the fed token's
                    # KV and keep consuming — drafting was ineligible here
                    with self._phase("commit"), self._phase("kv_write"):
                        self.kv.commit_span(i, int(self.pos[i]), spans, 1)
                    self.pos[i] += 1
                    self._pop_pending(i)
                    continue
                acc = accepted_prefix(drafts[i], choice[i])
                emit = plan_emit(acc, choice[i],
                                 budget=req.max_new_tokens - len(req.output),
                                 room=self.max_len - int(self.pos[i]),
                                 eos_id=req.eos_id)
                # commit before _pop_pending: trie donation of a page-aligned
                # prompt needs the fed token's KV in its page already
                with self._phase("commit"), self._phase("kv_write"):
                    self.kv.commit_span(i, int(self.pos[i]), spans,
                                        len(emit))
                self._pop_pending(i)
                adapt = self.slot_spec_adapt[i]
                if adapt is not None and drafts[i]:
                    adapt.observe(len(drafts[i]), acc)
                req.spec_drafted += len(drafts[i])
                self.stats.spec_drafted += len(drafts[i])
                gained = max(0, len(emit) - 1)
                req.spec_accepted += gained
                self.stats.spec_accepted += gained
                for tok in emit:
                    self.pos[i] += 1
                    if self._emit_token(i, req, int(tok), now):
                        break

    def tick(self) -> None:
        """One decode step for the whole slot batch, preceded by the tick's
        chunked-prefill budget. A slot mid-chunked-prefill is excluded from
        the decode batch, so co-resident decode slots keep emitting every
        tick while its prompt streams in chunk by chunk. With
        ``spec_decode=True`` and any drafts on offer, the tick runs the
        multi-token verify instead and commits every accepted token.

        Internally one tick is ``tick_begin()`` (everything up to and
        including the sample dispatch) followed by ``tick_finish()``
        (materialize the sampled tokens + emit/eos/release bookkeeping).
        The sync path runs them back to back; the async runtime interleaves
        begin(N+1) before finish(N) so the device stays a tick ahead."""
        self.tick_begin()
        while self._pending:
            self.tick_finish()

    def tick_begin(self) -> PendingTick:
        """Dispatch one tick's device work without reading its results:
        admission, chunked prefill, decode + sample dispatch, KV commit,
        position advance and prompt-consumption bookkeeping all happen now;
        the sampled-token array stays on device inside the returned
        ``PendingTick`` (also appended to the engine's pending deque).

        Pipelining contract: a next ``tick_begin`` issued before the finish
        feeds in-flight slots their unmaterialized token via a device-side
        overlay (``jnp.where`` against the pending sample array), offsets
        seeded-sampling step indices by the in-flight count, and skips slots
        whose completion is already predictable (budget / max_len). Verify
        (spec) ticks and state-mutating scheduler paths settle the pipeline
        first — they need host-visible history."""
        p = PendingTick()
        t0 = time.perf_counter()
        p.busy0 = self._busy_ms()
        p.tokens0 = self.stats.tokens_out
        p.ticks0 = self.stats.ticks
        self._tick_gap_ms = None
        self._last_verify_width = 1
        with self.trace.span("tick", pid=self._tpid):
            self._tick_begin_impl(p)
        p.gap_ms = self._tick_gap_ms
        p.verify_width = self._last_verify_width
        p.begin_s = time.perf_counter() - t0
        self._pending.append(p)
        return p

    def tick_finish(self) -> None:
        """Materialize the oldest pending tick and run its deferred host
        work: read the sampled tokens, append/emit/eos/release per slot, add
        the tick's wall to the stats ledger and fire ``on_tick``. A slot
        whose request changed since begin (released by an earlier finish
        discovering eos, or cancelled) skips its stale emission."""
        if not self._pending:
            return
        p = self._pending.popleft()
        t0 = time.perf_counter()
        with self.trace.span("tick_finish", pid=self._tpid):
            if p.nxt_dev is not None:
                with self._phase("wait_device"):
                    nxt = np.asarray(p.nxt_dev)
                now = time.time()
                with self._phase("emit"):
                    for i, req, pos_i in p.emits:
                        if self.slot_req[i] is not req:
                            continue    # released/cancelled since begin
                        self._emit_token(i, req, int(nxt[i]), now,
                                         pos_now=pos_i)
        wall_ms = (p.begin_s + time.perf_counter() - t0) * 1e3
        self.stats.tick_wall_ms_sum += wall_ms
        if self.on_tick is not None:
            self.on_tick({
                "wall_ms": wall_ms,
                "busy_ms": self._busy_ms() - p.busy0,
                "gap_ms": p.gap_ms,
                "tokens": self.stats.tokens_out - p.tokens0,
                "ticked": self.stats.ticks > p.ticks0,
                "active": sum(1 for r in self.slot_req if r is not None),
                "prefilling": sum(1 for t in self.slot_prefill_todo if t),
                "verify_width": p.verify_width,
                "dispatch_ahead_depth": len(self._pending),
            })

    def _tick_begin_impl(self, p: PendingTick) -> None:
        with self._phase("schedule"):
            self._prefetch_queue()
            with self._phase("admit"):
                self._admit()
        chunks = self._advance_prefill()
        active = [i for i in range(self.max_slots) if self._is_decoding(i)
                  and not self._slot_done_inflight(i)]
        if active:
            with self._phase("schedule"):
                active = self._ensure_capacity(active)
                active = [i for i in active
                          if not self._slot_done_inflight(i)]
        if not active:
            if chunks:
                self.stats.ticks += 1   # prefill-only tick still progresses
            return

        if self.spec_decode:
            # drafting proposes from host-visible history — settle any
            # pipelined tick so the proposer sees every emitted token
            self._settle_pipeline()
            active = [i for i in active if self._is_decoding(i)]
            if not active:
                if chunks:
                    self.stats.ticks += 1
                return
            with self._phase("schedule"):
                drafts = self._plan_drafts(active)
            if any(drafts[i] for i in active):
                self._tick_verify(active, drafts)
                return

        with self._phase("decode"):
            tokens = np.zeros((self.max_slots,), np.int32)
            overlay: List[int] = []
            for i in active:
                if not self.pending_prompt[i] and self._inflight_emits(i):
                    # fed token is still on device (previous tick's sample)
                    overlay.append(i)
                else:
                    tokens[i] = self._fed_token(i)
            temps, topks, topps, seeds, has_seed, steps = \
                self._sampling_vectors(active)

            fed = jnp.asarray(tokens)
            if overlay:
                # per-slot device overlay: feed each in-flight slot the
                # sample array of the *latest* pending tick that emitted for
                # it (with depth 1 that is simply the newest pending)
                by_src: Dict[int, Tuple[PendingTick, List[int]]] = {}
                for i in overlay:
                    for q in reversed(self._pending):
                        if any(j == i and r is self.slot_req[i]
                               for j, r, _ in q.emits):
                            by_src.setdefault(id(q), (q, []))[1].append(i)
                            break
                for q, slots in by_src.values():
                    mask = np.zeros((self.max_slots,), bool)
                    mask[slots] = True
                    fed = jnp.where(jnp.asarray(mask), q.nxt_dev, fed)
            # snapshot live engine buffers: without the sync path's
            # materialization barrier the dispatch is truly async, and
            # jnp.asarray may alias host numpy memory on CPU — the pos
            # advance below must not race the in-flight compute
            state = self.kv.decode_state(active, self.pos)
            logits, new_state = self._dispatch(
                self._decode, self._effective_params(), state,
                fed, jnp.asarray(self.pos.copy()),
                self._adapter_idx())
            self.stats.decode_steps += 1
        with self._phase("commit"):
            self.kv.commit(new_state, active, self.pos)
        with self._phase("sample"):
            self.key, sub = jax.random.split(self.key)
            p.nxt_dev = self._dispatch(
                self._sample,
                logits, sub, jnp.asarray(temps),
                jnp.asarray(topks), jnp.asarray(topps),
                jnp.asarray(seeds), jnp.asarray(has_seed),
                jnp.asarray(steps),
                use_topp=bool(np.any(topps < 1.0)),
                use_seeds=bool(np.any(has_seed)))

        self.stats.ticks += 1
        p.active = active
        for i in active:
            req = self.slot_req[i]
            if req is None:
                continue
            self.pos[i] += 1
            if self._pop_pending(i):
                continue  # still consuming the prompt — no emission
            p.emits.append((i, req, int(self.pos[i])))
            # predictable completion (budget / max_len): count every token
            # already emitted, in flight in older pending ticks, and this
            # tick's own pending emission
            n_out = (len(req.output) + self._inflight_emits(i)) + 1
            if n_out >= req.max_new_tokens or self.pos[i] >= self.max_len:
                p.done_slots.add(i)
