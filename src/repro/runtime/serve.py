"""Policy-driven serving front-end over the batched decode loop.

The decode step is the ``serve_step`` the dry-run lowers for the decode_32k
/ long_500k cells.  ``ServeEngine`` adds the production affordances around
it: a policy-driven admission scheduler, fixed decode slots (static shapes
— no recompilation), per-request sampling, per-slot stop handling, and
streaming request handles.

Request API
-----------
``submit`` takes a ``Request`` — prompt, token budget, ``SamplingParams``
(temperature / top-k / top-p / per-request seed / multi-token stop
sequences), a ``tenant`` for fairness accounting and a ``priority`` — and
returns a ``RequestHandle`` whose lifecycle walks ``QUEUED -> PREFILL ->
DECODE -> FINISHED(reason)`` and whose ``tokens()`` iterator streams output
tokens as the engine produces them.  Engine construction takes a
``ServeConfig``; the pre-PR-3 keyword sprawl still works through a
deprecation shim (see docs/serving.md for the migration table).

Admission scheduling (``ServeConfig.policy``)
---------------------------------------------
Each engine tick splits into a *decide* phase — ``runtime/scheduler.py``'s
``Scheduler.decide()`` assigns queued requests to freed slots under a
pluggable ``AdmissionPolicy`` (``fcfs`` / ``priority`` / ``sjf`` /
``drf-fair``), pure host bookkeeping — and an *execute* phase that runs
the compiled prefill/decode steps for the decisions.  ``drf-fair`` charges
each tenant's slot-and-KV usage through ``core/drf.py``'s ``DRFAllocator``
(the paper's Mesos DRF, pointed at serving), so no tenant starves the
pool.  Policies never touch device state.

Continuous batching (``mode="continuous"``, the default)
--------------------------------------------------------
Any freed slot immediately admits the scheduler's next choice at its *own*
position — there is no wave barrier.  The decode step takes a per-slot
position vector ``pos[B]`` (free slots parked at -1); when any live slot
samples, the tick dispatches to a sampled variant that additionally takes
the per-slot sampling arrays (``temp/top_k/top_p/keys``), so every slot
attends its own prefix and draws its own token in one ragged kernel call
— rows with ``temperature <= 0`` stay bitwise-greedy, and an all-greedy
tick never pays the sampling math.  Prompts are consumed by
**chunked prefill** where the architecture allows it; SSM/hybrid plans
fall back to per-slot token feeding with slot state zeroed on admission.

Preemption & SLO tiers (``ServeConfig.preempt``, ``tenant_weights``)
--------------------------------------------------------------------
Admission alone cannot undo a grab, so ``preempt=True`` makes the decide
phase two-phase (Mesos-style revocation): when a queued tenant's weighted
DRF share would stay strictly below a running tenant's, the scheduler
evicts a victim (``victim_policy``: ``youngest-first`` /
``lowest-weight-share-first``) and the executor checkpoints its slot —
decode position, last token, and KV state.  Paged checkpoints are
zero-copy (the page chain detaches from the slot, refcounts intact);
dense checkpoints snapshot the slot's cache stripe to a host buffer via
the models' ``copy_cache_out``/``copy_cache_in`` pair.  The request
re-enters the queue as ``PREEMPTED`` and later resumes into *any* free
slot at ``pos = checkpoint`` without re-running prefill, producing the
bitwise-identical token stream (sampling keys fold the absolute
position, never the slot).  ``tenant_weights`` maps SLO tiers onto DRF
shares — ``{"gold": 3, "free": 1}`` converges to a 3:1 slot split under
contention.

``mode="wave"`` keeps the legacy lockstep engine — admit a fresh wave only
when every slot is free, all slots decode at one scalar position — as the
baseline ``benchmarks/serve_throughput.py`` measures continuous batching
against (the serving analogue of the paper's exclusive, non-co-scheduled
mode).  Sampled requests are served by drawing host-side from the wave
logits through the same position-keyed ``sample_tokens``, so a seeded
request decodes the identical trajectory in either mode.

Speculative decode (``ServeConfig.draft_k``, continuous mode)
-------------------------------------------------------------
``draft_k > 0`` turns every decode tick into draft -> verify -> accept:
a host-side drafter (``runtime/draft.py``, default model-free n-gram
lookup over the slot's own history) proposes up to ``draft_k``
continuation tokens per slot, ONE compiled multi-token step scores the
feed token plus all drafts at per-slot positions (causal within the
draft), and the engine emits the longest verified prefix plus the free
correction token — one token minimum, ``draft_k + 1`` maximum per tick.
Greedy output is bitwise-identical to plain decode; sampled output is
bitwise-identical to the same seed's non-speculative trajectory (each
row folds its absolute position into the slot's key).  Rejected drafts
roll back by pure position truncation — dense: stale K/V beyond ``pos``
is never attended and is overwritten when reached; paged: draft writes
land only in the slot's already-reserved pages (padding past the span
hits the null page), so no page is ever allocated, freed, or leaked by
speculation and preemption checkpoints compose unchanged.

Paged KV cache (``cache="paged"``, continuous mode only)
--------------------------------------------------------
``cache="paged"`` swaps the dense per-slot ``(max_len)`` HBM stripes for a
global page pool (``runtime/kv_pool.py``): admission reserves exactly
``ceil((prompt + max_new) / page_size)`` pages, the scheduler queues with
**backpressure** when the pool is exhausted (``step`` never raises), and a
prefix cache admits shared prompts at ``pos = matched`` with copy-on-write
pages.  See docs/paged_kv.md.

All step functions keep static shapes and donate the caches, so each mode
compiles exactly once per (slots, max_len) and decodes in place.  Dense
continuous decode additionally picks its split-K fan-out per tick from
``(max(pos), live slots)`` (``steps.pick_decode_splits``) when
``RuntimeKnobs.decode_splits`` is 0 (auto).
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.draft import get_drafter
from repro.runtime.kv_pool import KVCacheManager
from repro.runtime.sampling import (SamplingParams, matches_stop,
                                    sample_tokens, speculative_accept)
from repro.runtime.scheduler import Scheduler
from repro.runtime.steps import (compiled_fn, compiled_step,
                                 pick_decode_splits)
from repro.runtime.telemetry import Telemetry

__all__ = ["Checkpoint", "Request", "RequestHandle", "RequestState",
           "SamplingParams", "ServeConfig", "ServeEngine", "ServeStalled",
           "request_metrics"]


def request_metrics(req: "Request") -> dict:
    """Per-request latency from the lifecycle stamps: time-to-first-token
    (``ttft_s``, includes queue wait — the quantity admission policies
    trade) and time-per-output-token (``tpot_s``).  Entries whose stamps
    the lifecycle has not reached yet are omitted.  The single source of
    the formulas — ``RequestHandle.metrics()`` and the benchmarks'
    percentile aggregation both call this."""
    out = {}
    if req.t_submit is not None and req.t_first is not None:
        out["ttft_s"] = req.t_first - req.t_submit
    if req.t_first is not None and req.t_finish is not None \
            and len(req.output) > 1:
        out["tpot_s"] = (req.t_finish - req.t_first) / (len(req.output) - 1)
    return out


def _abstract(x):
    """An array's shape and dtype, and its layout where it is split over
    devices, for lowering a step again as the call compiled it."""
    sharding = getattr(x, "sharding", None)
    if sharding is not None and len(sharding.device_set) == 1:
        sharding = None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _ckpt_fns(model, max_len: int):
    """(copy_out, copy_in) jitted pair for dense checkpoint/restore,
    memoized in ``runtime.steps``' shared compiled-callable LRU (keyed
    on (kind, cfg, knobs, max_len)) so replay/extra engines over the
    same model don't recompile."""
    def build_out():
        axes = model.cache_batch_axes(max_len)
        return lambda caches, slot: model.copy_cache_out(caches, slot,
                                                         axes)

    def build_in():
        axes = model.cache_batch_axes(max_len)
        return lambda caches, snap, slot: model.copy_cache_in(
            caches, snap, slot, axes)

    base = (model.cfg, model.knobs, max_len)
    return (compiled_fn(("copy_out",) + base, build_out),
            compiled_fn(("copy_in",) + base, build_in, donate=(0,)))


class ServeStalled(RuntimeError):
    """``run()`` exhausted its tick budget with requests undrained, or a
    streaming handle stopped making progress."""


class RequestState(enum.Enum):
    QUEUED = "queued"      # submitted, waiting for the scheduler
    PREFILL = "prefill"    # consuming the prompt (chunked or token feed)
    DECODE = "decode"      # generating
    PREEMPTED = "preempted"  # checkpointed + requeued; resumes at pos
    FINISHED = "finished"  # done; see Request.finish_reason


@dataclass
class Checkpoint:
    """A preempted request's resume point.  ``pages`` (paged cache) is
    the detached page chain — the K/V never left HBM; ``kv`` (dense) is
    the host-side snapshot of the slot's cache stripe."""

    pos: int  # decode position to resume at
    last_token: int  # the token to feed at ``pos``
    pages: Optional[list] = None
    kv: object = None


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int = 16
    eos_id: int = -1  # -1: never stops early
    sampling: SamplingParams = field(default_factory=SamplingParams)
    tenant: str = "default"  # drf-fair accounting unit
    priority: int = 0  # higher admits first under policy="priority"
    output: list = field(default_factory=list)
    done: bool = False
    state: RequestState = RequestState.QUEUED
    finish_reason: Optional[str] = None  # "eos" | "stop" | "length"
    preempt_count: int = 0  # times this request was checkpointed
    # wall-clock lifecycle stamps (time.perf_counter seconds)
    t_submit: Optional[float] = None
    t_first: Optional[float] = None
    t_finish: Optional[float] = None


class RequestHandle:
    """Caller-facing view of a submitted request.

    ``tokens()`` yields output tokens incrementally; when the engine has
    not yet produced the next token the iterator *drives* it (one
    ``engine.step()`` per attempt — which also serves every other live
    slot), so ``for tok in handle.tokens():`` streams a request to
    completion.  ``result()`` drains and returns the finished ``Request``.
    """

    def __init__(self, req: Request, engine: "ServeEngine"):
        self.req = req
        self._engine = engine

    @property
    def state(self) -> RequestState:
        return self.req.state

    @property
    def finish_reason(self) -> Optional[str]:
        return self.req.finish_reason

    @property
    def done(self) -> bool:
        return self.req.done

    @property
    def output(self) -> list:
        return list(self.req.output)

    def tokens(self, max_ticks: int = 100_000) -> Iterator[int]:
        i = stalled = 0
        while True:
            while i < len(self.req.output):
                stalled = 0
                yield self.req.output[i]
                i += 1
            if self.req.done:
                return
            self._engine.step()
            stalled += 1
            if stalled > max_ticks:
                raise ServeStalled(
                    f"request {self.req.req_id} produced no token in "
                    f"{max_ticks} ticks (state={self.req.state.value})")

    def result(self, max_ticks: int = 100_000) -> Request:
        for _ in self.tokens(max_ticks=max_ticks):
            pass
        return self.req

    def metrics(self) -> dict:
        """Per-request latency (see ``request_metrics``)."""
        return request_metrics(self.req)


@dataclass(frozen=True)
class ServeConfig:
    """Engine construction knobs, replacing the pre-PR-3 keyword sprawl.

    ``policy`` names an admission policy from
    ``runtime.scheduler.ADMISSION_POLICIES``; ``on_stall`` decides whether
    ``run()`` raises (``"raise"``, default) or warns and returns partial
    results (``"warn"``) when its tick budget is exhausted with requests
    undrained.

    ``tenant_weights`` maps tenant names onto weighted-DRF shares (SLO
    tiers; unlisted tenants weigh 1).  ``preempt=True`` lets the decide
    phase reclaim running slots when a swap strictly improves weighted
    fairness; ``victim_policy`` (``runtime.scheduler.VICTIM_POLICIES``)
    picks who gets checkpointed.

    ``draft_k > 0`` enables speculative decode (continuous mode,
    attention-only plans): every decode tick scores up to ``draft_k``
    drafted tokens per slot in one multi-token verify step and emits the
    accepted prefix plus the free correction token — bitwise-identical
    output, fewer ticks.  ``drafter`` names a ``runtime.draft.DRAFTERS``
    entry (default: model-free prompt/n-gram lookup)."""

    batch_slots: int = 4
    max_len: int = 128
    mode: str = "continuous"
    prefill_chunk: int = 32
    cache: str = "dense"
    page_size: int = 16
    num_pages: Optional[int] = None
    page_policy: str = "pack"
    prefix_cache: bool = True
    # quantized paged KV: "" (store at RuntimeKnobs.cache_dtype), "int8"
    # or "fp8" (float8_e4m3fn).  Pages hold quantized K/V with per-token
    # per-head f32 scales alongside ("k_scale"/"v_scale" pool leaves);
    # the attention kernels dequantize at read — ~2x pages per HBM byte
    # at int8.  Requires cache="paged"; composes with prefix sharing and
    # disaggregation (scales travel with pages).  See docs/paged_kv.md.
    kv_dtype: str = ""
    policy: str = "fcfs"
    on_stall: str = "raise"
    tenant_weights: Optional[dict] = None
    preempt: bool = False
    victim_policy: str = "youngest-first"
    draft_k: int = 0
    drafter: str = "ngram"
    # disaggregated serving role (runtime/disagg.py): "prefill" engines
    # run chunked prefill and surrender the finished slot to a handoff;
    # "decode" engines only accept handed-off (checkpointed) requests
    role: str = "unified"
    # device mesh for ONE sharded replica, e.g. (2, 4) = 2 data hosts x
    # TP 4 (see launch/mesh.py make_serve_mesh): the "model" axis carries
    # gather-form tensor parallelism through the layer stack, the leading
    # data axes shard the decode slots and split the KV page pool into
    # per-host sub-pools.  None (default): single-device engine.  The
    # sharded engine's token streams are bitwise-identical to the
    # unsharded one (docs/serving.md, tests/test_sharded_serve.py).
    mesh_shape: Optional[tuple] = None


_CONFIG_FIELDS = {f.name for f in dataclasses.fields(ServeConfig)}


class ServeEngine:
    def __init__(self, model, params, config: Optional[ServeConfig] = None,
                 *, mesh=None, cache_shardings=None, telemetry=None,
                 replica: int = 0, **legacy):
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass either a ServeConfig or legacy keyword arguments, "
                    "not both")
            unknown = set(legacy) - _CONFIG_FIELDS
            if unknown:
                raise TypeError(f"unknown ServeEngine arguments: "
                                f"{sorted(unknown)}")
            warnings.warn(
                "ServeEngine(batch_slots=..., max_len=..., ...) keyword "
                "construction is deprecated; pass ServeConfig(...) instead "
                "(see docs/serving.md for the migration table)",
                DeprecationWarning, stacklevel=2)
            config = ServeConfig(**legacy)
        elif config is None:
            config = ServeConfig()
        assert config.mode in ("continuous", "wave"), config.mode
        assert config.cache in ("dense", "paged"), config.cache
        assert config.on_stall in ("raise", "warn"), config.on_stall
        if config.preempt and config.mode != "continuous":
            raise ValueError("preempt=True requires mode='continuous' "
                             "(wave slots drain in lockstep)")
        if config.draft_k < 0:
            raise ValueError(f"draft_k must be >= 0: {config.draft_k}")
        if config.role not in ("unified", "prefill", "decode"):
            raise ValueError(f"unknown role {config.role!r} "
                             f"(expected unified/prefill/decode)")
        if config.role != "unified":
            if config.mode != "continuous":
                raise ValueError("disaggregated roles require "
                                 "mode='continuous'")
            if not model.supports_chunked_prefill():
                raise ValueError(
                    f"disaggregated roles need chunked prefill, unsupported "
                    f"for family={model.cfg.family!r} (token-feed prefill "
                    f"cannot hand off mid-prompt)")
        if config.draft_k:
            if config.mode != "continuous":
                raise ValueError("speculative decode (draft_k > 0) requires "
                                 "mode='continuous'")
            if not model.supports_speculative():
                raise ValueError(
                    f"speculative decode unsupported for "
                    f"family={model.cfg.family!r} (SSM state advances one "
                    f"token at a time)")
            if config.draft_k + 1 >= config.max_len:
                raise ValueError(f"draft_k {config.draft_k} too deep for "
                                 f"max_len {config.max_len}")
        if config.kv_dtype:
            if config.cache != "paged":
                raise ValueError("kv_dtype requires cache='paged' (dense "
                                 "caches store at RuntimeKnobs.cache_dtype)")
            if config.kv_dtype not in ("int8", "fp8"):
                raise ValueError(f"unknown kv_dtype {config.kv_dtype!r} "
                                 f"(expected int8/fp8)")
            # quantization is a model-layout property: rebuild with the
            # kv_quant knob so cache init/update/attention all agree (the
            # knob keys the compiled-step cache, so quantized and plain
            # engines over one config never share a step)
            if model.knobs.kv_quant != config.kv_dtype:
                model = type(model)(
                    model.cfg,
                    model.knobs.with_(kv_quant=config.kv_dtype))
        # ---- device mesh: shard this replica without changing its output
        self._batch_sharding = None
        self._num_hosts = 1
        if mesh is None and config.mesh_shape is not None:
            from repro.launch.mesh import make_serve_mesh
            mesh = make_serve_mesh(config.mesh_shape)
        if mesh is not None:
            if config.mode != "continuous":
                raise ValueError("sharded serving (mesh / mesh_shape) "
                                 "requires mode='continuous'")
            if model.knobs.use_pallas:
                raise ValueError(
                    "sharded serving requires the XLA path "
                    "(RuntimeKnobs.use_pallas=False): the Pallas decode "
                    "kernels are single-device and do not partition")
            from repro.sharding import (ServeShardFn, serve_batch_sharding,
                                        serve_cache_shardings,
                                        serve_param_shardings)
            # rebuild the model with the gather-form TP seams threaded
            # through the layer stack; ServeShardFn hashes on the mesh,
            # so engines over the same mesh still share compiled steps
            model = type(model)(model.cfg,
                                model.knobs.with_(
                                    shard_fn=ServeShardFn(mesh)))
            params = jax.device_put(
                params, serve_param_shardings(mesh, model.cfg, params))
            self._batch_sharding = serve_batch_sharding(
                mesh, config.batch_slots)
            if self._batch_sharding is not None:
                # slot dim sharded over the data axes -> each host row
                # decodes a contiguous block of slots; the KV page pool
                # splits into per-host sub-pools so a slot's page chain
                # stays on the host that computes its queries
                self._num_hosts = 1
                for ax in ("pod", "data"):
                    self._num_hosts *= dict(mesh.shape).get(ax, 1)
        self.config = config
        self.model = model
        self.params = params
        self.role = config.role
        self.slots = config.batch_slots
        self.max_len = config.max_len
        self.mode = config.mode
        self.mesh = mesh
        self.cache = config.cache
        batch_slots, max_len = config.batch_slots, config.max_len
        self.active: list[Optional[Request]] = [None] * batch_slots
        self.pos = np.full(batch_slots, -1, dtype=np.int32)
        self.tokens = np.zeros((batch_slots, 1), dtype=np.int32)
        # per-slot sampling arrays: one compiled step serves any mix of
        # greedy (temp 0) and sampled requests
        self.samp_temp = np.zeros(batch_slots, np.float32)
        self.samp_topk = np.zeros(batch_slots, np.int32)
        self.samp_topp = np.ones(batch_slots, np.float32)
        self.samp_keys = np.zeros((batch_slots, 2), np.uint32)
        self._finished: list[Request] = []
        self._admit_emitted = 0  # tokens emitted by chunked prefill
        # jitted steps come from runtime.steps' module-level LRU: engines
        # over equal (cfg, knobs) share one compiled callable per step
        self._decode_one = compiled_step(model, "decode_one")
        # compiled step -> the abstract arguments of its first call
        self._ran: dict = {}
        # checkpoint/restore (dense): built on first preemption
        self._copy_out = self._copy_in = None
        self.kv: Optional[KVCacheManager] = None
        self._pf_buf = None  # dense (1, max_len) slot view, XLA paged only
        if config.cache == "paged":
            if config.mode != "continuous":
                raise ValueError("cache='paged' requires mode='continuous'")
            if not model.supports_paged_cache():
                raise ValueError(
                    f"paged KV cache unsupported for "
                    f"family={model.cfg.family!r}")
            page_size = config.page_size
            if max_len % page_size:
                raise ValueError(f"max_len {max_len} not a multiple of "
                                 f"page_size {page_size}")
            # prefill chunks must cover whole pages at page-aligned
            # offsets; C also divides max_len so chunk writes never clamp
            c = max(page_size,
                    (min(config.prefill_chunk, max_len) // page_size)
                    * page_size)
            while max_len % c:
                c -= page_size
            self.prefill_chunk = c
            self.chunked = True
            # dense-equivalent capacity by default (+ the null page);
            # benchmarks pass a smaller pool to realize the HBM saving
            num_pages = config.num_pages
            if num_pages is None:
                num_pages = batch_slots * (max_len // page_size) + 1
            if self._num_hosts > 1:
                # host sub-pools must tile the pool evenly (the device
                # page dim shards over the data axes): round capacity UP
                # so a caller-sized pool never silently shrinks
                num_pages = -(-num_pages // self._num_hosts) \
                    * self._num_hosts
            self.kv = KVCacheManager(
                slots=batch_slots, max_len=max_len, page_size=page_size,
                num_pages=num_pages, policy=config.page_policy,
                prefix_cache=config.prefix_cache, chunk=c,
                num_hosts=self._num_hosts)
            # the pool may round capacity up (num_hosts alignment): size
            # the device pools from what it actually holds, never the ask
            num_pages = self.kv.pool.num_pages
            make_caches = functools.partial(model.init_cache_paged,
                                            num_pages, page_size)
            # greedy and sampled variants both exist (jit is lazy — only
            # the ones a trace actually hits compile); a tick pays the
            # sampling math only when a live slot has temperature > 0
            self._step = compiled_step(model, "paged_serve",
                                       page_size=page_size)
            self._step_sampled = compiled_step(model, "paged_serve",
                                               page_size=page_size,
                                               sampled=True)
            if model.knobs.use_pallas:
                # fused paged prefill kernel reads K/V through the page
                # table — no dense slot view to maintain
                self._pf_buf = None
                self._prefill = compiled_step(
                    model, "paged_prefill_chunk", page_size=page_size)
                self._prefill_sampled = compiled_step(
                    model, "paged_prefill_chunk", page_size=page_size,
                    sampled=True)
            else:
                # XLA path: carry one dense (1, max_len) slot view across
                # the chunk loop so each chunk inserts C rows instead of
                # re-gathering the whole page chain (the gather variant
                # rebuilds the view once on a prefix-cache hit)
                self._pf_buf = model.init_cache(1, max_len)
                self._prefill = compiled_step(
                    model, "paged_prefill_chunk_buf", page_size=page_size)
                self._prefill_sampled = compiled_step(
                    model, "paged_prefill_chunk_buf", page_size=page_size,
                    sampled=True)
                self._prefill_gather = compiled_step(
                    model, "paged_prefill_chunk_buf_gather",
                    page_size=page_size)
                self._prefill_gather_sampled = compiled_step(
                    model, "paged_prefill_chunk_buf_gather",
                    page_size=page_size, sampled=True)
        else:
            make_caches = functools.partial(model.init_cache, batch_slots,
                                            max_len)
            self._step = compiled_step(model, "serve")
            self._step_sampled = compiled_step(model, "serve", sampled=True)
            # chunked prefill: one compiled (1, C) step reused for every
            # slot and offset; C rounded down to a divisor of max_len so
            # padded chunk writes never clamp out of bounds.
            self.chunked = (config.mode == "continuous"
                            and config.prefill_chunk > 1
                            and model.supports_chunked_prefill())
            c = max(1, min(config.prefill_chunk, max_len))
            while max_len % c:
                c -= 1
            self.prefill_chunk = c
            if self.chunked:
                self._prefill = compiled_step(model, "prefill_chunk")
                self._prefill_sampled = compiled_step(model, "prefill_chunk",
                                                      sampled=True)
        # speculative decode: one verify step of static width T = k + 1
        # per (cache layout, sampled) variant; the drafter is pure host
        self.draft_k = config.draft_k
        if self.draft_k:
            self.drafter = get_drafter(config.drafter)
            spec_kind = ("paged_spec_serve" if config.cache == "paged"
                         else "spec_serve")
            spec_ps = config.page_size if config.cache == "paged" else 0
            self._spec_step = compiled_step(
                model, spec_kind, page_size=spec_ps, draft_len=self.draft_k)
            self._spec_step_sampled = compiled_step(
                model, spec_kind, page_size=spec_ps, draft_len=self.draft_k,
                sampled=True)
            # acceptance telemetry: proposed/accepted draft tokens and
            # how many tokens each spec tick emitted
            self.spec_proposed = 0
            self.spec_accepted = 0
            self.spec_emitted = 0
            self.spec_ticks = 0
        if mesh is not None and cache_shardings is None:
            # default layout: KV-head dim over "model" (each TP shard
            # attends its own heads), slot/page dim over the data axes
            # (serve_cache_shardings — NOT the training cache rules,
            # which shard the sequence dim and would psum softmax stats)
            cache_shardings = serve_cache_shardings(
                mesh, jax.eval_shape(make_caches),
                paged=(config.cache == "paged"))
        # a sharded cache is created in its layout: no device ever holds
        # the whole pool, which may not fit one device
        self.caches = (make_caches() if cache_shardings is None else
                       jax.jit(make_caches, out_shardings=cache_shardings)())
        if mesh is not None and self._pf_buf is not None:
            # the XLA prefill's slot view splits its KV heads like the pool
            self._pf_buf = jax.device_put(
                self._pf_buf, serve_cache_shardings(mesh, self._pf_buf))
        # decide/execute split: the scheduler owns the queue, the policy,
        # the per-tenant (weighted) DRF accounting, and the preemption
        # victim policy — host state only
        self.scheduler = Scheduler(config.policy, slots=batch_slots,
                                   max_len=max_len, kv=self.kv,
                                   weights=config.tenant_weights,
                                   preempt=config.preempt,
                                   victim=config.victim_policy)
        # split-K autotune (Pallas decode, dense AND paged): pick the
        # fan-out per tick from (max(pos), live slots); each compiles
        # once.  The paged variant tiles by whole pages, so the picker
        # gets page_size and constrains splits to divide max_pages.
        self._autotune = (config.cache in ("dense", "paged")
                          and config.mode == "continuous"
                          and model.knobs.use_pallas
                          and model.knobs.decode_splits == 0)
        # SSM/hybrid state is not position-masked: zero a slot on admission
        self._needs_reset = model.cfg.family in ("ssm", "hybrid")
        if self._needs_reset:
            self._reset = self._make_slot_reset(model, max_len)
        # telemetry: every engine binds a Telemetry sink (a private one by
        # default — metrics always on, tracing off unless the caller
        # passes Telemetry(trace=True)); a ClusterRouter rebinds its
        # replicas onto one shared sink with per-replica labels
        self.bind_telemetry(telemetry, replica=replica)

    def bind_telemetry(self, telemetry: Optional[Telemetry] = None, *,
                       replica: int = 0) -> None:
        """Bind (or rebind — replica rejoin reuses this) the engine and
        its scheduler/KV manager to a ``Telemetry`` sink.  Registry
        series carry a ``replica`` label; trace events use the replica id
        as their ``pid`` track.  Hot-path counter children are prebound
        here so a tick increments a float, never does a dict lookup."""
        self.tm = telemetry if telemetry is not None else Telemetry()
        self.replica = int(replica)
        reg = self.tm.registry
        lbl = {"replica": str(self.replica)}
        self._m_ticks = reg.counter(
            "engine_ticks_total", "engine ticks stepped",
            ("replica",)).labels(**lbl)
        self._m_tokens = reg.counter(
            "engine_tokens_total", "output tokens emitted",
            ("replica",)).labels(**lbl)
        self._m_submitted = reg.counter(
            "engine_requests_submitted_total", "requests submitted",
            ("replica",)).labels(**lbl)
        self._m_finished = reg.counter(
            "engine_requests_finished_total",
            "requests finished, by finish reason", ("replica", "reason"))
        reg.gauge("engine_live_slots", "slots holding an active request",
                  ("replica",)).labels(**lbl).set_function(
            lambda: sum(r is not None for r in self.active))
        reg.gauge("engine_queue_depth", "requests awaiting admission",
                  ("replica",)).labels(**lbl).set_function(
            lambda: len(self.scheduler.queue))
        if self.draft_k:
            # function-backed: the spec tick's tight loop keeps bumping
            # plain attributes; the registry reads them at export time
            for name, attr in (("engine_spec_proposed", "spec_proposed"),
                               ("engine_spec_accepted", "spec_accepted"),
                               ("engine_spec_emitted", "spec_emitted"),
                               ("engine_spec_ticks", "spec_ticks")):
                reg.gauge(name, f"speculative decode: {attr}",
                          ("replica",)).labels(**lbl).set_function(
                    lambda a=attr: getattr(self, a))
        self.scheduler.bind_metrics(reg, self.replica)
        if self.kv is not None:
            self.kv.bind_metrics(reg, self.replica)
        if self.tm.trace.enabled:
            self.tm.trace.set_process_name(self.replica,
                                           f"replica {self.replica}")

    def _set_state(self, req: Request, state: RequestState, **args) -> None:
        """One request-lifecycle edge: flip ``req.state`` and roll the
        request's trace span over to the new state (no-op sink when
        tracing is off)."""
        req.state = state
        self.tm.req_transition(self.replica, req.req_id, state.name, **args)

    def _span(self, name: str, **stats):
        """One tick phase on this replica's engine row
        (``Telemetry.span``)."""
        return self.tm.span(name, pid=self.replica, **stats)

    def _run(self, step, *args):
        """Call a compiled step, noting the shapes of its first call
        (``step_hlo_texts``)."""
        if step not in self._ran:
            self._ran[step] = jax.tree.map(_abstract, args)
        return step(*args)

    def step_hlo_texts(self) -> list:
        """The optimized HLO text of every compiled step this engine has
        run, compiled again from the shapes of its first call (the
        program comes back from the compile cache where one is set).  A
        profile's device ops name their HLO instructions; these texts
        give each one its ``op_name``, the program's named scopes."""
        return [step.lower(*args).compile().as_text()
                for step, args in self._ran.items()]

    def _tick_telemetry(self, emitted: int) -> None:
        """Per-tick accounting: counters always (two float adds), plus a
        Chrome counter-track sample of the engine's vitals when tracing
        is live."""
        self._m_ticks.inc()
        if emitted:
            self._m_tokens.inc(emitted)
        tr = self.tm.trace
        if not tr.enabled:
            return
        vals = {"live_slots": sum(r is not None for r in self.active),
                "queue_depth": len(self.scheduler.queue)}
        if self.kv is not None:
            vals["free_pages"] = self.kv.pool.available
        if self.draft_k:
            vals["spec_proposed"] = self.spec_proposed
            vals["spec_accepted"] = self.spec_accepted
        tr.counter(self.replica, "engine", vals)

    @property
    def queue(self) -> deque:
        """The scheduler's admission queue (read-mostly; use submit())."""
        return self.scheduler.queue

    @staticmethod
    def _make_slot_reset(model, max_len):
        """Zero one slot's cache state (batch axes per leaf from
        ``model.cache_batch_axes`` — layouts vary across plans)."""
        axes = model.cache_batch_axes(max_len)

        def reset(caches, slot):
            def zero(c, ax):
                keep = jnp.arange(c.shape[ax]) != slot
                shape = [1] * c.ndim
                shape[ax] = c.shape[ax]
                return c * keep.reshape(shape).astype(c.dtype)

            return jax.tree.map(zero, caches, axes)

        return jax.jit(reset, donate_argnums=(0,))

    def submit(self, req: Request) -> RequestHandle:
        if self.role == "decode" and not getattr(req, "_preempted", False):
            raise ValueError(
                "decode-role engines only accept handed-off (checkpointed) "
                "requests — route fresh requests to a prefill replica")
        if not 0 < len(req.prompt) < self.max_len:
            raise ValueError(
                f"prompt length {len(req.prompt)} outside [1, "
                f"{self.max_len - 1}] for max_len={self.max_len}")
        if self.kv is not None and not self.kv.fits_ever(
                len(req.prompt), req.max_new_tokens):
            raise ValueError(
                f"request needs more pages than the pool can ever supply "
                f"(prompt {len(req.prompt)} + max_new {req.max_new_tokens} "
                f"vs {self.kv.pool.capacity} pages of "
                f"{self.kv.page_size})")
        self._set_state(req, RequestState.QUEUED, tenant=req.tenant)
        req.t_submit = time.perf_counter()
        self._m_submitted.inc()
        self.scheduler.submit(req)
        return RequestHandle(req, self)

    # ------------------------------------------------------------ admission
    def _emit(self, req: Request, tok: int):
        if not req.output:
            req.t_first = time.perf_counter()
        req.output.append(tok)

    def _clear_slot(self, s: int):
        """Park slot ``s``: no occupant, pos -1, sampling state neutral
        (finish and preemption both come through here)."""
        self.active[s] = None
        self.pos[s] = -1
        self.tokens[s, 0] = 0
        self.samp_temp[s] = 0.0
        self.samp_topk[s] = 0
        self.samp_topp[s] = 1.0
        self.samp_keys[s] = 0

    def _finish(self, s: int, reason: str):
        req = self.active[s]
        req.done = True
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.t_finish = time.perf_counter()
        # close the request's span stream (FINISHED is terminal — an end,
        # not a new span) and count the finish by reason
        self.tm.req_end(self.replica, req.req_id, reason=reason,
                        tokens=len(req.output))
        self._m_finished.labels(replica=str(self.replica),
                                reason=reason).inc()
        self._clear_slot(s)
        if self.kv is not None:
            self.kv.free_slot(s)  # pages return to the pool immediately
        self.scheduler.on_finish(req)
        self._finished.append(req)

    # ----------------------------------------------------------- preempt
    def _ensure_ckpt_fns(self):
        """Dense checkpoint/restore steps, compiled on first preemption
        and shared module-wide (same memoization rationale as the step
        cache).  ``copy_out`` slices one slot's stripe (then device_get'd
        to a host buffer); ``copy_in`` rewrites it in place (donated)."""
        if self._copy_out is None:
            self._copy_out, self._copy_in = _ckpt_fns(self.model,
                                                      self.max_len)

    def _execute_preemption(self, pre):
        """Executor half of preemption: capture the slot's device state
        into the request's checkpoint and park the slot.  The scheduler
        already did the host half (page detach, DRF credit, requeue);
        this MUST run before any admission reuses the slot."""
        s, req = pre.slot, pre.req
        if self.kv is not None:
            kv_snap = None  # zero-copy: the detached page chain IS the KV
        else:
            self._ensure_ckpt_fns()
            kv_snap = jax.device_get(self._copy_out(self.caches,
                                                    jnp.int32(s)))
        req._ckpt = Checkpoint(pos=int(self.pos[s]),
                               last_token=int(self.tokens[s, 0]),
                               pages=getattr(req, "_ckpt_pages", None),
                               kv=kv_snap)
        self._set_state(req, RequestState.PREEMPTED, pos=req._ckpt.pos,
                        count=req.preempt_count + 1)
        req.preempt_count += 1
        self._clear_slot(s)

    def _execute_resume(self, s: int, req: Request):
        """Restore a checkpointed request into slot ``s`` at
        ``pos = checkpoint`` — no prefill re-run.  Paged: the page table
        row was remapped by the scheduler (attach_slot).  Dense: the
        host-side stripe snapshot is written back in place (full stripe,
        so SSM/recurrent state restores exactly and the previous
        occupant leaves no residue)."""
        ck = req._ckpt
        if self.kv is None:
            self._ensure_ckpt_fns()
            self.caches = self._copy_in(self.caches,
                                        jax.device_put(ck.kv),
                                        jnp.int32(s))
        self.pos[s] = ck.pos
        self.tokens[s, 0] = ck.last_token
        req._feed = deque()  # type: ignore
        req._ckpt = None
        req._ckpt_pages = None
        req._preempted = False
        req._handoff_kv = 0  # adopted chain now charged via _drf_charged
        self._set_state(req, RequestState.DECODE, resume=True,
                        pos=int(self.pos[s]))

    def release(self, req: Request) -> Checkpoint:
        """Voluntarily checkpoint a *running* request so its KV can move
        to another engine (the disagg handoff / drain-migration path).

        Same device capture as ``_execute_preemption`` — paged detaches
        the slot's page chain zero-copy, dense snapshots the cache stripe
        to host — but the request is *leaving this engine*: its trace
        span stream on this pid is ended (not transitioned), the
        scheduler is credited the full DRF charge (slot AND chain — the
        pages depart with the request), and the caller re-submits the
        checkpointed request to the destination engine, which resumes it
        at ``pos = checkpoint`` with no prefill re-run."""
        s = next(i for i, r in enumerate(self.active) if r is req)
        if self.kv is not None:
            req._ckpt_pages = self.kv.detach_slot(s)
            kv_snap = None
        else:
            self._ensure_ckpt_fns()
            kv_snap = jax.device_get(self._copy_out(self.caches,
                                                    jnp.int32(s)))
        req._ckpt = Checkpoint(pos=int(self.pos[s]),
                               last_token=int(self.tokens[s, 0]),
                               pages=getattr(req, "_ckpt_pages", None),
                               kv=kv_snap)
        req.state = RequestState.PREEMPTED
        self.tm.req_end(self.replica, req.req_id, reason="handoff",
                        pos=req._ckpt.pos)
        req.preempt_count += 1
        req._preempted = True
        self._clear_slot(s)
        self.scheduler.on_finish(req)  # full DRF credit: the chain leaves
        return req._ckpt

    def _execute_admission(self, adm):
        """Executor half of admission: apply one scheduler decision —
        device prefill / checkpoint restore / slot reset / token-feed
        setup."""
        s, req = adm.slot, adm.req
        self.active[s] = req
        sp = req.sampling
        self.samp_temp[s] = sp.temperature
        self.samp_topk[s] = sp.top_k
        self.samp_topp[s] = sp.top_p
        self.samp_keys[s] = sp.key_data(req.req_id)
        if adm.resume:
            self._execute_resume(s, req)
            return
        self._set_state(req, RequestState.PREFILL, slot=s)
        if self.kv is not None:
            # CoW pages (adm.kv.cow) need no device copy here: they span
            # [start, matched), so the first re-run prefill chunk rewrites
            # every one of them in full (chunks write whole pages) before
            # anything reads them
            self._prefill_slot(s, req, start=adm.kv.start)
            # prefill already produced the first token; the request may
            # complete before a single decode tick runs, in which case
            # the freed slot admits again immediately
            if not self._maybe_stop(s):
                self._set_state(req, RequestState.DECODE)
            return
        if self._needs_reset:
            self.caches = self._reset(self.caches, jnp.int32(s))
        if self.chunked:
            self._prefill_slot(s, req)
            if not self._maybe_stop(s):
                self._set_state(req, RequestState.DECODE)
        else:
            req._feed = deque(req.prompt.tolist())  # type: ignore
            self.tokens[s, 0] = req._feed.popleft()
            self.pos[s] = 0

    def _admit_continuous(self):
        """Decide/execute rounds until the scheduler has nothing to admit
        (a prefilled request can finish instantly and free its slot for
        the same tick, hence the loop).  Preemptions execute first: a
        slot must be checkpointed before its next occupant prefills."""
        while True:
            with self._span("engine.admit") as sp:
                plan = self.scheduler.decide(self.active)
                for pre in plan.preemptions:
                    self._execute_preemption(pre)
                sp.set(admitted=len(plan.admissions))
            if not plan:
                return
            for adm in plan.admissions:
                self._execute_admission(adm)

    def _prefill_slot(self, s: int, req: Request, start: int = 0):
        """Run the slot's prompt tokens [start, prompt_len) through the
        stack in (1, C) chunks, writing the KV cache in place; the token
        drawn from the last real token's logits (greedy or sampled, per
        the request) seeds decode at pos = prompt_len.

        ``start`` (paged mode, a multiple of C and <= prompt_len - 1) is
        where the prefix cache left off; the paged step additionally
        takes the page-table array, and the full prompt pages are
        published for future prefix hits afterwards."""
        c = self.prefill_chunk
        prompt = np.asarray(req.prompt, np.int32)
        p = len(prompt)
        n_chunks = max(1, -(-(p - start) // c))
        with self._span("engine.prefill", slot=s, tokens=p - start,
                        chunks=n_chunks):
            padded = np.zeros(n_chunks * c, np.int32)
            padded[:p - start] = prompt[start:]
            req._feed = deque()  # type: ignore
            sp = req.sampling
            sampling = sp.temperature > 0
            extra = (() if self.kv is None
                     else (jnp.asarray(self.kv.page_table),))
            samp = (() if not sampling else
                    (jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                     jnp.float32(sp.top_p),
                     jnp.asarray(sp.key_data(req.req_id))))
            prefill = self._prefill_sampled if sampling else self._prefill
            nxt = None
            for ci in range(n_chunks):
                last = (p - start - 1) - ci * c  # final-chunk row of the
                last_row = last if 0 <= last < c else 0  # last real token
                chunk = jnp.asarray(padded[None, ci * c:(ci + 1) * c])
                if self._pf_buf is not None:
                    # buffered paged prefill: thread the dense slot view
                    # through the chunk loop; a prefix-cache hit rebuilds it
                    # from the page table on the first chunk only
                    fn = prefill
                    if ci == 0 and start > 0:
                        fn = (self._prefill_gather_sampled if sampling
                              else self._prefill_gather)
                    if sampling:
                        nxt, self.caches, self._pf_buf = self._run(
                            fn, self.params, self.caches, chunk,
                            jnp.int32(s), jnp.int32(start + ci * c), *extra,
                            self._pf_buf, jnp.int32(last_row), *samp)
                    else:
                        nxt, self.caches, self._pf_buf = self._run(
                            fn, self.params, self.caches, chunk,
                            jnp.int32(s), jnp.int32(start + ci * c), *extra,
                            self._pf_buf)
                elif sampling:
                    nxt, self.caches = self._run(
                        prefill, self.params, self.caches, chunk,
                        jnp.int32(s), jnp.int32(start + ci * c), *extra,
                        jnp.int32(last_row), *samp)
                else:
                    nxt, self.caches = self._run(
                        prefill, self.params, self.caches, chunk,
                        jnp.int32(s), jnp.int32(start + ci * c), *extra)
            with self._span("engine.sync"):
                tok = (int(np.asarray(nxt)) if sampling
                       else int(np.asarray(nxt)[(p - start - 1)
                                                - (n_chunks - 1) * c]))
            self.pos[s] = p
            self.tokens[s, 0] = tok
            self._emit(req, tok)
            self._admit_emitted += 1
            if self.kv is not None:
                self.kv.register_prefix(s, prompt)

    def _maybe_stop(self, s: int) -> bool:
        req = self.active[s]
        reason = matches_stop(req.output, req.sampling, req.eos_id)
        if reason is None and (len(req.output) >= req.max_new_tokens
                               or self.pos[s] >= self.max_len - 1):
            reason = "length"
        if reason is not None:
            self._finish(s, reason)
            return True
        return False

    # ----------------------------------------------------------- wave mode
    def _admit_wave(self):
        """Wave batching: admit a fresh wave only when every slot is free —
        all slots then decode in lockstep at one scalar position (static
        shapes, exact cache indexing).  Prompts are fed token-by-token;
        the admission *order* still follows the configured policy."""
        if any(r is not None for r in self.active) or not self.queue:
            return
        self.caches = jax.tree.map(lambda c: jnp.zeros_like(c), self.caches)
        self.pos[:] = 0
        self.tokens[:] = 0
        with self._span("engine.admit") as sp:
            plan = self.scheduler.decide(self.active)
            sp.set(admitted=len(plan.admissions))
        for adm in plan.admissions:
            s, req = adm.slot, adm.req
            self.active[s] = req
            sp = req.sampling
            self.samp_temp[s] = sp.temperature
            self.samp_topk[s] = sp.top_k
            self.samp_topp[s] = sp.top_p
            self.samp_keys[s] = sp.key_data(req.req_id)
            self._set_state(req, RequestState.PREFILL, slot=s)
            req._feed = deque(req.prompt.tolist())  # type: ignore
            self.tokens[s, 0] = req._feed.popleft()

    # ------------------------------------------------------------ stepping
    def step(self) -> int:
        """One engine tick = one decode step for every live slot."""
        with self._span("engine.step"):
            if self.mode == "wave":
                emitted = self._step_wave()
            else:
                emitted = self._step_continuous()
            self._tick_telemetry(emitted)
        return emitted

    def _put_b(self, x):
        """Slot-dim host array -> device.  Sharded engines place it over
        the mesh's data axes (the layout the compiled step expects for
        the slot dim); unsharded engines just convert.  The page table
        deliberately does NOT come through here — every host gathers
        pages, so it stays replicated."""
        a = jnp.asarray(x)
        if self._batch_sharding is not None:
            a = jax.device_put(a, self._batch_sharding)
        return a

    def _step_for_splits(self, splits: int, sampled: bool):
        """Decode step with a given split-K fan-out (fan-outs from the
        small set the heuristic emits: 1, 2, 4, 8), for whichever cache
        layout this engine runs.  Resolution goes through the
        module-level step cache, so every engine over the same model
        shares one compiled callable per fan-out."""
        if splits <= 1:
            return self._step_sampled if sampled else self._step
        if self.kv is not None:
            return compiled_step(self.model, "paged_serve", sampled=sampled,
                                 page_size=self.config.page_size,
                                 decode_splits=splits)
        return compiled_step(self.model, "serve", sampled=sampled,
                             decode_splits=splits)

    def _step_continuous(self) -> int:
        self._admit_emitted = 0
        self._admit_continuous()
        emitted = self._admit_emitted  # first tokens from chunked prefill
        if self.role == "prefill":
            # prefill workers never decode: chunked prefill completed
            # atomically inside admission (emitting the first token), and
            # the router extracts the finished slot as a handoff this same
            # tick — so the decode phase below would only burn a step
            return emitted
        live = sum(r is not None for r in self.active)
        if not live:
            return emitted
        with self._span("engine.decode", live=live):
            if self.draft_k:
                return self._decode_tick_spec(emitted, live)
            return self._decode_tick_plain(emitted, live)

    def _decode_tick_plain(self, emitted: int, live: int) -> int:
        """One single-token decode step for every live slot (the
        baseline tick; also what a speculative engine dispatches on
        ticks where no slot proposed a draft — the T-wide verify step
        would pay ~T x attention/unembed work to emit the same one
        token per slot)."""
        with self._span("engine.dispatch") as sp:
            pos = self._put_b(self.pos)
            # pay the sampling math only when a live slot actually samples
            # (finished slots reset their temp to 0)
            sampling = bool(self.samp_temp.max() > 0)
            samp = (() if not sampling else
                    (self._put_b(self.samp_temp), self._put_b(self.samp_topk),
                     self._put_b(self.samp_topp), self._put_b(self.samp_keys)))
            step = self._step_sampled if sampling else self._step
            splits = max(self.model.knobs.decode_splits, 1)
            if self._autotune:
                splits = pick_decode_splits(
                    int(self.pos.max()), live, max_len=self.max_len,
                    page_size=(0 if self.kv is None
                               else self.config.page_size))
                step = self._step_for_splits(splits, sampling)
            sp.set(splits=splits)
            extra = (() if self.kv is None
                     else (jnp.asarray(self.kv.page_table),))
            nxt_dev, self.caches = self._run(
                step, self.params, self.caches, self._put_b(self.tokens),
                pos, *extra, *samp)
        with self._span("engine.sync"):
            nxt = np.asarray(nxt_dev)
        with self._span("engine.emit") as sp:
            before = emitted
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                self.pos[s] += 1
                feed = getattr(req, "_feed")
                if feed:  # still consuming the prompt (token-feed path)
                    self.tokens[s, 0] = feed.popleft()
                    continue
                if req.state is RequestState.PREFILL:  # token-feed done
                    self._set_state(req, RequestState.DECODE)
                tok = int(nxt[s, 0])
                self._emit(req, tok)
                emitted += 1
                self.tokens[s, 0] = tok
                self._maybe_stop(s)
            sp.set(emitted=emitted - before)
        return emitted

    # ------------------------------------------------------- speculative
    def _draft_cap(self, s: int, req: Request) -> int:
        """Deepest draft slot ``s`` may carry this tick.

        Bounded by (a) the configured ``draft_k``; (b) the request's
        remaining token budget minus one (the verify tick always emits
        at least the correction token, so cap + 1 never overshoots
        ``max_new_tokens``); (c) the ``max_len`` window (after accepting
        everything, ``pos`` stays <= max_len - 1 — the same boundary the
        baseline length-stop enforces); and (d), paged only, the slot's
        mapped page span — admission reserved pages for the full token
        budget, so (b) already implies (d), but the explicit bound means
        an off-by-one can reject a draft, never write an unheld page.
        Draft padding beyond the cap still flows through the compiled
        step; its writes land clamped / in the null page and its rows
        are never read (rollback = position truncation).
        """
        cap = min(self.draft_k,
                  req.max_new_tokens - len(req.output) - 1,
                  self.max_len - 2 - int(self.pos[s]))
        if self.kv is not None:
            cap = min(cap, self.kv.slot_span(s) - 1 - int(self.pos[s]))
        return max(cap, 0)

    def _decode_tick_spec(self, emitted: int, live: int) -> int:
        """One speculative decode tick: draft per slot (host), verify all
        drafts in one compiled multi-token step (device), accept the
        longest confirmed prefix plus the free correction token (host).

        The emission loop replays the baseline tick ordering per token —
        advance ``pos``, emit, stop-check — so eos/stop/length fire at
        exactly the token they would have in sequential decode and any
        accepted-but-past-stop tokens are discarded, keeping the output
        stream bitwise-identical to the non-speculative engine.

        Ticks where no slot proposes a draft (incompressible output, or
        every slot at cap 0 near its budget) fall back to the plain
        single-token step — already compiled, and bitwise the same as a
        draft-less verify — instead of paying the T-wide verify work to
        emit one token per slot; ``spec_ticks`` therefore counts only
        the multi-token verify dispatches.
        """
        t_width = self.draft_k + 1
        feed = np.zeros((self.slots, t_width), np.int32)
        feed[:, 0] = self.tokens[:, 0]
        draft_len = np.zeros(self.slots, np.int32)
        for s, req in enumerate(self.active):
            if req is None or getattr(req, "_feed", None):
                continue  # parked / token-feeding slots carry no draft
            cap = self._draft_cap(s, req)
            if cap <= 0:
                continue
            # hand the drafter only its lookback window: per-tick host
            # work stays O(lookback), not O(tokens generated so far)
            lb = getattr(self.drafter, "lookback", 0)
            out = req.output
            if lb and len(out) >= lb:
                ctx = np.asarray(out[-lb:], np.int32)
            else:
                head = (req.prompt[max(len(req.prompt) + len(out) - lb, 0):]
                        if lb else req.prompt)
                ctx = np.concatenate([np.asarray(head, np.int32),
                                      np.asarray(out, np.int32)])
            d = self.drafter.propose(ctx, cap)
            if len(d):
                feed[s, 1:1 + len(d)] = d
                draft_len[s] = len(d)
        if not draft_len.any():
            return self._decode_tick_plain(emitted, live)
        with self._span("engine.dispatch", splits=1):
            pos = self._put_b(self.pos)
            sampling = bool(self.samp_temp.max() > 0)
            samp = (() if not sampling else
                    (self._put_b(self.samp_temp), self._put_b(self.samp_topk),
                     self._put_b(self.samp_topp), self._put_b(self.samp_keys)))
            step = self._spec_step_sampled if sampling else self._spec_step
            extra = (() if self.kv is None
                     else (jnp.asarray(self.kv.page_table),))
            target_dev, self.caches = self._run(
                step, self.params, self.caches, self._put_b(feed), pos,
                *extra, *samp)
        with self._span("engine.sync"):
            target = np.asarray(target_dev)  # (B, T) per-row verified
        self.spec_ticks += 1
        with self._span("engine.emit") as sp:
            before = emitted
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                fq = getattr(req, "_feed")
                if fq:  # still consuming the prompt (token-feed path)
                    self.pos[s] += 1
                    self.tokens[s, 0] = fq.popleft()
                    continue
                if req.state is RequestState.PREFILL:  # token-feed done
                    self._set_state(req, RequestState.DECODE)
                k_s = int(draft_len[s])
                m = (speculative_accept(feed[s, 1:1 + k_s], target[s, :k_s])
                     if k_s else 0)
                self.spec_proposed += k_s
                self.spec_accepted += m
                for t in range(m + 1):
                    self.pos[s] += 1
                    tok = int(target[s, t])
                    self._emit(req, tok)
                    emitted += 1
                    self.spec_emitted += 1
                    self.tokens[s, 0] = tok
                    if self._maybe_stop(s):
                        break  # accepted-but-past-stop tokens discarded
            sp.set(emitted=emitted - before)
        return emitted

    def spec_stats(self) -> dict:
        """Speculative-decode telemetry: draft acceptance rate and the
        average tokens emitted per verify tick (1.0 = plain decode).
        Values are read back through the metrics registry (the
        function-backed ``engine_spec_*`` gauges), keeping this legacy
        dict a view over the one telemetry source of truth."""
        if not self.draft_k:
            return {"draft_k": 0}
        v = self.tm.registry.value
        lbl = {"replica": str(self.replica)}
        proposed = int(v("engine_spec_proposed", **lbl))
        accepted = int(v("engine_spec_accepted", **lbl))
        emitted = int(v("engine_spec_emitted", **lbl))
        ticks = int(v("engine_spec_ticks", **lbl))
        return {
            "draft_k": self.draft_k,
            "drafter": self.config.drafter,
            "proposed": proposed,
            "accepted": accepted,
            "acceptance_rate": accepted / max(proposed, 1),
            "spec_ticks": ticks,
            "tokens_per_tick": emitted / max(ticks, 1),
        }

    def _step_wave(self) -> int:
        self._admit_wave()
        live = sum(r is not None for r in self.active)
        if not live:
            return 0
        with self._span("engine.decode", live=live):
            return self._decode_tick_wave()

    def _decode_tick_wave(self) -> int:
        with self._span("engine.dispatch", splits=1):
            pos = int(self.pos.max())  # lockstep position (wave batching)
            logits, self.caches = self._run(
                self._decode_one, self.params, self.caches,
                jnp.asarray(self.tokens), jnp.int32(pos))
            if bool(self.samp_temp.max() > 0):
                # sampled wave mode: host-side draw from the wave logits.
                # Slots advance in lockstep from position 0, so each
                # slot's absolute token position IS the wave position —
                # the same (key, position) fold as the continuous sampled
                # step, hence the same trajectory for a given seed; greedy
                # (temp 0) rows stay the bitwise argmax inside
                # sample_tokens.
                sampler = compiled_fn(("wave_sample",),
                                      lambda: sample_tokens)
                nxt_dev = self._run(
                    sampler, logits, jnp.asarray(self.pos),
                    jnp.asarray(self.samp_temp), jnp.asarray(self.samp_topk),
                    jnp.asarray(self.samp_topp), jnp.asarray(self.samp_keys))
            else:
                nxt_dev = jnp.argmax(logits, axis=-1)
        with self._span("engine.sync"):
            nxt = np.asarray(nxt_dev, dtype=np.int32)
        emitted = 0
        with self._span("engine.emit") as sp:
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                self.pos[s] += 1
                feed = getattr(req, "_feed")
                if feed:  # still consuming the prompt
                    self.tokens[s, 0] = feed.popleft()
                    continue
                if req.state is RequestState.PREFILL:
                    self._set_state(req, RequestState.DECODE)
                tok = int(nxt[s])
                self._emit(req, tok)
                emitted += 1
                self.tokens[s, 0] = tok
                self._maybe_stop(s)
            sp.set(emitted=emitted)
        return emitted

    # --------------------------------------------------------- cluster hooks
    def free_slots(self) -> int:
        """Slots a router may target right now: parked slots minus the
        queue the engine already owes admissions to (queued requests
        claim freed slots before any new placement lands)."""
        return max(0, sum(r is None for r in self.active)
                   - len(self.scheduler.queue))

    def offer(self) -> dict:
        """Resource offer for a cluster router (the Mesos ``advertise``
        analogue, per engine replica): free decode slots, free KV pages
        (``None`` for the dense cache — slots are the only currency),
        and the backlog depth a placement would queue behind.

        Sharded paged engines (``mesh_shape`` with > 1 data host)
        additionally advertise ``free_pages_by_host`` — the per-host
        sub-pool balance.  The aggregate ``free_pages`` stays in the
        offer unchanged, so unsharded routers compose as before; a
        host-aware router can see that 40 free pages split 40/0 admit
        less than 20/20."""
        out = {
            "free_slots": self.free_slots(),
            "free_pages": (None if self.kv is None
                           else self.kv.pool.available),
            "page_size": None if self.kv is None else self.kv.page_size,
            "queue_depth": len(self.scheduler.queue),
        }
        if self.kv is not None and self.kv.num_hosts > 1:
            out["free_pages_by_host"] = self.kv.free_by_host()
        return out

    def live_requests(self) -> list:
        """Every unfinished request this engine holds — running slots
        plus its admission queue (which includes PREEMPTED requests
        waiting to resume).  A router recovering a lost replica replays
        exactly this set."""
        return ([r for r in self.active if r is not None]
                + [r for r in self.queue])

    def can_accept(self, req: Request) -> bool:
        """Could a router place ``req`` here without queuing it behind
        backpressure?  Host-side sizing only (free slot + page fit);
        optimistic across multiple placements in one tick — the engine's
        own scheduler absorbs any overshoot as ordinary backpressure."""
        if self.free_slots() < 1:
            return False
        if self.kv is not None:
            return (self.kv.fits_ever(len(req.prompt), req.max_new_tokens)
                    and self.kv.fits_now(req.prompt, req.max_new_tokens))
        return 0 < len(req.prompt) < self.max_len

    # ------------------------------------------------------------- metrics
    def kv_reserved_bytes(self) -> int:
        """HBM bytes held by the KV cache (dense stripes or page pools)."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(self.caches))

    def kv_stats(self) -> dict:
        stats = {"cache": self.cache,
                 "kv_reserved_bytes": self.kv_reserved_bytes()}
        if self.kv is not None:
            stats.update(self.kv.stats())
        return stats

    def run(self, max_ticks: int = 10_000,
            on_stall: Optional[str] = None) -> list[Request]:
        """Drive the engine until every request drains.

        If ``max_ticks`` is exhausted with requests still queued or
        active, the stall is *reported*, never silently truncated:
        ``on_stall="raise"`` (the default, from ``ServeConfig``) raises
        ``ServeStalled``; ``"warn"`` emits a ``RuntimeWarning`` carrying
        the undrained counts and returns the partial results."""
        stall_mode = on_stall or self.config.on_stall
        if stall_mode not in ("raise", "warn"):
            raise ValueError(f"on_stall must be 'raise' or 'warn': "
                             f"{stall_mode!r}")
        ticks = 0
        while self.queue or any(r is not None for r in self.active):
            if ticks >= max_ticks:
                queued = len(self.queue)
                live = sum(r is not None for r in self.active)
                msg = (f"ServeEngine.run() exhausted {max_ticks} ticks "
                       f"with {queued + live} requests undrained "
                       f"({queued} queued, {live} active)")
                if stall_mode == "raise":
                    raise ServeStalled(msg)
                warnings.warn(msg, RuntimeWarning, stacklevel=2)
                break
            self.step()
            ticks += 1
        finished, self._finished = self._finished, []
        return finished
