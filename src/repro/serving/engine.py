"""Batched serving engine core: continuous batching with chunked prefill,
arrival-driven admission, and streaming.

Tick model
----------
The engine owns one batched decode state of ``capacity`` slots.  Every call
to ``step()`` advances the batch by ONE jitted pass, which is either:

  * a **decode tick** (``decode_step``) — every live slot advances by one
    token at the decode-specialized matmul shapes (M = capacity), or
  * a **prefill pass** (``models.prefill``) — taken whenever any live slot
    still has unconsumed prompt.  Each prefilling slot contributes its next
    prompt chunk (up to the largest configured bucket) and each DECODING
    slot rides along with its single next token, so admission never stalls
    generation: a prefilling slot and a decoding slot coexist in one batch
    via per-slot position/length tracking (``n_tokens``).

Chunked prefill turns prompt admission from O(prompt_len) sequential
full-model ticks into O(prompt_len / chunk) passes whose matmuls run at
M = capacity * chunk — the MXU-friendly shapes the packed ABFP kernel is
2–5x faster per byte at (see BENCH_serving.json for the measured
time-to-first-token win; ``chunked=False`` restores the legacy
prefill-in-decode behavior for comparison).

Open-loop serving
-----------------
``submit()`` enqueues a request with an ``arrival_time`` (defaulting to the
engine clock "now"); ``poll()`` admits every arrived request the active
scheduling policy picks (``repro.serving.scheduler``: fcfs / sjf /
priority with per-tenant fairness), runs one ``step()``, and returns the
requests that finished during that pass.  The clock is SIMULATED by
default — each jitted pass advances ``tick_time`` — so arrival-driven tests
are fully deterministic; pass ``clock=time.perf_counter`` for wall-clock
serving (the open-loop benchmark does).  When the batch is idle and every
queued request is still in the future, ``poll()`` jumps the simulated
clock to the next arrival instead of burning empty ticks.

Per-request TTFT/TPOT/E2E, tick utilization, and queue depth are recorded
in ``engine.metrics`` (``repro.serving.metrics.ServingMetrics``); each
generated token is also streamed to ``Request.on_token`` the moment it is
sampled.  ``run()`` is a thin closed-loop compatibility wrapper (submit
everything at "now", drain FCFS) and is bit-identical to the historical
static-batch runner for greedy same-seed workloads.

Bucketing policy
----------------
Chunk lengths are drawn from the small static set ``prefill_chunks`` (the
pass is padded up to the smallest bucket that fits, per-slot padding is
masked via ``n_tokens``), so jit compiles at most ``len(prefill_chunks)``
prefill shapes — occupancy, chunk fill, and slot membership are all data,
not shape.

Numerics
--------
Pluggable via ``QuantConfig``: ``mode="abfp_ref"`` serves the model exactly
as the AMS device would compute it (the paper's deployment target),
``mode="float"`` is the FLOAT32 reference.  ``mode="abfp_packed"`` is the
production path: all dense weights are quantized ONCE at engine init
(int8 tile codes + bf16 scales, ``models.packing``) and every pass runs the
packed Pallas kernel — no per-token weight re-quantization, half the weight
HBM traffic.  Float-mode chunked prefill is bit-identical to the token-by-
token path; ABFP modes are statistically equivalent only (the kernel's
noise PRNG salts by grid position, and chunked grids differ from
decode-shaped grids — same noise distribution, different draws).

Sampling: ``temperature == 0`` decodes greedily (argmax); ``temperature >
0`` samples from the temperature-scaled softmax using a stream seeded by
(engine seed, request uid, token index), so draws are reproducible for a
given engine seed regardless of how requests interleave across ticks.

Sharded serving
---------------
``mesh=`` (a ``jax.sharding.Mesh`` with a 'model' axis and optional
'data'/'pod' axes) makes the whole stack mesh-aware: dense weights —
including pre-packed int8 codes + bf16 scales, which shard TOGETHER —
are placed column-parallel over 'model'
(``distributed.sharding.serving_param_spec_tree``), slot state / KV
caches shard over the data axes, and every matmul dispatches through
``kernels.ops.dense_tp`` (shard_map + all-gather, noise salts
globalized per column shard).  Column-parallel splitting never crosses
an ABFP K-tile and never reorders an f32 contraction, so greedy decode
is BIT-IDENTICAL to the single-device engine at any mesh shape, noise
included — the open-loop submit/poll/drain API is unchanged
(tests/test_sharded_serving.py).

Paged KV + overload robustness
------------------------------
``paged=True`` swaps the per-slot ``max_len`` KV strips for a shared
``serving.pages.PagePool``: pages are fixed-size (aligned to the ABFP
tile width so quantized KV scales never straddle a page) and each slot
addresses them through a static-shape page table gathered INSIDE the
jitted pass — allocation churn never recompiles, and float-mode decode
is bit-identical to the unpaged engine.  The host-side table
(``self._table``) is the source of truth and is refreshed into device
state before every pass; unallocated entries hold a sentinel
(``pool.num_pages``) whose writes drop and whose reads clamp, so a dead
slot can never corrupt a live page.  Prefix pages of identical prompts
are shared copy-on-write across requests (chained-hash keys over full
pages; a write to a shared page splits it first).

Under page saturation the engine PREEMPTS the lowest-priority / youngest
slot: its pages return to the pool and the request requeues carrying a
replay of ``prompt + generated``; on re-admission it re-prefills the
replay and continues bit-identically (greedy decode is deterministic, so
recompute IS restore).  Conservation extends to ``preempted == resumed +
timed_out`` per request.  Backpressure sheds newly ARRIVED requests past
``queue_watermark`` (marked ``shed`` with a ``retry_after`` hint,
surfaced through ``poll()``); ``tenant_quota`` caps one tenant's pages at
projected footprint; pool pressure above ``page_watermarks[0]`` flips
hysteretic DEGRADED mode (admissions get ``degraded_max_new``, prefill
drops to the smallest bucket) until pressure falls below
``page_watermarks[1]``.

Tracing
-------
The engine marks its host work as spans in the JAX profiler's trace
(``serving.metrics.span``), so a trace taken with ``jax.profiler.trace``
puts each host step on the clock of the device's executables:
``serving.admit`` (args ``admitted``, ``queued``), ``serving.step`` around
all of ``step()``, inside it ``serving.prefill_pass`` (``pass_id``,
``bucket``, ``rows`` = capacity x bucket, ``tokens`` fed, ``live``) or
``serving.decode_tick`` (``pass_id``, ``live``, ``fused_layers``: the
layers whose tick runs the fused attention kernel), and inside those
``serving.launch`` (``pass_id``) around the executable's dispatch.  The
stream adds ``serving.stream_wait``, ``serving.fetch`` (``pass_id``) and,
on delivery, ``serving.deliver`` (``pass_id``); garbage collections show as
``python.gc``.  ``pass_id`` is ``ticks`` at dispatch: it ties one pass's
spans together across threads.  With no profiler running a span records
nothing, and costs a few microseconds of host time.
"""

from __future__ import annotations

import dataclasses
import gc
import time
import weakref
from collections import deque
from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.abfp import QuantConfig
from repro.distributed.fault import StragglerMonitor, plan_recovery_mesh
from repro.serving import faults as faultlib
from repro.serving.faults import FaultConfig, FaultPlan
from repro.serving.metrics import GcSpans, ServingMetrics, span
from repro.serving.pages import (
    PagePool,
    page_table_array,
    pages_needed,
    plan_chunk,
    prefix_key,
)
from repro.serving.runners import ModelRunner, runner_for
from repro.serving.scheduler import Scheduler, get_scheduler
from repro.serving.stream import (
    DeviceStream,
    OverlappedStream,
    Ticket,
    TokenRec,
)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    arrival_time: Optional[float] = None    # engine clock; None = at submit
    priority: int = 0                       # larger = served first
    tenant: str = "default"                 # fairness domain for `priority`
    model: Optional[str] = None         # fleet routing key (ServingEngine
                                        # with models=...); None on a
                                        # single-model engine
    features: Optional[Any] = None      # frontend side input (enc-dec:
                                        # (enc_len, d_model) frame embeds)
    deadline: Optional[float] = None    # absolute engine-clock time; past it
                                        # the request is cancelled (queued or
                                        # in-flight) and marked timed_out
    on_token: Optional[Callable[["Request", int], None]] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    prompt_pos: int = 0                 # prompt tokens consumed so far
    dispatched: int = 0                 # tokens whose pass has been launched
                                        # on device; > len(generated) while
                                        # overlapped deliveries are in flight
    done: bool = False
    timed_out: bool = False             # cancelled by deadline expiry
    replay: Optional[List[int]] = None  # recompute stream after preemption:
                                        # prompt + tokens already streamed,
                                        # re-prefilled verbatim on resume
    preempted: int = 0                  # times evicted under page pressure
    shed: bool = False                  # rejected by admission backpressure
    retry_after: Optional[float] = None  # backoff hint stamped when shed


class ServingEngine:
    def __new__(cls, params=None, mcfg=None, *args, models=None, **kwargs):
        # ``models={name: (params, mcfg[, runner])}`` turns the engine into
        # a multi-model FLEET: one lane (single-model sub-engine) per
        # entry, multiplexed on a shared clock (serving.fleet).
        if models is not None and cls is ServingEngine:
            from repro.serving.fleet import FleetEngine
            return super().__new__(FleetEngine)
        return super().__new__(cls)

    def __init__(self, params, mcfg: ModelConfig, *, capacity: int = 8,
                 max_len: int = 512,
                 runner: Optional[ModelRunner] = None,
                 quant: QuantConfig = QuantConfig(mode="float"),
                 seed: int = 0,
                 prefill_chunks: Sequence[int] = (16, 64, 128),
                 chunked: bool = True,
                 policy: Union[str, Scheduler] = "fcfs",
                 tick_time: float = 1.0,
                 clock: Optional[Callable[[], float]] = None,
                 mesh=None,
                 faults: Optional[Union[FaultConfig, FaultPlan]] = None,
                 recovery: bool = True,
                 detect_every: int = 4,
                 paged: bool = False,
                 page_size: Optional[int] = None,
                 pool_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 preemption: Optional[bool] = None,
                 queue_watermark: Optional[int] = None,
                 page_watermarks: Tuple[float, float] = (0.85, 0.5),
                 degraded_max_new: Optional[int] = None,
                 tenant_quota: Optional[int] = None,
                 overlap: bool = False,
                 inflight: int = 4,
                 stream: Optional[DeviceStream] = None):
        self.mesh = mesh
        self.runner = runner if runner is not None else runner_for(mcfg)
        if quant.mode in ("abfp_packed", "abfp_fused"):
            # Quantize-once: pack every dense weight at admission time so
            # the per-tick decode path only streams int8 codes + bf16
            # scales (the paper's program-the-array-once deployment).  With
            # a mesh, codes + scales are column-sharded together over the
            # 'model' axis as part of the same one-time step.  abfp_fused
            # additionally bakes per-tile ADC gains into each PackedWeight
            # and routes decode ticks through the fused QKV + attention
            # kernels (kernels.abfp_decode_fused).
            from repro.models.packing import pack_model_params
            params = pack_model_params(params, quant, mcfg, mesh=mesh)
        elif mesh is not None:
            from repro.distributed.sharding import shard_serving_params
            params = shard_serving_params(params, mesh, quant)
        self.params = params
        self.mcfg = mcfg
        self.capacity = capacity
        self.max_len = max_len
        self.quant = quant
        self.seed = seed
        self.key = jax.random.PRNGKey(seed)
        self.prefill_chunks = tuple(sorted({int(c) for c in prefill_chunks}))
        self.chunked = chunked and bool(self.prefill_chunks)

        # -- paged KV pool (serving.pages) ---------------------------------
        # With ``paged=False`` the engine allocates the legacy per-slot
        # max_len caches and NOTHING below exists on the hot path.
        self.paged = bool(paged)
        self.pool: Optional[PagePool] = None
        self.page_size = 0
        self.max_pages = 0
        if self.paged:
            if not self.runner.paged_ok:
                raise ValueError(
                    "paged serving needs append-only full-attention KV "
                    f"caches; got attention_type={mcfg.attention_type!r} "
                    f"({type(self.runner).__name__})")
            # ABFP tile width is the natural page quantum: the paper's
            # fixed-size analog tiles align with the int8 cache blocks.
            self.page_size = int(page_size) if page_size else (
                quant.tile_width if quant.mode != "float"
                else min(16, max_len))
            self.max_pages = pages_needed(max_len, self.page_size)
            self.pool = PagePool(
                int(pool_pages) if pool_pages else capacity * self.max_pages,
                self.page_size)
            self._table = page_table_array(capacity, self.max_pages,
                                           self.pool.sentinel)
            self._slot_pages: List[List[int]] = [[] for _ in range(capacity)]
            self._slot_len = [0] * capacity     # tokens appended per slot
            self._slot_keys: List[List[int]] = [[] for _ in range(capacity)]
            self._slot_cap: List[Optional[int]] = [None] * capacity
        self.prefix_enabled = (self.paged and bool(prefix_cache)
                               and self.chunked
                               and self.runner.prefix_cache_ok)
        self.preemption = self.paged if preemption is None else bool(preemption)
        self.queue_watermark = queue_watermark
        hi, lo = page_watermarks
        assert 0.0 < lo <= hi <= 1.0, "page_watermarks must be (hi, lo) in (0,1]"
        self.page_watermarks = (float(hi), float(lo))
        self.degraded_max_new = degraded_max_new
        self.tenant_quota = tenant_quota
        self._degraded = False

        self.state = self.runner.init_state(
            capacity, max_len,
            page_size=self.page_size if self.paged else None,
            pool_pages=self.pool.num_pages if self.paged else None)
        if mesh is not None:
            # Slot state / KV caches shard over the data axes (slot = batch
            # row); everything stays replicated over 'model' so the
            # column-parallel matmul dispatch keeps results bit-identical
            # to single-device at any mesh shape.
            self.state = self.runner.shard_state(self.state, mesh)
        self.slots: List[Optional[Request]] = [None] * capacity
        self._next_input = np.zeros((capacity,), np.int32)

        # -- overlapped runtime (serving.stream) ---------------------------
        # overlap=False keeps the historical blocking tick: every pass
        # host-syncs through a DeviceStream (inline fetch), and the
        # simulated-clock path is bit-identical to the pre-stream engine.
        # overlap=True (wall clock only) dispatches ahead: sampling runs
        # ON DEVICE inside the jitted pass, the host tracks token COUNTS
        # (`Request.dispatched`) without values, and a background worker
        # resolves each pass's sampled tokens, fires streaming callbacks,
        # and finalizes metrics while the next pass is already running.
        self.overlap = bool(overlap)
        if self.overlap and clock is None:
            raise ValueError(
                "overlap=True needs a wall clock (clock=time.perf_counter): "
                "the simulated clock is defined by blocking passes")
        self._perf = time.perf_counter  # injectable for deterministic tests
        self._owns_stream = stream is None
        self._stream: DeviceStream = stream if stream is not None else (
            OverlappedStream(depth=inflight) if self.overlap
            else DeviceStream())
        self._delivered: deque = deque()    # finished by the worker,
                                            # flushed into poll() returns
        self._dev_next = None               # previous pass's device samples
        self._ov_vals = np.zeros((capacity,), np.int32)
        self._ov_mask = np.zeros((capacity,), bool)

        # Collections show in a trace as ``python.gc`` spans.  The hook
        # holds no reference to the engine; it goes with close(), or with
        # the engine when it is never closed.
        hook = GcSpans()
        gc.callbacks.append(hook)
        self._unhook_gc = weakref.finalize(self, gc.callbacks.remove, hook)

        self.ticks = 0
        self.scheduler = get_scheduler(policy)
        self.metrics = ServingMetrics(capacity)
        self.tick_time = float(tick_time)
        self._clock = clock             # None => simulated (tick_time/pass)
        self.now = clock() if clock is not None else 0.0
        self._just_finished: List[Request] = []
        self._returned: List[Request] = []  # finalized outside step():
                                            # shed + admission-pass expiries
        self._has_deadlines = False     # set on first deadline'd request

        # Wall-clock tick monitoring: every jitted pass's host-visible
        # duration feeds the trailing-median straggler model; escalation
        # state (log -> reslice -> remesh) surfaces in metrics.summary().
        self.straggler = StragglerMonitor()
        self.metrics.straggler = self.straggler

        # -- fault tolerance (serving.faults) ------------------------------
        # With ``faults=None`` nothing below exists on the hot path: the
        # params, jitted functions, and per-tick flow are identical to a
        # build without fault machinery (zero-overhead guarantee,
        # parity-tested in tests/test_faults.py).
        self.recovery = recovery
        self.detect_every = max(1, int(detect_every))
        self._fault_cursor = 0
        self._lost_shard: Optional[int] = None
        self._fault_dirty = False       # unrepaired injected faults active
        if isinstance(faults, FaultConfig):
            from repro.kernels.ops import tp_size
            faults = faultlib.make_fault_plan(self.params, faults,
                                              tp=tp_size(mesh))
        self.fault_plan: Optional[FaultPlan] = faults
        if self.fault_plan is not None:
            # Clean copy = the replicated hot spare the repairs re-program
            # from (a reference, not a copy: injection replaces arrays).
            self._params_clean = self.params
            self._fault_sites = faultlib.fault_sites(self.params)
            self._baselines = faultlib.fingerprint_baselines(self.params)

        self._build_jitted()

    def _build_jitted(self):
        """(Re)build the jitted step/prefill/reset closures for the current
        mesh — called at init and again after a shard-drop re-shard.  The
        closures themselves come from the runner (the model-family seam);
        the engine owns only jit + donation policy.

        Step and prefill are built in their SAMPLED form (the runner wraps
        the same core body either way): every pass returns ``(logits,
        sampled, new_state)`` with next-token sampling on device, so the
        blocking and overlapped paths share one closure and one compile —
        the blocking path simply fetches logits and keeps the host
        sampler, bit-identically to the pre-stream engine."""
        r = self.runner
        self._jit_step = jax.jit(
            r.make_step(self.quant, self.mesh, seed=self.seed),
            donate_argnums=(1,))
        # One compile per chunk bucket (shape-specialized), nothing more.
        self._jit_prefill = jax.jit(
            r.make_prefill(self.quant, self.mesh, seed=self.seed),
            donate_argnums=(1,))
        # Per-shape warmed executables + warmup bookkeeping: a reshard
        # invalidates every compiled shape (new mesh, new shardings).
        self._cached_pref = {}
        self._warmed_shapes = set()
        self._dev_next = None
        # Compile-once slot reset: the slot index is data, so admission
        # under churn costs one fused scatter pass instead of a host-side
        # state rebuild that scales with model size.
        self._jit_reset = jax.jit(r.make_reset(), donate_argnums=(0,))
        self._jit_attach = jax.jit(r.make_attach(), donate_argnums=(0,))
        self._jit_copy_page = jax.jit(r.make_copy_page(), donate_argnums=(0,))
        self._jit_admit = None
        if r.needs_admission:
            self._jit_admit = jax.jit(r.make_admit(self.quant, self.mesh),
                                      donate_argnums=(1,))

    # -- warmed executables -----------------------------------------------
    def _executable(self, shape_key: Tuple, args: Tuple):
        """The ``_cached_pref`` map: one AOT-compiled executable per jit
        shape — ``("decode",)`` or ``("prefill", bucket)`` — compiled (via
        ``jit(...).lower(args).compile()``) OUTSIDE the timed region, so a
        cold bucket's compile never lands in a straggler sample or a
        utilization span.  Returns ``(fn, warmup)``; ``warmup`` marks the
        first EXECUTION of this shape, which the caller excludes from the
        straggler model (first-run dispatch overhead is not a straggler
        signal — see StragglerMonitor)."""
        fn = self._cached_pref.get(shape_key)
        if fn is None:
            base = (self._jit_step if shape_key[0] == "decode"
                    else self._jit_prefill)
            fn = base.lower(*args).compile()
            self._cached_pref[shape_key] = fn
        warm = shape_key not in self._warmed_shapes
        self._warmed_shapes.add(shape_key)
        return fn, warm

    def warmup(self):
        """Pre-compile the decode tick and every prefill bucket so no
        compile happens once traffic is live (benchmarks call this before
        the timed window; a cold engine self-warms lazily through
        ``_executable`` instead)."""
        self._executable(("decode",), self._decode_proto())
        if self.chunked:
            for bucket in self.prefill_chunks:
                self._executable(("prefill", bucket),
                                 self._prefill_proto(bucket))
        # Pre-compiling must not mark shapes as executed: the first REAL
        # pass per shape still carries first-dispatch overhead.
        self._warmed_shapes.clear()

    def compiled_hlo(self, shape_key: Tuple = ("decode",)) -> str:
        """Optimized HLO of the warmed executable for ``shape_key``: the
        program the device runs, where Pallas kernels compiled for a TPU
        appear as ``tpu_custom_call`` custom calls."""
        return self._cached_pref[shape_key].as_text()

    def _call(self, shape_key: Tuple, args: Tuple):
        """Dispatch one pass through the warmed-executable cache.  If the
        AOT executable rejects the concrete arguments (e.g. a sharding
        lowered from a host prototype disagreeing with a live device
        array), fall back to plain jit dispatch for that shape — correct
        either way, the cache is an optimization.

        Only that rejection is caught: the executable checks its
        arguments before it runs (``TypeError`` for types or tree
        structure, ``ValueError`` for shardings), so nothing was executed
        or donated yet.  A failure while running propagates: with donated
        state a re-run would read invalidated buffers."""
        fn, warm = self._executable(shape_key, args)
        base = (self._jit_step if shape_key[0] == "decode"
                else self._jit_prefill)
        with span("serving.launch", pass_id=self.ticks):
            if fn is base:
                return fn(*args), warm
            try:
                return fn(*args), warm
            except (TypeError, ValueError):
                self._cached_pref[shape_key] = base
                return base(*args), warm

    # -- dispatch inputs --------------------------------------------------
    def _samp_arrays(self):
        """Per-slot sampling inputs for the on-device sampler: temperature,
        uid, and NEXT token index (``dispatched``, which in overlap mode
        runs ahead of ``len(generated)``) — zeros for empty slots."""
        temps = np.zeros((self.capacity,), np.float32)
        uids = np.zeros((self.capacity,), np.int32)
        idxs = np.zeros((self.capacity,), np.int32)
        for i, req in enumerate(self.slots):
            if req is not None:
                temps[i] = req.temperature
                uids[i] = req.uid & 0x7FFFFFFF
                idxs[i] = req.dispatched
        return temps, uids, idxs

    def _decode_proto(self) -> Tuple:
        """Zero-valued decode-tick arguments (lowering prototypes only)."""
        z = np.zeros
        c = self.capacity
        return (self.params, self.state, z((c,), np.int32), z((c,), np.int32),
                z((c,), bool), self.key, z((c,), np.float32),
                z((c,), np.int32), z((c,), np.int32))

    def _prefill_proto(self, bucket: int) -> Tuple:
        """Zero-valued prefill-pass arguments for one chunk bucket."""
        z = np.zeros
        c = self.capacity
        return (self.params, self.state, z((c, bucket), np.int32),
                z((c,), np.int32), z((c,), np.int32), z((c,), bool),
                self.key, z((c,), np.float32), z((c,), np.int32),
                z((c,), np.int32))

    def _set_next(self, i: int, val: int):
        """Host-known next input for slot i.  The blocking path reads it
        from ``_next_input``; the overlapped path additionally records it
        as an OVERRIDE (``ov_mask``) because the base decode input there is
        the previous pass's device sample, which a host prompt feed must
        shadow."""
        self._next_input[i] = int(val)
        if self.overlap:
            self._ov_vals[i] = int(val)
            self._ov_mask[i] = True

    def _clear_ov(self, i: int):
        self._ov_vals[i] = 0
        self._ov_mask[i] = False

    # -- delivery (the stream's consumer side) ----------------------------
    def _account_dispatch(self, i: int, req: Request) -> TokenRec:
        """Host bookkeeping for one on-device sampled token the overlapped
        path has NOT seen yet: bump the dispatched count and, when it hits
        the request's limit, free the slot immediately — completion is a
        COUNT property, so the next admission can reuse the slot while the
        final token is still in flight.  (Device passes execute in
        dispatch order, so pages released here cannot be overwritten
        before this pass's writes land.)"""
        req.dispatched += 1
        limit = req.max_new_tokens
        if self.paged and self._slot_cap[i] is not None:
            limit = min(limit, self._slot_cap[i])
        finishing = req.dispatched >= limit
        if finishing:
            self.slots[i] = None
            self._release_slot(i, req.tenant)
        return TokenRec(slot=i, req=req, finishing=finishing,
                        corrupted=self._fault_dirty)

    def _deliver_ticket(self, ticket: Ticket):
        """Resolve one dispatched pass (runs on the stream's worker thread
        in overlap mode): fetch the (B,) sampled tokens — the ONLY
        device->host transfer on the overlapped hot path — append values,
        fire streaming callbacks, finalize metrics, and feed the
        straggler/utilization gauges."""
        vals = self._stream.fetch(ticket.sampled, pass_id=ticket.pass_id)
        with span("serving.deliver", pass_id=ticket.pass_id):
            done = self._perf()
            self.metrics.on_device_span(ticket.t0, done)
            if not ticket.warmup:
                self.straggler.observe(done - ticket.t0)
            for rec in ticket.recs:
                req = rec.req
                nxt = int(vals[rec.slot])
                req.generated.append(nxt)
                self.metrics.on_token(req.uid, ticket.now)
                if rec.corrupted:
                    self.metrics.on_corrupted(req.uid)
                if req.on_token is not None:
                    req.on_token(req, nxt)
                if rec.finishing:
                    req.done = True
                    self.metrics.on_finish(req.uid, ticket.now)
                    self._delivered.append(req)

    def _drain_delivered(self) -> List[Request]:
        out: List[Request] = []
        while self._delivered:
            out.append(self._delivered.popleft())
        return out

    def sync(self):
        """Wait until every in-flight pass has delivered its tokens
        (no-op on the blocking path).  Called internally before anything
        that must observe COMPLETE token streams: preemption replay
        snapshots, deadline expiry, fault requeues, reshards."""
        self._stream.sync()

    def close(self):
        """Shut down the background delivery worker and remove the
        ``python.gc`` hook.  Safe on any engine; an engine sharing a
        fleet-owned stream leaves the stream to the fleet."""
        if self._owns_stream:
            self._stream.sync()
            self._stream.close()
        self._unhook_gc()

    # -- clock ----------------------------------------------------------------
    def _tick_clock(self):
        """One jitted pass just ran: advance the engine clock (simulated
        ticks or wall time) BEFORE tokens from that pass are recorded."""
        self.ticks += 1
        self.now = (self._clock() if self._clock is not None
                    else self.now + self.tick_time)

    # -- slot state reset -------------------------------------------------
    def _reset_slot(self, i: int):
        self.state = self._jit_reset(self.state, jnp.int32(i))

    # -- admission ------------------------------------------------------------
    def _feed(self, req: Request) -> List[int]:
        """The token stream this request prefills from: the preemption
        replay snapshot (prompt + tokens already streamed) when resuming a
        recompute, else the prompt."""
        return req.replay if req.replay is not None else req.prompt

    def fits(self, req: Request) -> bool:
        """A request needs a non-empty prompt (there is no token to condition
        the first generation on otherwise) and must leave room for at least
        one generated token — the chunk scatter parks padding lanes on the
        next unwritten cache slot, which only exists while
        length + n_tokens < max_len.

        Under paging the legacy ``prompt + max_new <= max_len`` hard bound
        relaxes to a PAGE-BUDGET check: a long request is admissible iff
        the page table can address it and the pool (at full eviction) could
        grow it — the pool serves worst cases that per-slot allocation
        would have to reserve for everyone."""
        if len(req.prompt) < 1:
            return False
        total = len(req.prompt) + max(1, req.max_new_tokens)
        if not self.paged:
            # Fixed-state runners (recurrent families) hold O(1) decode
            # state per slot — sequence length never hits a cache bound.
            return self.runner.fixed_state or total <= self.max_len
        need = self.runner.capacity_cost(total, self.page_size)
        return need <= self.max_pages and need <= self.pool.num_pages

    def _should_shed(self, req: Request, at: float) -> bool:
        """Admission backpressure for requests arriving NOW: shed when the
        queue is past its watermark, or when the pool is past the high
        pressure watermark AND the queue already covers the batch."""
        if (self.queue_watermark is not None
                and self.scheduler.pending(at) >= self.queue_watermark):
            return True
        if (self.paged and self.pool.pressure() >= self.page_watermarks[0]
                and self.scheduler.pending(at) >= self.capacity):
            return True
        return False

    def _retry_after(self, at: float) -> float:
        """Absolute engine-clock time the shed client should retry at:
        backlog / capacity service rounds at the observed mean E2E (or a
        few ticks before any request has finished)."""
        fin = [r.e2e for r in self.metrics.finished() if r.e2e is not None]
        est = float(np.mean(fin)) if fin else self.tick_time * 8
        backlog = self.scheduler.pending(at) + sum(
            1 for s in self.slots if s is not None)
        return at + est * max(1.0, backlog / max(1, self.capacity))

    def submit(self, req: Request) -> bool:
        """Enqueue a request for arrival-driven admission.  Stamps
        ``arrival_time`` with the current clock when unset.  Oversized
        requests are rejected (marked done, recorded in metrics) instead of
        crashing the serve loop; under backpressure watermarks an arriving
        request is SHED instead of queued (``req.shed`` with a
        ``req.retry_after`` hint, surfaced through the next ``poll()``).
        Returns False for both."""
        if not self.fits(req) or not self.runner.accepts(req):
            req.done = True
            self.metrics.on_reject(req.uid)
            return False
        if req.arrival_time is None:
            req.arrival_time = self.now
        if req.arrival_time <= self.now and self._should_shed(
                req, req.arrival_time):
            req.done = True
            req.shed = True
            req.retry_after = self._retry_after(req.arrival_time)
            self.metrics.on_shed(req.uid, tenant=req.tenant,
                                 retry_after=req.retry_after)
            self._returned.append(req)
            return False
        if req.deadline is not None:
            self._has_deadlines = True
        self.metrics.on_submit(req.uid, arrival_time=req.arrival_time,
                               tenant=req.tenant,
                               prompt_len=len(req.prompt))
        self.scheduler.add(req)
        return True

    def try_admit(self, req: Request) -> bool:
        if not self.fits(req):
            raise ValueError(
                f"request {req.uid}: prompt ({len(req.prompt)}) must be "
                f"non-empty and prompt + max_new ({req.max_new_tokens}) "
                f"must fit max_len ({self.max_len})")
        for i, slot in enumerate(self.slots):
            if slot is None:
                self._reset_slot(i)
                self._clear_ov(i)   # stale override from a past occupant
                self.slots[i] = req
                if req.arrival_time is None:
                    req.arrival_time = self.now
                if req.deadline is not None:
                    self._has_deadlines = True
                self.metrics.on_admit(req.uid, self.now, tenant=req.tenant,
                                      prompt_len=len(req.prompt),
                                      arrival_time=req.arrival_time)
                if self.paged:
                    self._table[i, :] = self.pool.sentinel
                    self._slot_pages[i] = []
                    self._slot_len[i] = 0
                    self._slot_keys[i] = []
                    # Degraded mode caps generation for admissions made
                    # under pressure (never below what a resumed request
                    # already streamed).
                    self._slot_cap[i] = None
                    if self._degraded and self.degraded_max_new is not None:
                        self._slot_cap[i] = max(self.degraded_max_new,
                                                len(req.generated) + 1)
                if self._jit_admit is not None:
                    # Runner admission hook (enc-dec: one encoder pass whose
                    # cross-attention KV is cached in this slot for the whole
                    # request).  Keyed off the request uid so a preemption
                    # replay re-encodes to bit-identical features.
                    akey = jax.random.fold_in(
                        jax.random.PRNGKey(self.seed), req.uid)
                    self.state = self._jit_admit(
                        self.params, self.state,
                        jnp.asarray(req.features), jnp.int32(i), akey)
                toks = self._feed(req)
                if self.chunked:
                    req.prompt_pos = 0      # consumed by prefill passes
                    if self.prefix_enabled:
                        self._attach_prefix(i, req)
                else:
                    # Legacy prefill-in-decode: one prompt token per tick.
                    self._set_next(i, toks[0])
                    req.prompt_pos = 1
                return True
        return False

    def _admissible(self, req: Request) -> bool:
        """Pop-time admission filter: per-tenant page quota (noisy-neighbor
        isolation) and basic pool availability.  Requests failing it are
        SKIPPED, not dequeued, so one greedy tenant never head-of-line
        blocks the rest of the queue."""
        if not self.paged:
            return True
        return self._quota_ok(req) and self.pool.available() >= 1

    def _quota_ok(self, req: Request) -> bool:
        """Per-tenant page quota, checked against PROJECTED footprints.
        Pages are allocated lazily per prefill chunk, so gating on current
        holdings alone would let a tenant admit several requests "under
        quota" in one pass and then grow all of them past it; instead each
        live same-tenant slot is charged its full eventual footprint.  A
        tenant with nothing in flight always passes — a quota can throttle
        a tenant, never starve it outright.  Also the quota-only filter
        for the priority-claim path, where page availability is what
        preemption is about to create."""
        if self.tenant_quota is None or self.pool is None:
            return True
        live = [r for r in self.slots
                if r is not None and r.tenant == req.tenant]
        if not live and self.pool.tenant_held(req.tenant) == 0:
            return True
        charged = sum(
            self.runner.capacity_cost(
                len(r.prompt) + max(1, r.max_new_tokens), self.page_size)
            for r in live)
        remaining = max(1, req.max_new_tokens - len(req.generated))
        need = self.runner.capacity_cost(
            len(self._feed(req)) + remaining, self.page_size)
        return charged + need <= self.tenant_quota

    def _admit_arrived(self) -> List[Request]:
        """Fill free slots from the scheduler queue (policy order) with
        requests that have arrived by the current clock.

        Queue expiry runs FIRST: a request requeued (by fault recovery or
        preemption) whose deadline has since passed must be timed out here,
        never re-admitted — its expiry is surfaced through the same poll
        that would have admitted it."""
        with span("serving.admit") as sp:
            if self._has_deadlines:
                self._returned.extend(self._expire_queue())
            admitted: List[Request] = []
            free = self.slots.count(None)
            while free > 0:
                req = self.scheduler.pop(
                    self.now, self._admissible if self.paged else None)
                if req is None:
                    break
                self.try_admit(req)     # a slot is free; fits() held
                admitted.append(req)
                free -= 1
            if self.paged and self.preemption:
                self._priority_claim(admitted)
            sp.set_metadata(admitted=len(admitted),
                            queued=len(self.scheduler))
        return admitted

    def _priority_claim(self, admitted: List[Request]):
        """Under saturation, a strictly-higher-priority arrival claims a
        slot (and its pages) by preempting the lowest-priority live
        request; ties and lower priorities wait their turn."""
        while True:
            top = self.scheduler.peek(self.now, self._quota_ok)
            if top is None:
                return
            if self.slots.count(None) and self.pool.available() >= 1:
                return              # normal admission will take it
            victims = [i for i, s in enumerate(self.slots)
                       if s is not None and s.priority < top.priority]
            if not victims:
                return
            v = min(victims, key=lambda i: (self.slots[i].priority,
                                            -(self.slots[i].arrival_time
                                              or 0.0),
                                            -self.slots[i].uid))
            self._preempt_slot(v)
            self.scheduler.remove(top)
            self.try_admit(top)
            admitted.append(top)

    # -- sampling -------------------------------------------------------------
    def _record(self, i: int, req: Request, logits_row: np.ndarray):
        if req.temperature > 0:
            # Temperature sampling from the engine's seeded stream: the
            # draw is keyed by (engine seed, uid, token index), so outputs
            # are reproducible for a given engine seed no matter how the
            # scheduler interleaves this request with others.
            z = logits_row.astype(np.float64) / req.temperature
            z -= z.max()
            p = np.exp(z)
            p /= p.sum()
            rng = np.random.default_rng(
                (self.seed, req.uid, len(req.generated)))
            nxt = int(rng.choice(len(p), p=p))
        else:
            nxt = int(np.argmax(logits_row))
        req.generated.append(nxt)
        req.dispatched = len(req.generated)
        self._next_input[i] = nxt
        self.metrics.on_token(req.uid, self.now)
        if self._fault_dirty:
            # This token was computed against faulted weights that no
            # detection round has repaired yet: the request's output can't
            # be trusted.  (Cleared if recovery later requeues it.)
            self.metrics.on_corrupted(req.uid)
        if req.on_token is not None:
            req.on_token(req, nxt)
        limit = req.max_new_tokens
        if self.paged and self._slot_cap[i] is not None:
            limit = min(limit, self._slot_cap[i])
        if len(req.generated) >= limit:
            req.done = True
            self.slots[i] = None            # free for the next request
            self._release_slot(i, req.tenant)
            self.metrics.on_finish(req.uid, self.now)
            self._just_finished.append(req)

    # -- paged pool management --------------------------------------------
    def _release_slot(self, i: int, tenant: str):
        """Return slot i's pages to the pool and clear its host mirrors.
        Pages the prefix cache also holds stay allocated for reuse."""
        if not self.paged:
            return
        if self._slot_pages[i]:
            self.pool.release(self._slot_pages[i], tenant)
        self._slot_pages[i] = []
        self._slot_len[i] = 0
        self._slot_keys[i] = []
        self._slot_cap[i] = None
        self._table[i, :] = self.pool.sentinel

    def _preempt_slot(self, i: int):
        """Evict slot i to the queue with a recompute plan: its pages go
        back to the pool NOW, and ``req.replay`` snapshots prompt + every
        token already streamed so the resume prefills the identical stream
        (bit-identical continuation in float mode — re-prefilling the same
        tokens rebuilds the same cache the decode ticks had built)."""
        self.sync()     # the replay snapshot needs every in-flight token
        req = self.slots[i]
        self.slots[i] = None
        self._next_input[i] = 0
        self._clear_ov(i)
        self._release_slot(i, req.tenant)
        req.replay = list(req.prompt) + list(req.generated)
        req.prompt_pos = 0
        req.preempted += 1
        self.metrics.on_preempt(req.uid, self.now)
        self.scheduler.requeue(req)

    def _preempt_for(self, req: Request) -> bool:
        """Free pages for ``req`` by preempting a live victim that does not
        outrank it (strictly lower priority, or same priority but younger).
        Returns False when no such victim exists."""
        cand = [i for i, s in enumerate(self.slots)
                if s is not None and s is not req
                and (s.priority < req.priority
                     or (s.priority == req.priority
                         and (s.arrival_time or 0.0)
                         >= (req.arrival_time or 0.0)))]
        if not cand:
            return False
        v = min(cand, key=lambda i: (self.slots[i].priority,
                                     -(self.slots[i].arrival_time or 0.0),
                                     -self.slots[i].uid))
        self._preempt_slot(v)
        return True

    def _chunk_cap(self) -> int:
        """Largest prefill chunk this tick: degraded mode shrinks the
        bucket to the smallest configured chunk so admission burst memory
        stays bounded while the pool is saturated."""
        if self.paged and self._degraded:
            return self.prefill_chunks[0]
        return self.prefill_chunks[-1] if self.prefill_chunks else 1

    def _update_degraded(self):
        """Hysteretic degraded mode: enter at the high pool-pressure
        watermark, recover only once pressure falls to the low one."""
        hi, lo = self.page_watermarks
        p = self.pool.pressure()
        if not self._degraded and p >= hi:
            self._degraded = True
            self.metrics.on_degraded(True, self.now)
        elif self._degraded and p <= lo:
            self._degraded = False
            self.metrics.on_degraded(False, self.now)

    def _grow_slot(self, i: int, req: Request, need: int) -> bool:
        """Make slot i's next ``need`` token positions writable: CoW-split
        shared pages in the write range, allocate missing pages, and — when
        the pool is dry — preempt non-outranking victims (possibly slot i
        itself, returning False)."""
        extra, writes = plan_chunk(self._slot_len[i], need,
                                   self._slot_pages[i], self.page_size)
        for j in writes:
            p = self._slot_pages[i][j]
            newp = self.pool.cow(p, req.tenant)
            while newp is None:
                if not self._preempt_for(req):
                    self._preempt_slot(i)
                    return False
                newp = self.pool.cow(p, req.tenant)
            if newp != p:
                self.state = self._jit_copy_page(
                    self.state, jnp.int32(p), jnp.int32(newp))
                self._slot_pages[i][j] = newp
                self._table[i, j] = newp
        while extra > 0:
            got = self.pool.alloc(extra, req.tenant)
            if got is not None:
                base = len(self._slot_pages[i])
                for jj, p in enumerate(got):
                    self._table[i, base + jj] = p
                self._slot_pages[i].extend(got)
                break
            if not self._preempt_for(req):
                self._preempt_slot(i)
                return False
        return True

    def _ensure_pages(self, live: List[int]) -> List[int]:
        """Before a jitted pass, guarantee every live slot owns writable
        pages for the tokens it is about to append — higher-priority /
        older slots claim first, so pool exhaustion preempts the requests
        preemption policy says should yield.  Returns the surviving live
        list."""
        cap = self._chunk_cap()
        order = sorted(live, key=lambda i: (-self.slots[i].priority,
                                            self.slots[i].arrival_time or 0.0,
                                            self.slots[i].uid))
        for i in order:
            req = self.slots[i]
            if req is None:
                continue            # preempted by an earlier claimant
            toks = self._feed(req)
            rem = len(toks) - req.prompt_pos
            need = min(rem, cap) if rem > 0 else 1
            self._grow_slot(i, req, need)
        return [i for i in live if self.slots[i] is not None]

    def _attach_prefix(self, i: int, req: Request):
        """Prefix-cache attach at admission: walk the prompt's full-page
        chain keys through the pool cache; every hit is SHARED (ref++) so
        those pages are never re-prefilled.  When the whole prompt hits, we
        back off one token — the last token re-feeds through the normal
        pass to produce first logits, and its write triggers the CoW split
        of the shared final page."""
        toks = self._feed(req)
        key = None
        matched: List[Tuple[int, int]] = []
        pos = 0
        while pos + self.page_size <= len(toks):
            key = prefix_key(key, toks[pos:pos + self.page_size])
            p = self.pool.lookup(key)
            if p is None:
                break
            matched.append((key, p))
            pos += self.page_size
        if not matched:
            return
        self.pool.share([p for _, p in matched], req.tenant)
        self._slot_pages[i] = [p for _, p in matched]
        self._slot_keys[i] = [k for k, _ in matched]
        for j, (_, p) in enumerate(matched):
            self._table[i, j] = p
        attached = min(pos, len(toks) - 1)
        self._slot_len[i] = attached
        req.prompt_pos = attached
        self.state = self._jit_attach(self.state, jnp.int32(i),
                                      jnp.int32(attached))

    def _register_prefix(self, i: int, req: Request):
        """Publish slot i's fully-prefilled PROMPT pages under their chain
        keys (fresh requests only — replay streams would poison the cache
        with generated tokens)."""
        if req.replay is not None:
            return
        full = min(req.prompt_pos, len(req.prompt)) // self.page_size
        while len(self._slot_keys[i]) < full:
            j = len(self._slot_keys[i])
            block = req.prompt[j * self.page_size:(j + 1) * self.page_size]
            prev = self._slot_keys[i][-1] if self._slot_keys[i] else None
            key = prefix_key(prev, block)
            self._slot_keys[i].append(key)
            if j < len(self._slot_pages[i]):
                self.pool.register(key, self._slot_pages[i][j])

    # -- deadlines --------------------------------------------------------
    def _expire_slots(self):
        """Cancel in-flight requests past their deadline: free the slot
        immediately (the next admit resets its state) instead of letting a
        stuck request squat until max_new_tokens."""
        for i, req in enumerate(self.slots):
            if (req is not None and req.deadline is not None
                    and req.deadline <= self.now):
                self.slots[i] = None
                self._release_slot(i, req.tenant)
                req.done = True
                req.timed_out = True
                self.metrics.on_timeout(req.uid, self.now)
                self._just_finished.append(req)

    def _expire_queue(self) -> List[Request]:
        """Time out queued requests whose deadline already passed."""
        expired = self.scheduler.expire(self.now)
        for req in expired:
            req.done = True
            req.timed_out = True
            self.metrics.on_timeout(req.uid, self.now)
        return expired

    # -- fault tolerance --------------------------------------------------
    def _inject_due_faults(self):
        """Apply every fault event scheduled at or before the current tick:
        a sharding-preserving rewrite of the packed operands the jitted
        step streams (serving.faults), so the fault flows through
        dense_tp / the packed kernels at any mesh shape."""
        from repro.kernels.ops import tp_size
        due, self._fault_cursor = self.fault_plan.due(
            self.ticks, self._fault_cursor)
        for ev in due:
            if ev.kind == "shard_drop":
                # The injectable host-failure signal distributed.fault
                # documents — recovery reads it as a health-check verdict.
                self._lost_shard = ev.shard
            self.params = faultlib.apply_event(
                self.params, ev, tp=tp_size(self.mesh), quant=self.quant,
                mesh=self.mesh)
            self.metrics.on_fault(ev.kind)
            self._fault_dirty = True

    def _detect_and_recover(self):
        """One detection round: fingerprint-probe every fault site against
        its healthy baseline; with recovery on, repair what was found
        (re-quantize drifted tiles, remap stuck columns, re-shard on a
        lost-shard health signal + requeue its in-flight requests)."""
        self.sync()     # requeues read complete streams + corruption marks
        if self._lost_shard is not None and self.recovery:
            self._reshard_and_requeue()
            return
        hits = []
        for site in self._fault_sites:
            cur = faultlib.site_fingerprint(self.params, site)
            det = faultlib.detect_site(self._baselines[site.path], cur)
            if not det.clean:
                hits.append((site, det))
        if hits:
            self.metrics.on_detected(sum(
                len(d.stuck_cols) + len(d.drifted) for _, d in hits))
        if not self.recovery:
            return
        for site, det in hits:
            if det.stuck_cols:
                self.params = faultlib.repair_stuck(
                    self.params, self._params_clean, site.path,
                    det.stuck_cols)
                self.metrics.on_repair("cols_remapped", len(det.stuck_cols))
            if det.drifted:
                self.params = faultlib.repair_drift(
                    self.params, self._params_clean, site.path, det.drifted)
                self.metrics.on_repair("tiles_requantized", len(det.drifted))
        if hits:
            # Tokens emitted during the dirty window were computed against
            # faulted weights; with recovery on they are DISCARDED and the
            # request re-decoded from the now-clean array (a shipped token
            # is gone, so only in-flight requests can be salvaged).
            self._requeue_corrupted()
        # Everything detectable was just repaired; ticks from here on are
        # clean until the next injection flips the flag back.
        self._fault_dirty = False

    def _requeue_corrupted(self):
        """Restart in-flight requests whose partial output (and KV cache)
        was produced under an active fault: free the slot, clear generated
        tokens, and requeue — arrival order is preserved, so they re-admit
        ahead of younger traffic."""
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            rec = self.metrics.requests.get(req.uid)
            if rec is None or not rec.corrupted:
                continue
            self.slots[i] = None
            self._next_input[i] = 0
            self._clear_ov(i)
            self._release_slot(i, req.tenant)
            req.prompt_pos = 0
            req.generated.clear()
            req.dispatched = 0
            req.replay = None       # corrupted stream: restart from prompt
            self.metrics.on_requeue(req.uid)
            self.scheduler.requeue(req)

    def _reshard_and_requeue(self):
        """Shard-drop recovery: re-plan the mesh without the lost bank
        (distributed.fault.plan_recovery_mesh), re-program weights from
        the clean master onto the surviving chips, and requeue every
        in-flight request through the scheduler with state reset — the
        lost shard's slot state (KV caches) died with it, but no request
        is ever lost (conservation: submitted == completed + rejected +
        timed_out still holds over the whole trace)."""
        import numpy as onp
        from jax.sharding import Mesh

        from repro.distributed.sharding import shard_serving_params

        self._lost_shard = None
        if self.mesh is not None and self.mesh.devices.size > 1:
            old_shape = tuple(self.mesh.devices.shape)
            dp, tp = old_shape
            # Losing model bank s costs its chip in every data row.
            plan = plan_recovery_mesh(dp * tp - dp, tp, old_shape)
            devices = list(self.mesh.devices.flat)
            keep = devices[: plan.new_shape[0] * plan.new_shape[1]]
            self.mesh = Mesh(
                onp.asarray(keep).reshape(plan.new_shape),
                self.mesh.axis_names)
            self.params = shard_serving_params(
                self._params_clean, self.mesh, self.quant)
            self._params_clean = self.params
            self._build_jitted()        # closures bind the new mesh
            self.state = self.runner.init_state(
                self.capacity, self.max_len,
                page_size=self.page_size if self.paged else None,
                pool_pages=self.pool.num_pages if self.paged else None)
            self.state = self.runner.shard_state(self.state, self.mesh)
        else:
            # Single-array engine: re-program the array from the spare.
            self.params = self._params_clean
            self.state = self.runner.init_state(
                self.capacity, self.max_len,
                page_size=self.page_size if self.paged else None,
                pool_pages=self.pool.num_pages if self.paged else None)
        if self.paged:
            # The lost shard's pool pages died with the state: rebuild the
            # allocator (prefix cache included) from scratch.
            self.pool = PagePool(self.pool.num_pages, self.page_size)
            self._table = page_table_array(self.capacity, self.max_pages,
                                           self.pool.sentinel)
            self._slot_pages = [[] for _ in range(self.capacity)]
            self._slot_len = [0] * self.capacity
            self._slot_keys = [[] for _ in range(self.capacity)]
            self._slot_cap = [None] * self.capacity
        inflight = [r for r in self.slots if r is not None]
        self.slots = [None] * self.capacity
        self._next_input[:] = 0
        self._ov_vals[:] = 0
        self._ov_mask[:] = False
        for req in inflight:
            req.prompt_pos = 0
            req.generated.clear()
            req.dispatched = 0
            req.replay = None
            self.metrics.on_requeue(req.uid)
            self.scheduler.requeue(req)
        self.metrics.on_repair("reshards", 1)
        self._fault_dirty = False

    # -- one engine tick ------------------------------------------------------
    def step(self):
        with span("serving.step"):
            # Completion flushing happens per pass (not only per poll) so
            # a long-lived engine driven through the legacy
            # try_admit()/step() path never accumulates finished Request
            # objects.
            self._just_finished = []
            if self._has_deadlines:
                if self.overlap:
                    self.sync()     # cancel only COMPLETE streams
                self._expire_slots()
                self._just_finished.extend(self._expire_queue())
            if self.fault_plan is not None:
                # Detect (and repair) faults from earlier ticks BEFORE this
                # tick's injections land, so every fault is live for at
                # least one pass — then inject whatever the plan schedules
                # now.
                if self.ticks % self.detect_every == 0 and (
                        self._fault_dirty or self._lost_shard is not None):
                    self._detect_and_recover()
                self._inject_due_faults()
            live = [i for i, s in enumerate(self.slots) if s is not None]
            if self.paged:
                self._update_degraded()
                if live:
                    # Claim/CoW/grow pages for every token this pass
                    # appends; pool exhaustion preempts here, before the
                    # jitted call.
                    live = self._ensure_pages(live)
            if not live:
                return
            self.metrics.on_tick(
                self.now, len(live), self.capacity,
                self.scheduler.pending(self.now),
                pool=self.pool.stats() if self.paged else None,
                degraded=self._degraded)
            prefilling = [i for i in live
                          if self.slots[i].prompt_pos
                          < len(self._feed(self.slots[i]))]
            if self.chunked and prefilling:
                if all(len(self._feed(self.slots[i]))
                       - self.slots[i].prompt_pos == 1 for i in prefilling):
                    # Every prefilling slot has exactly ONE prompt token
                    # left: the decode tick already has the right shape, so
                    # feed that token as the decode input instead of paying
                    # a padded smallest-bucket chunk pass.
                    for i in prefilling:
                        req = self.slots[i]
                        self._set_next(i, self._feed(req)[req.prompt_pos])
                        req.prompt_pos += 1
                    self._decode_tick()
                else:
                    self._prefill_pass(live)
            else:
                self._decode_tick()

    def _prefill_pass(self, live: List[int]):
        """One bucketed prefill pass: prompt chunks for prefilling slots,
        a single next token for decoding slots, no-op for empty slots.

        Decoding slots riding along take their input from ``_next_input``
        on the blocking path, or from the previous pass's on-device sample
        (``rider_mask``) on the overlapped path — unless a host override is
        pending (preemption zeroing, legacy feeds), which wins either way.
        """
        cap = self._chunk_cap()
        need = np.zeros((self.capacity,), np.int32)
        for i in live:
            req = self.slots[i]
            rem = len(self._feed(req)) - req.prompt_pos
            need[i] = min(rem, cap) if rem > 0 else 1
        bucket = next(c for c in self.prefill_chunks if c >= need.max())

        pass_id, fed = self.ticks, int(need.sum())
        self.metrics.on_prefill(self.capacity * bucket, fed)
        with span("serving.prefill_pass", pass_id=pass_id, bucket=bucket,
                  rows=self.capacity * bucket, tokens=fed, live=len(live)):
            tokens = np.zeros((self.capacity, bucket), np.int32)
            riders = np.zeros((self.capacity,), bool)
            for i in live:
                req = self.slots[i]
                toks = self._feed(req)
                if req.prompt_pos < len(toks):
                    n = int(need[i])
                    tokens[i, :n] = toks[req.prompt_pos:req.prompt_pos + n]
                elif (self.overlap and self._dev_next is not None
                        and not self._ov_mask[i]):
                    riders[i] = True    # input = previous device sample
                else:
                    tokens[i, 0] = self._next_input[i]
            if self.paged:
                # A private copy: JAX may read a host array after the call
                # returns (on the CPU backend in place), and the host goes
                # on editing the table for the passes it dispatches next.
                self.state["page_table"] = jnp.asarray(self._table.copy())
            temps, uids, idxs = self._samp_arrays()
            self.key, sub = jax.random.split(self.key)
            rv = (self._dev_next if self._dev_next is not None
                  else np.zeros((self.capacity,), np.int32))
            args = (self.params, self.state, tokens, need, rv, riders, sub,
                    temps, uids, idxs)
            t0 = self._perf()
            self.metrics.window_open(t0)
            (logits, sampled, self.state), warm = self._call(
                ("prefill", bucket), args)
            self._dev_next = sampled
            self._ov_vals[:] = 0
            self._ov_mask[:] = False

            # Recipients: slots whose prompt completes this pass, or decode
            # riders — exactly the slots _record would have sampled for.
            recipients = [
                i for i in live
                if (len(self._feed(self.slots[i]))
                    - self.slots[i].prompt_pos <= int(need[i]))]

            if not self.overlap:
                lg = None
                if recipients:
                    lg = self._stream.fetch(logits, np.float32,
                                            pass_id=pass_id)  # host sync
                    done = self._perf()
                    self.metrics.on_device_span(t0, done)
                    if not warm:
                        self.straggler.observe(done - t0)
                self._tick_clock()
                if self.paged:
                    for i in live:
                        self._slot_len[i] += int(need[i])
                for i in live:
                    req = self.slots[i]
                    toks = self._feed(req)
                    if req.prompt_pos < len(toks):
                        req.prompt_pos += int(need[i])
                        if self.prefix_enabled:
                            self._register_prefix(i, req)
                        if req.prompt_pos < len(toks):
                            continue    # still prefilling; logits unused
                    # Prompt just completed (logits are at its last prompt
                    # token) or the slot was decoding: sample either way.
                    self._record(i, req, lg[i])
                return

            self._tick_clock()
            if self.paged:
                for i in live:
                    self._slot_len[i] += int(need[i])
            recs: List[TokenRec] = []
            for i in live:
                req = self.slots[i]
                toks = self._feed(req)
                if req.prompt_pos < len(toks):
                    req.prompt_pos += int(need[i])
                    if self.prefix_enabled:
                        self._register_prefix(i, req)
                    if req.prompt_pos < len(toks):
                        continue
                recs.append(self._account_dispatch(i, req))
            self._stream.submit(Ticket(engine=self, t0=t0, warmup=warm,
                                       sampled=sampled, recs=recs,
                                       now=self.now, pass_id=pass_id))

    def _decode_tick(self):
        pass_id = self.ticks
        fed = [i for i, s in enumerate(self.slots) if s is not None]
        with span("serving.decode_tick", pass_id=pass_id,
                  live=len(fed)) as sp:
            if self.paged:
                # A private copy: JAX may read a host array after the call
                # returns (on the CPU backend in place), and the host goes
                # on editing the table for the passes it dispatches next.
                self.state["page_table"] = jnp.asarray(self._table.copy())
            # Host inputs go in as private copies, as the page table does.
            token = (self._dev_next
                     if self.overlap and self._dev_next is not None
                     else self._next_input.copy())
            ov_vals, ov_mask = self._ov_vals.copy(), self._ov_mask.copy()
            temps, uids, idxs = self._samp_arrays()
            self.key, sub = jax.random.split(self.key)
            args = (self.params, self.state, token, ov_vals, ov_mask, sub,
                    temps, uids, idxs)
            t0 = self._perf()
            self.metrics.window_open(t0)
            (logits, sampled, self.state), warm = self._call(
                ("decode",), args)
            sp.set_metadata(fused_layers=self.runner.fused_layers)
            self._dev_next = sampled
            self._ov_vals[:] = 0
            self._ov_mask[:] = False

            recipients = [i for i in fed
                          if self.slots[i].prompt_pos
                          >= len(self._feed(self.slots[i]))]

            if not self.overlap:
                lg = None
                if recipients:
                    lg = self._stream.fetch(logits, np.float32,
                                            pass_id=pass_id)  # host sync
                    done = self._perf()
                    self.metrics.on_device_span(t0, done)
                    if not warm:
                        self.straggler.observe(done - t0)
                self._tick_clock()
                if self.paged:
                    for i in fed:
                        self._slot_len[i] += 1
                for i, req in enumerate(self.slots):
                    if req is None:
                        continue
                    toks = self._feed(req)
                    if req.prompt_pos < len(toks):
                        # legacy prefill-in-decode: feed the next prompt
                        # token
                        self._set_next(i, toks[req.prompt_pos])
                        req.prompt_pos += 1
                        continue
                    self._record(i, req, lg[i])
                return

            self._tick_clock()
            if self.paged:
                for i in fed:
                    self._slot_len[i] += 1
            recs: List[TokenRec] = []
            for i in list(fed):
                req = self.slots[i]
                if req is None:
                    continue
                toks = self._feed(req)
                if req.prompt_pos < len(toks):
                    self._set_next(i, toks[req.prompt_pos])
                    req.prompt_pos += 1
                    continue
                recs.append(self._account_dispatch(i, req))
            self._stream.submit(Ticket(engine=self, t0=t0, warmup=warm,
                                       sampled=sampled, recs=recs,
                                       now=self.now, pass_id=pass_id))

    # -- open-loop API ----------------------------------------------------
    def poll(self) -> List[Request]:
        """One arrival-driven engine round: sync the clock, admit every
        arrived request the policy picks, run one ``step()``.  Returns the
        requests that FINISHED during this poll (possibly empty) plus any
        requests finalized OUTSIDE a step since the last poll: shed
        submissions (``req.shed`` with a ``retry_after`` hint) and queued
        requests whose deadline passed during an admission pass.  With the
        simulated clock an idle engine jumps straight to the next arrival;
        with a real clock it returns immediately and the caller re-polls."""
        if self._clock is not None:
            self.now = self._clock()
        out = self._returned
        self._returned = []
        out.extend(self._drain_delivered())
        self._admit_arrived()
        if all(s is None for s in self.slots):
            if self._stream.pending():
                # Overlap: everything dispatched, nothing left to feed —
                # wait for in-flight deliveries (they may finish requests
                # or fire callbacks that submit new ones).
                self._stream.sync()
                out.extend(self._drain_delivered())
            self.metrics.window_close(self._perf())
            nxt = self.scheduler.next_arrival()
            if nxt is None:
                return out                  # fully drained
            if self._clock is not None:
                # Real time hasn't caught up to the next arrival: nap
                # (capped) instead of letting drain() busy-spin a core
                # through the inter-arrival gap.  Re-sync the clock after
                # the nap — otherwise the next admission pass stamps
                # queue-delay against a ``now`` from before the sleep.
                if nxt > self.now:
                    time.sleep(min(nxt - self.now, 0.01))
                    self.now = self._clock()
                return out
            self.now = max(self.now, nxt)
            self._admit_arrived()
        self.step()
        return out + list(self._just_finished)

    def drain(self) -> List[Request]:
        """Poll until the queue, every slot, the in-flight stream, and the
        returned buffer are empty; returns finished requests in completion
        order."""
        finished: List[Request] = []
        while (len(self.scheduler)
               or any(s is not None for s in self.slots)
               or self._returned
               or self._stream.pending()
               or self._delivered):
            finished.extend(self.poll())
        return finished

    def run(self, requests: List[Request]) -> List[Request]:
        """Closed-loop compatibility wrapper: serve a static workload to
        completion under the engine's policy (FCFS by default, matching the
        historical behavior bit-for-bit for greedy same-seed workloads).
        Oversized requests are rejected up front (marked done, nothing
        generated) rather than crashing the serve loop mid-flight; SHED
        requests surface through drain()'s polls, not here, so nothing is
        returned twice."""
        finished: List[Request] = []
        for r in requests:
            if not self.submit(r) and not r.shed:
                finished.append(r)
        finished.extend(self.drain())
        return finished
