"""Continuous-batching serving engine over the per-slot decode step.

The decode step (models/*.lm_decode_step) is one fused jitted program taking
per-slot positions, so every batch row advances through its own request
independently. This module adds the request-level machinery a serving
deployment needs, vLLM-style but reduced to its core:

  * slot allocation for a fixed decode batch with **mid-run admission**: a
    slot freed by a finished request is refilled from the queue on the next
    step, its cache region reset (recurrent rwkv/mamba state zeroed; KV rows
    additionally invalidated logically by the per-row validity masks in
    models/attention.py), so batch occupancy stays saturated under a request
    stream instead of draining to one straggler;
  * **paged KV** (``kv="paged"``): attention caches become a pool of
    fixed-size token blocks (serve/kv_pool.py) shared by every slot — memory
    scales with tokens actually resident, not slots x worst-case ``max_seq``,
    and a single long prompt can span blocks a dense layout could never give
    one slot. Admission is reservation-gated: a request the pool cannot
    guarantee is *deferred*, never admitted into a future OOM. The dense
    layout stays as the bit-for-bit reference (parity pinned in
    tests/test_serving_cb.py);
  * **chunked stepping** (``prefill_chunk=C``): each fused step advances
    every active slot by up to C tokens (an inner masked scan — one device
    program, C sub-steps). Prefilling slots chew C prompt tokens per step,
    so time-to-first-token drops ~C× in steps; decoding slots emit up to C
    tokens per step (the host truncates at ``max_new_tokens``), amortizing
    per-step dispatch ~C×. Mid-run admission between steps is untouched,
    and C=1 reproduces the one-token engine exactly — any C is token-exact
    against it because each sub-step IS a one-token step;
  * **token-level stepping** (``step_mode="tokens"``): instead of C uniform
    sub-steps for every slot, each fused step runs ONE variable-composition
    batch of live tokens — prefilling slots contribute ``min(C, remaining
    prompt)`` rows, decoding slots contribute one row each (vLLM-style token
    batching). Step FLOPs scale with scheduled tokens, not ``slots x C``:
    idle slots and past-prompt-end chunk rows cost nothing. Attention-only
    families (every segment kind ``attn_mlp``) only — recurrent segments
    carry per-slot state that cannot flatten, and MoE routes a decode batch
    as one capacity group where padding rows would steal expert slots; the
    server falls back to chunked stepping (recorded in
    ``meshes.fallbacks()``). Token-exact against chunked stepping because
    every scheduled row is the same one-token decode at the same position;
  * **paged-attention kernel** (``attn_impl="pallas"``, paged KV only): the
    block-table-aware Pallas kernel in ``kernels/paged_attn`` walks each
    token's mapped blocks directly instead of gathering the padded
    ``(B, nb*bs)`` K/V view; the gather path stays as the bit-exact
    reference (``attn_impl="gather"``, the default);
  * prefill-as-decode per slot with per-slot stop handling (max_new_tokens /
    max_seq), greedy or temperature sampling restricted to the true
    (unpadded) vocab;
  * one fused device program per step: next-token selection (prompt feed vs
    last sample), decode, sampling, and position advance all trace into a
    single jitted call over device arrays — tokens, per-slot positions, the
    active mask, and (paged) the block tables; the host loop only does
    request bookkeeping on the step's (sampled, emitted) output;
  * mesh-backed serving: ``BatchedServer(mesh=...)`` shards the KV/state
    caches over the ``data`` axis (slots for dense caches, *blocks* for the
    paged pool) and ``model`` axis (heads / features) via
    ``dist.meshes.SERVE_CACHE_RULES``, with the same divisibility-fallback
    bookkeeping ``Engine.sharded_path`` uses;
  * **preemptive scheduling** (serve/scheduler.py): admission is a priority
    queue (lower ``Request.priority`` = more important, FIFO within a
    class) with per-request deadlines (TTFT and end-to-end, measured on the
    server clock from submission). When a higher-priority request is
    blocked — no free slot, or the paged pool cannot cover its reservation
    — the scheduler evicts a victim (lowest priority class, most recently
    admitted): the victim's blocks are ``release()``d and it is requeued
    **carrying its generated tokens**, resuming later by chunked prefill
    over ``prompt + generated``. Under greedy decoding the resume is
    token-exact vs an uncontended run: the re-prefill recomputes exactly
    the KV prefix the evicted cache held, and emission restarts at the end
    of the carried tokens (``tests/test_serve_scheduler.py`` pins this
    across GQA/MLA x dense/paged x chunked/tokens). Deadline misses are
    *cancelled* — blocks freed immediately, status
    ``CANCELLED_DEADLINE`` — so overload sheds load instead of occupying
    slots; every request ends in a terminal status (``FINISHED`` /
    ``CANCELLED_DEADLINE`` / ``REJECTED``);
  * **decode-time pool pressure never raises out of ``run()``**: mid-run
    ``ensure_step`` failures (possible when a fault plan shrinks the pool
    out from under admission's reservations) are routed through the same
    preemption machinery — victims are evicted until the write fits, the
    failing slot itself evicted last;
  * **fault injection** (serve/faults.py): a seeded ``FaultPlan`` applies
    scripted pool shrinkage, forced preemptions, admission stalls, and
    virtual-clock deadline pressure at chosen steps, driving the chaos
    suite (``tests/test_serve_chaos.py``); ``debug_checks=`` (default: on
    under pytest, off in benches) asserts the block-pool invariants after
    every step so corruption fails at the step that caused it;
  * **prefix sharing** (``prefix_cache``, paged + attention-only families):
    fully-written feed blocks register content keys in the pool's
    ``PrefixIndex``; a new request whose prompt starts with a resident
    chain maps those blocks *shared* (refcount bump, no copy, no free-list
    pop) and starts prefill at its first divergent position — the final
    prompt position is always recomputed, so emission and sampling run the
    unchanged step path. Writes into a still-shared block COW-split it
    first (``cow_step`` swaps in a private copy; the device rows are
    duplicated by a tiny jitted scatter before the fused step), so sharers
    never observe another request's scatters — token-exact vs the unshared
    pool (pinned in ``tests/test_serve_prefix.py`` across GQA/MLA x
    gather/pallas x chunked/tokens, including preempt-then-resume).
    Ineligible shapes (SWA ring pools — ring rows wrap, so a sharer would
    be missing skipped window writes — and families with per-slot
    recurrent/MoE state, whose skipped positions carry state KV blocks
    don't) fall back with a recorded fallback;
  * **multi-tenant fairness** (``scheduler="wdrr"`` + ``tenant_weights``):
    weighted deficit round robin over ``Request.tenant`` queues inside
    each priority class (serve/scheduler.py) — tenants get admission
    shares proportional to weight under saturation, with per-tenant
    rollups in ``metrics.per_tenant``;
  * a ``serve.metrics.ServeMetrics`` rollup (occupancy %, admitted/finished/
    deferrals, tok/s, TTFT, prefill vs decode tokens, blocks-in-use %,
    prefix hits/skipped prefill tokens, KV bytes written (COW splits
    included), preemptions/recompute/deadline-miss counters and
    per-priority / per-tenant rollups), so benchmarks and tests assert
    saturation and robustness.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.dist import meshes
from repro.models import model_zoo
from repro.models.config import ModelConfig
from repro.models.transformer import segments_for
from repro.serve import scheduler as sched
from repro.serve.kv_pool import PagedKV, PoolExhausted, prefix_keys
from repro.serve.metrics import ServeMetrics

# cache leaves that stay per-slot (B at axis 1 of the layer-stacked leaf)
# even under paged KV: recurrent state is O(1) per slot, not per-token
_PER_SLOT_KEYS = frozenset({"wkv", "shift_t", "shift_c", "ssm", "conv"})


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # fused steps consumed since the LAST admission; one step advances a slot
    # by up to ``prefill_chunk`` tokens, so TTFT in steps is
    # ceil(prompt_len / chunk) for a never-preempted request
    steps: int = 0
    submit_s: float | None = None  # server clock at submission (queue entry)
    admit_s: float | None = None  # server clock at FIRST admission into a slot
    # wall seconds from submission to first generated token — includes queue
    # wait, which is exactly what drain-then-refill's waves inflate
    ttft_s: float | None = None
    # scheduling: lower priority value = more important (0 = interactive
    # class); deadlines are wall budgets from submission on the server clock
    # (deadline_ttft_s until the first token, deadline_s end to end) — a miss
    # cancels the request and frees its blocks immediately
    priority: int = 1
    deadline_ttft_s: float | None = None
    deadline_s: float | None = None
    # lifecycle: QUEUED -> RUNNING -> FINISHED, with PREEMPTED (requeued,
    # will resume), CANCELLED_DEADLINE, REJECTED (see serve/scheduler.py)
    status: str = sched.QUEUED
    preemptions: int = 0  # times evicted; resume re-prefills prompt+out
    seq: int = -1  # submission order (scheduler-assigned; kept across resumes)
    admit_seq: int = -1  # admission order — drives victim selection
    submit_step: int | None = None  # server step counter at submission
    # tenant id for weighted fairness (scheduler="wdrr") and the per-tenant
    # metrics rollup; the default folds everything into one tenant
    tenant: int | str = 0
    # prompt positions the prefix cache served from resident shared blocks
    # at the LAST admission (prefill starts at this offset)
    prefix_shared_tokens: int = 0


def _leaf_key(path) -> str | None:
    k = path[-1] if path else None
    return getattr(k, "key", None)


def _cow_copy_blocks(cache, src, dst):
    """Duplicate block rows ``src -> dst`` across the block-pool cache
    leaves (copy-on-write split: the writer got a private physical block and
    the shared original must be byte-identical in it before the next step's
    scatter). Leaves are layer-stacked ``(L, num_blocks, block_size, ...)``
    — blocks live on axis 1. Padding entries carry ``dst == num_blocks``
    (out of range: jax drops OOB scatter updates, same gating the paged
    write path uses), so one compiled program serves any pad bucket."""

    def one(path, c):
        if _leaf_key(path) in _PER_SLOT_KEYS:
            return c
        return c.at[:, dst].set(c[:, src])

    return jax.tree_util.tree_map_with_path(one, cache)


def _cache_row_bytes(cache) -> int:
    """Bytes of cache one written position costs, summed over every
    non-per-slot leaf and all layers: leaves are layer-stacked ``(L, B_or_NB,
    S_or_bs, tail...)``, so one row is ``L * prod(tail)`` elements per leaf.
    Recurrent per-slot leaves are O(1) state updates, not per-token KV —
    excluded (a pure-recurrent family reports 0)."""
    total = 0
    for path, c in jax.tree_util.tree_leaves_with_path(cache):
        if _leaf_key(path) in _PER_SLOT_KEYS or c.ndim < 3:
            continue
        total += int(c.shape[0]) * int(np.prod(c.shape[3:], dtype=np.int64)) \
            * c.dtype.itemsize
    return total


def _reset_slot_rows(cache, idx, paged: bool):
    """Zero the batch rows listed in ``idx`` (padded with out-of-range
    sentinels, which the scatter drops) across the per-slot cache leaves.
    Leaves are layer-stacked (L, B, ...): rows live on axis 1; with donation
    this is an in-place row write, not a whole-cache rebuild. Under paged KV
    only the recurrent per-slot leaves are touched — block-pool leaves have
    no slot rows; recycled blocks are invalidated by the validity masks."""

    def zero(path, c):
        if paged and _leaf_key(path) not in _PER_SLOT_KEYS:
            return c
        return c.at[:, idx].set(jnp.zeros((), c.dtype))

    return jax.tree_util.tree_map_with_path(zero, cache)


class BatchedServer:
    """Fixed-slot continuous-batching server; see module docstring.

    ``admission`` picks the scheduling discipline: ``"continuous"`` (default)
    refills freed slots mid-run; ``"drain"`` is the static-batch ablation that
    only admits when every slot is empty (drain-then-refill) — the baseline
    ``benchmarks/bench_serve.py`` measures continuous batching against.

    ``kv`` picks the cache layout: ``"dense"`` (reference; every slot owns a
    ``max_seq`` row) or ``"paged"`` (block pool, ``block_size`` tokens per
    block, ``kv_blocks`` total — default dense-equivalent capacity). Models
    with no attention cache (pure recurrent) silently serve dense; the
    effective layout is ``server.kv_mode``. ``prefill_chunk`` sets the
    chunked-prefill width C (1 = classic one-token prefill).

    ``step_mode`` picks the fused-step composition: ``"chunked"`` (default,
    the reference) runs C uniform sub-steps across all slots;  ``"tokens"``
    flattens live prefill chunks and decode tokens into one variable-size
    token batch per step (attention-only families; other families fall back
    to chunked, recorded in ``meshes.fallbacks()``). The effective mode is
    ``server.step_mode``.

    ``attn_impl`` picks the paged decode-attention backend: ``"gather"``
    (default, bit-exact reference) or ``"pallas"`` (block-table kernel;
    requires ``kv="paged"``, otherwise falls back to gather with a recorded
    fallback). The effective backend is ``server.attn_impl``.

    ``scheduler`` picks the admission policy: ``"priority"`` (default —
    priority classes, deadlines, and preemption; with uniform priorities and
    no deadlines it behaves exactly like FIFO) or ``"fifo"`` (the
    pre-scheduler ablation: submission order, no preemption). ``preemption``
    overrides the policy default (priority: on, fifo: off).

    ``debug_checks`` asserts the paged-pool allocator invariants after every
    step (``KVBlockPool.check``); default None resolves to the
    ``REPRO_SERVE_DEBUG_CHECKS`` env var ("0"/"1") or, absent that, to
    "running under pytest" — on in tests/CI, off in benches.

    ``fault_plan`` installs a ``serve.faults.FaultPlan`` applied at the top
    of each step; a plan carrying a ``VirtualClock`` also becomes the server
    ``clock`` (the callable behind every timestamp and deadline — defaults
    to ``time.perf_counter``).
    """

    def __init__(self, cfg: ModelConfig, params, batch_slots: int, max_seq: int,
                 temperature: float = 0.0, seed: int = 0, mesh=None,
                 param_specs=None, admission: str = "continuous",
                 kv: str = "dense", block_size: int = 16,
                 kv_blocks: int | None = None, prefill_chunk: int = 1,
                 step_mode: str = "chunked", attn_impl: str = "gather",
                 scheduler: str = "priority", preemption: bool | None = None,
                 debug_checks: bool | None = None, fault_plan=None,
                 clock=None, prefix_cache: bool | None = None,
                 tenant_weights: dict | None = None):
        if cfg.family == "encdec":
            raise ValueError(
                "BatchedServer serves decoder-only families; enc-dec decode "
                "needs per-request encoder output (see examples/ seamless path)"
            )
        if admission not in ("continuous", "drain"):
            raise ValueError(f"admission must be continuous|drain, got {admission!r}")
        if kv not in ("dense", "paged"):
            raise ValueError(f"kv must be dense|paged, got {kv!r}")
        if step_mode not in ("chunked", "tokens"):
            raise ValueError(f"step_mode must be chunked|tokens, got {step_mode!r}")
        if attn_impl not in ("gather", "pallas"):
            raise ValueError(f"attn_impl must be gather|pallas, got {attn_impl!r}")
        if scheduler not in sched.POLICIES:
            raise ValueError(
                f"scheduler must be one of {sched.POLICIES}, got {scheduler!r}"
            )
        # explicit >= 1 check, not truthiness: a falsy 0 must fail loudly
        # here instead of slipping through downstream `or` defaults
        if max_seq < 1:
            raise ValueError(f"max_seq must be >= 1, got {max_seq}")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if kv == "paged" and block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_seq = max_seq
        self.temperature = float(temperature)
        self.admission = admission
        self.prefill_chunk = int(prefill_chunk)
        # pure-recurrent models have no per-token cache to page
        self.kv_mode = kv if not (kv == "paged" and cfg.family == "ssm") else "dense"
        # prefix sharing needs (a) a paged pool without a SWA ring (ring rows
        # wrap: a sharer skipping prefill would be missing the skipped
        # positions' window writes) and (b) attention-only segments (skipped
        # positions carry recurrent/MoE-capacity state that blocks don't
        # hold). None = on wherever eligible; an explicit True on an
        # ineligible shape records a fallback instead of serving wrong KV.
        kinds = {s.kind for s in segments_for(cfg)}
        prefix_ok = (self.kv_mode == "paged" and kinds == {"attn_mlp"})
        if prefix_cache is None:
            prefix_cache = prefix_ok
        elif prefix_cache and not prefix_ok:
            meshes.record_fallback(
                "serve_prefix", "prefix_cache", 0,
                f"prefix sharing needs paged KV over attention-only segments "
                f"(kv={self.kv_mode!r}, kinds={sorted(kinds)}); serving "
                "unshared",
            )
            prefix_cache = False
        self.prefix_cache = bool(prefix_cache)
        if self.kv_mode == "paged":
            self._paged = PagedKV.for_model(cfg, batch_slots, max_seq,
                                            block_size, kv_blocks,
                                            prefix_cache=self.prefix_cache)
            ring = self._paged.ring
            self.cache = model_zoo.make_paged_cache(
                cfg, batch_slots, self._paged.pool.num_blocks, block_size,
                ring_num_blocks=ring.num_blocks if ring is not None else 0,
                ring_width=self._paged.ring_width,
            )
        else:
            self._paged = None
            self.cache = model_zoo.make_cache(cfg, batch_slots, max_seq)
        if attn_impl == "pallas" and self._paged is None:
            meshes.record_fallback(
                "serve_attn", "impl", 0,
                "attn_impl='pallas' needs kv='paged' (the kernel walks block "
                "tables); dense layout falls back to gather attention",
            )
            attn_impl = "gather"
        self.attn_impl = attn_impl
        if step_mode == "tokens":
            kinds = {s.kind for s in segments_for(cfg)}
            if kinds != {"attn_mlp"}:
                meshes.record_fallback(
                    "serve_step", "token_batch", 0,
                    f"token-level stepping needs attention-only segments, got "
                    f"{sorted(kinds)}: recurrent state is per-slot and MoE "
                    "capacity groups see padding rows; falling back to "
                    "chunked stepping",
                )
                step_mode = "chunked"
        self.step_mode = step_mode
        self.key = jax.random.PRNGKey(seed)
        self.active: list[Request | None] = [None] * batch_slots
        # the admission queue IS the scheduler (len/bool/iter work like the
        # old list); `finished` holds every TERMINAL request — FINISHED and
        # CANCELLED_DEADLINE both land here so run() drains
        self.scheduler = scheduler
        self.preemption = (scheduler in ("priority", "wdrr")) \
            if preemption is None else bool(preemption)
        self.queue = sched.AdmissionScheduler(scheduler,
                                              tenant_weights=tenant_weights)
        self.finished: list[Request] = []
        # rids of requests in an OPEN deferral episode: blocked at the head
        # at least once since they last entered a slot. One deferral
        # *episode* per request per blocked period — the episode ends on
        # admission or cancellation, NOT when another head takes over the
        # blockage (two heads alternating under preemption is two episodes,
        # not one per alternation; pinned in tests/test_serve_scheduler.py)
        self._deferring: set[int] = set()
        # fault injection + timekeeping: the clock is THE time source for
        # submit/TTFT/deadline/wall accounting, so a fault plan's
        # VirtualClock makes deadline pressure deterministic
        self._faults = fault_plan
        self._admit_stall = 0  # steps admission stays stalled (fault)
        self._step_no = 0  # monotonic fused-step counter (fault schedule key)
        self._admit_seq = 0  # admission counter behind Request.admit_seq
        if clock is None and fault_plan is not None \
                and getattr(fault_plan, "clock", None) is not None:
            clock = fault_plan.clock
        self._clock = clock if clock is not None else time.perf_counter
        if debug_checks is None:
            env = os.environ.get("REPRO_SERVE_DEBUG_CHECKS")
            if env in ("0", "1"):
                debug_checks = env == "1"
            else:
                # on under pytest (CI test jobs inherit it), off in benches
                debug_checks = "PYTEST_CURRENT_TEST" in os.environ
        self.debug_checks = bool(debug_checks)
        # wall seconds the latest step spent inside _admit (the admission
        # portion of that step's wall_s)
        self.last_admit_s = 0.0
        self.metrics = ServeMetrics(slots=batch_slots)
        if self._paged is not None:
            self.metrics.kv_blocks_total = self._paged.pool.num_blocks

        # per-slot device-program state (held as host numpy, shipped to the
        # device as tiny arrays each step; the cache stays resident on device)
        self._positions = np.zeros(batch_slots, np.int32)
        self._prompt_buf = np.zeros((batch_slots, max_seq), np.int32)
        self._prompt_len = np.zeros(batch_slots, np.int32)
        self._last_tok = np.zeros(batch_slots, np.int32)
        self._active_mask = np.zeros(batch_slots, bool)
        # the prompt buffer is the one per-slot array that is not O(slots):
        # keep its device copy resident and refresh it only on admission
        self._prompt_buf_dev = jnp.asarray(self._prompt_buf)
        # block tables ship as tiny int32 arrays, refreshed only when the
        # allocator maps or releases blocks (dense mode passes empty dummies)
        self._no_table = jnp.zeros((0,), jnp.int32)
        self._table_dev = self._ring_dev = self._no_table
        self._tables_fresh = False
        # prefix-sharing bookkeeping: each occupied slot's feed-block content
        # keys and the watermark of blocks already registered in the index
        self._slot_keys: list[list | None] = [None] * batch_slots
        self._reg_upto = np.zeros(batch_slots, np.int32)

        self.mesh = mesh
        self.last_sharded_path: tuple | None = None
        if mesh is not None:
            self.last_sharded_path = self.sharded_path(mesh)
            with meshes.use_mesh(mesh):
                cache_sh = meshes.tree_shardings(
                    model_zoo.cache_specs(self.cache,
                                          paged=self._paged is not None),
                    self.cache, mesh,
                    rules=(meshes.SERVE_KERNEL_CACHE_RULES
                           if self.attn_impl == "pallas"
                           else meshes.SERVE_CACHE_RULES),
                )
                self.cache = jax.device_put(self.cache, cache_sh)
                if param_specs is not None:
                    self.params = jax.device_put(
                        params, meshes.tree_shardings(param_specs, params, mesh)
                    )
                else:
                    self.params = jax.device_put(params, meshes.replicated(mesh))

        # donate the cache through both programs: the old cache is dead the
        # moment the step/reset returns, and without donation XLA keeps input
        # + output cache buffers live — a 2x peak that matters at multi-GB
        # KV-cache scale
        self._step_fn = jax.jit(self._build_step(), donate_argnums=(1,))
        self._token_step_fn = (
            jax.jit(self._build_token_step(), donate_argnums=(1,))
            if self.step_mode == "tokens" else None
        )
        self._reset_fn = jax.jit(
            functools.partial(_reset_slot_rows, paged=self._paged is not None),
            donate_argnums=(0,),
        )
        self._cow_fn = (jax.jit(_cow_copy_blocks, donate_argnums=(0,))
                        if self.prefix_cache else None)
        # bytes one written cache row costs across every non-per-slot leaf
        # (all layers; paged: full + ring regions both scatter per position)
        # — the unit behind metrics.kv_bytes_written
        self._kv_row_bytes = _cache_row_bytes(self.cache)

    # -- sharding ------------------------------------------------------------
    def sharded_path(self, mesh) -> tuple:
        """Decide how the serving caches shard on ``mesh``: returns
        ``("gspmd", data_axes, model_axis)``. The cache batch (slot) dim — or
        the block-pool dim under paged KV — goes over the data axes when it
        divides them; head/feature dims go over the model axis when the
        family has a head-partitioned cache tensor that divides it.
        Divisibility drops are recorded in ``meshes.fallbacks()`` — the same
        bookkeeping ``Engine.sharded_path`` uses — and the dropped dim stays
        replicated (GSPMD still shards whatever per-tensor dims do resolve).
        """
        data = meshes.mesh_data_axes(mesh)
        n_data = meshes.mesh_axis_size(mesh, *data) if data else 1
        if self._paged is not None:
            nb = self._paged.pool.num_blocks
            if data and self.attn_impl == "pallas":
                meshes.record_fallback(
                    "serve_cache", "kv_blocks", 1,
                    "paged-attention kernel walks the whole block pool "
                    "through its scalar-prefetched table (any token may map "
                    "any physical block); block pool stays replicated",
                )
                data = ()
            elif data and nb % n_data != 0:
                meshes.record_fallback(
                    "serve_cache", "kv_blocks", 1,
                    f"paged pool of {nb} blocks not divisible by data axes "
                    f"{data}={n_data}; block pool stays replicated",
                )
                data = ()
        elif data and self.slots % n_data != 0:
            meshes.record_fallback(
                "serve_cache", "batch", 0,
                f"batch slots {self.slots} not divisible by data axes "
                f"{data}={n_data}; cache slots stay replicated",
            )
            data = ()
        model_axis = None
        m_size = meshes.mesh_axis_size(mesh, "model")
        if m_size > 1:
            heads = self._cache_head_dim()
            if heads is None:
                meshes.record_fallback(
                    "serve_cache", "kv_heads", 2,
                    "no head-partitioned cache tensor in this family "
                    "(latent/recurrent cache); model axis shards params only",
                )
            elif heads % m_size != 0:
                meshes.record_fallback(
                    "serve_cache", "kv_heads", 2,
                    f"cache head dim {heads} not divisible by mesh axis "
                    f"'model'={m_size}; cache heads stay replicated",
                )
            else:
                model_axis = "model"
        return "gspmd", data, model_axis

    def _cache_head_dim(self) -> int | None:
        """Size of the cache dim the model axis would partition, if any."""
        cfg = self.cfg
        if cfg.family == "ssm":  # rwkv wkv state: (B, heads, hd, hd)
            return cfg.d_model // cfg.rwkv_head_size
        if cfg.attn_kind == "mla":  # latent cache has no head dim
            return None
        return cfg.n_kv_heads

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request):
        try:
            if not req.prompt:
                raise ValueError(f"request {req.rid}: empty prompt")
            if req.max_new_tokens < 1:
                raise ValueError(
                    f"request {req.rid}: max_new_tokens must be >= 1, "
                    f"got {req.max_new_tokens}"
                )
            if len(req.prompt) >= self.max_seq:
                raise ValueError(
                    f"request {req.rid}: prompt len {len(req.prompt)} >= "
                    f"max_seq {self.max_seq}"
                )
            for name in ("deadline_ttft_s", "deadline_s"):
                d = getattr(req, name)
                if d is not None and d <= 0:
                    raise ValueError(
                        f"request {req.rid}: {name} must be > 0, got {d}"
                    )
            if self._paged is not None:
                full, _ = self._paged.required(
                    len(req.prompt), req.max_new_tokens, self.prefill_chunk,
                    token_step=self.step_mode == "tokens",
                )
                if full > self._paged.pool.num_blocks:
                    # deferral only makes sense when finish-time releases can
                    # ever satisfy it; an impossible request must fail loudly
                    raise ValueError(
                        f"request {req.rid}: needs {full} KV blocks but the "
                        f"pool only has {self._paged.pool.num_blocks}"
                    )
        except ValueError:
            # fail loudly AND leave the corpse inspectable: callers that
            # catch the raise still see a terminal status on the request
            req.status = sched.REJECTED
            self.metrics.rejected += 1
            raise
        req.submit_s = self._clock()
        req.submit_step = self._step_no
        req.status = sched.QUEUED
        self.queue.push(req)

    def _free_slot(self) -> int | None:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return None

    def _head_admissible(self, head: Request) -> bool:
        """Can the paged pool cover ``head``'s worst-case reservation right
        now? Resumes reserve for ``prompt + carried output`` — the same
        positions the original reservation covered. With the prefix cache
        the reservation is net of resident shared blocks (never more than
        the unshared demand), re-planned on every check: evictions between
        checks can free shared blocks out of the index."""
        if self._paged is None:
            return True
        feed_len = len(head.prompt) + len(head.out)
        max_new = head.max_new_tokens - len(head.out)
        token_step = self.step_mode == "tokens"
        if self.prefix_cache:
            return self._paged.can_admit_shared(
                self._feed_keys(head), feed_len, max_new,
                self.prefill_chunk, token_step=token_step,
            )
        return self._paged.can_admit(feed_len, max_new, self.prefill_chunk,
                                     token_step=token_step)

    def _feed_keys(self, req: Request) -> list[tuple]:
        """Content keys of ``req``'s full feed blocks (prompt + carried
        output — a resume shares whatever prefix of its recompute is still
        resident, its own pre-eviction blocks included)."""
        return prefix_keys(req.prompt + req.out, self._paged.block_size)

    def _admit_into(self, slot: int, req: Request, now: float):
        """Bind ``req`` to ``slot``. A resumed (preempted) request feeds
        ``prompt + out`` as its prompt: the chunked re-prefill recomputes
        exactly the KV prefix its evicted cache held, and the engine's
        emit boundary (``positions + 1 >= prompt_len``) restarts emission
        right after the carried tokens — token-exact under greedy."""
        feed = req.prompt + req.out
        plen = len(feed)
        start = 0
        if self._paged is not None:
            max_new = req.max_new_tokens - len(req.out)
            token_step = self.step_mode == "tokens"
            if self.prefix_cache:
                keys = self._feed_keys(req)
                start, n_shared = self._paged.admit_shared(
                    slot, keys, plen, max_new, self.prefill_chunk,
                    token_step=token_step,
                )
                self._slot_keys[slot] = keys
                self._reg_upto[slot] = n_shared
                self._tables_fresh = False  # shared blocks mapped host-side
                if n_shared:
                    self.metrics.prefix_hits += 1
                    self.metrics.prefix_tokens += start
                    ten = self.metrics.tenant(req.tenant)
                    ten["prefix_hits"] += 1
                    ten["prefix_tokens"] += start
            else:
                self._paged.admit(slot, plen, max_new, self.prefill_chunk,
                                  token_step=token_step)
        req.prefix_shared_tokens = start
        self.active[slot] = req
        req.steps = 0
        req.status = sched.RUNNING
        req.admit_seq = self._admit_seq
        self._admit_seq += 1
        if req.admit_s is None:
            # first admission only: resumes must not inflate throughput
            # accounting (admitted counts requests, not slot bindings)
            req.admit_s = now
            self.metrics.admitted += 1
            self.metrics.prio(req.priority)["admitted"] += 1
            self.metrics.tenant(req.tenant)["admitted"] += 1
        # prefill starts past the shared prefix; the final prompt position is
        # never shared (plan_shared caps start at plen-1), so the emission
        # boundary (positions + 1 >= prompt_len) is reached by computation
        self._positions[slot] = start
        self._prompt_buf[slot] = 0
        self._prompt_buf[slot, :plen] = feed
        self._prompt_len[slot] = plen
        self._last_tok[slot] = 0
        self._active_mask[slot] = True

    def _preempt(self, slot: int):
        """Evict the request in ``slot``: release its blocks and requeue it
        carrying its generated tokens (it resumes via ``_admit_into``'s
        re-prefill). The recompute-on-resume tax — every cached position is
        recomputed — is recorded in ``metrics.recompute_tokens``."""
        req = self.active[slot]
        if self._paged is not None:
            self._paged.release(slot)
            self._tables_fresh = False
        self._slot_keys[slot] = None
        self.active[slot] = None
        self._active_mask[slot] = False
        req.status = sched.PREEMPTED
        req.preemptions += 1
        self.metrics.preemptions += 1
        self.metrics.prio(req.priority)["preemptions"] += 1
        self.metrics.tenant(req.tenant)["preemptions"] += 1
        self.metrics.recompute_tokens += int(self._positions[slot])
        self.queue.push(req)  # keeps its original seq: front of its class

    def _cancel(self, req: Request, slot: int | None):
        """Deadline miss: cancel ``req`` (terminal), freeing its slot and
        blocks immediately — overload sheds load instead of occupying."""
        if slot is not None:
            if self._paged is not None:
                self._paged.release(slot)
                self._tables_fresh = False
            self._slot_keys[slot] = None
            self.active[slot] = None
            self._active_mask[slot] = False
        req.status = sched.CANCELLED_DEADLINE
        self.finished.append(req)
        self.metrics.deadline_misses += 1
        self.metrics.prio(req.priority)["deadline_misses"] += 1
        self.metrics.tenant(req.tenant)["deadline_misses"] += 1
        self._deferring.discard(req.rid)  # episode over: cancelled

    def _sweep_deadlines(self, now: float):
        """Cancel every queued or running request past a deadline (one
        definition of "missed" for both sides: scheduler.deadline_missed)."""
        for req in self.queue.expired(now):
            self._cancel(req, slot=None)
        for i, req in enumerate(self.active):
            if req is not None and sched.deadline_missed(req, now):
                self._cancel(req, slot=i)

    def _record_first_token(self, req: Request, now: float):
        req.ttft_s = now - req.submit_s
        self.metrics.ttft_s.append(req.ttft_s)
        self.metrics.ttft_steps.append(req.steps)
        rollup = self.metrics.prio(req.priority)
        rollup["ttft_steps"].append(req.steps)
        # e2e steps: fused steps since SUBMISSION, queue wait included — the
        # number preemptive scheduling improves for the interactive class
        e2e = (self._step_no - req.submit_step + 1
               if req.submit_step is not None else req.steps)
        rollup["ttft_e2e_steps"].append(e2e)
        self.metrics.tenant(req.tenant)["ttft_e2e_steps"].append(e2e)

    def _finish(self, req: Request, slot: int):
        req.done = True
        req.status = sched.FINISHED
        self.finished.append(req)
        self.active[slot] = None
        self._active_mask[slot] = False
        self.metrics.finished += 1
        self.metrics.prio(req.priority)["finished"] += 1
        self.metrics.tenant(req.tenant)["finished"] += 1
        self._slot_keys[slot] = None
        if self._paged is not None:
            self._paged.release(slot)  # free-on-finish
            self._tables_fresh = False

    def _admit(self):
        now = self._clock()
        self._sweep_deadlines(now)
        if not self.queue:
            return
        if self.admission == "drain" and any(r is not None for r in self.active):
            return  # static batching: refill only once the batch has drained
        newly = []
        while self.queue:
            head = self.queue.peek()
            free = self._free_slot()
            ok = self._head_admissible(head)
            if free is None or not ok:
                # head is blocked (no slot / pool can't cover it). Preemption
                # may clear the blockage by evicting a STRICTLY lower-priority
                # victim — the strict inequality is the termination argument:
                # heads pop in non-decreasing priority, so nothing admitted in
                # this loop can become a later head's victim.
                victim = (sched.pick_victim(self.active, below=head.priority)
                          if self.preemption and self.admission == "continuous"
                          else None)
                if victim is not None:
                    self._preempt(victim)
                    continue  # retry the head against the freed capacity
                if not ok:
                    # pool-blocked with nobody to evict: defer (head-of-line —
                    # skipping ahead would starve long prompts) until
                    # finish-time releases free capacity. Never admit into a
                    # future OOM. One deferral *episode* per request per
                    # blocked period (a request blocked for ten steps is one
                    # deferred request, not ten) — tracked as a SET of open
                    # episodes, ended only by admission or cancellation:
                    # when two heads alternate under preemption (A blocked,
                    # B blocked, A blocked again), A's episode is still the
                    # same blockage and must not re-count.
                    if head.rid not in self._deferring:
                        self._deferring.add(head.rid)
                        self.metrics.deferrals += 1
                    self.metrics.deferral_steps += 1
                break
            req = self.queue.pop()
            self._deferring.discard(req.rid)  # episode over: admitted
            self._admit_into(free, req, now)
            newly.append(free)
        if newly:
            # reset the freed slots' per-slot cache rows: recurrent state
            # (wkv/ssm/conv/shift) must start from zeros; dense KV rows get
            # zeroed too, belt-and-braces on top of the per-row validity
            # masks (paged block pools skip this — recycled blocks are
            # invalidated by the masks alone). Fixed (slots,) index vector
            # padded with an out-of-range sentinel (scatter drops OOB rows)
            # keeps this a single compiled program that only writes the
            # admitted rows — continuous batching calls it per admission, so
            # it must not touch the whole cache
            idx = np.full(self.slots, self.slots, np.int32)
            idx[: len(newly)] = newly
            self.cache = self._reset_fn(self.cache, jnp.asarray(idx))
            self._prompt_buf_dev = jnp.asarray(self._prompt_buf)

    # -- the fused device step -------------------------------------------------
    def _build_step(self):
        cfg = self.cfg
        decode = model_zoo.decode_fn(cfg)
        temperature = self.temperature
        vocab = cfg.vocab_size
        chunk = self.prefill_chunk
        paged = self._paged
        attn_impl = self.attn_impl
        if paged is not None:
            block_size, ring_width = paged.block_size, paged.ring_width
            max_seq = self.max_seq

        # chunk == 1: every active row runs the (single) sub-step, so the
        # PR-4 semantics hold as-is — inactive rows' dummy writes land at
        # their parked position behind the validity masks and are reset on
        # admission — and skipping the select keeps the donated cache an
        # in-place update. chunk > 1 needs it: an idle row's recurrent
        # state must freeze mid-chunk and a horizon-capped row must not
        # clobber its last KV row, at the cost of a per-sub-step select
        # (the write-gated dense scatter that would remove it is ROADMAP'd).
        gate_idle_rows = chunk > 1

        def select_rows(run, new, old):
            """Keep ``old`` for rows that did not run this sub-step. Cache
            leaves carry the slot dim at axis 1 ((L, B, ...)); paged block
            leaves have no slot rows — their writes were already gated by
            the write-ok sentinel inside the attention scatter."""

            def one(path, n, o):
                if paged is not None and _leaf_key(path) not in _PER_SLOT_KEYS:
                    return n
                m = run.reshape((1, run.shape[0]) + (1,) * (n.ndim - 2))
                return jnp.where(m, n, o)

            return jax.tree_util.tree_map_with_path(one, new, old)

        seq_limit = self.max_seq

        def step(params, cache, positions, prompt_buf, prompt_len, last_tok,
                 active, key, table, ring_table):
            b = positions.shape[0]
            rows = jnp.arange(b)

            # chunked stepping: C masked sub-steps inside the ONE jitted
            # program, each one a full one-token decode for every running
            # slot (prefill feeds the prompt buffer, decode feeds the last
            # sample — every sub-step does useful work for every row). Rows
            # at the max_seq horizon idle with cache/state/position frozen,
            # so C=1 reproduces the one-token engine bit for bit and any C
            # is token-exact against it.
            def substep(carry, _):
                cache, positions, last_tok, key = carry
                run = active & (positions < seq_limit)
                in_prompt = positions < prompt_len
                idx = jnp.clip(positions, 0, prompt_buf.shape[1] - 1)
                tok = jnp.where(in_prompt, prompt_buf[rows, idx], last_tok)
                tok = jnp.where(run, tok, 0).astype(jnp.int32)
                if paged is not None:
                    ctx = {
                        "table": table, "ring_table": ring_table,
                        "write_ok": run, "block_size": block_size,
                        "ring_width": ring_width, "max_seq": max_seq,
                        "impl": attn_impl,
                    }
                    logits, new_cache = decode(params, tok, cache, positions,
                                               paged=ctx)
                else:
                    logits, new_cache = decode(params, tok, cache, positions)
                cache = (select_rows(run, new_cache, cache)
                         if gate_idle_rows else new_cache)
                logits = logits[:, :vocab].astype(jnp.float32)
                if temperature > 0:
                    key, sub = jax.random.split(key)
                    nxt = jax.random.categorical(sub, logits / temperature,
                                                 axis=-1)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                nxt = nxt.astype(jnp.int32)
                # the sample is a real generation once the prompt is consumed
                emit = run & (positions + 1 >= prompt_len)
                positions = jnp.where(run, positions + 1, positions)
                last_tok = jnp.where(run, nxt, last_tok)
                return (cache, positions, last_tok, key), (nxt, emit)

            init = (cache, positions, last_tok, key)
            (cache, positions, last_tok, key), (toks, emits) = jax.lax.scan(
                substep, init, None, length=chunk
            )
            # toks/emits: (C, B) — the host truncates at max_new_tokens
            return cache, positions, last_tok, key, toks, emits

        return step

    def _build_token_step(self):
        """Fused decode over a flattened (T,) token batch. ``tokens``/
        ``slot``/``pos``/``live`` come from the host scheduler
        (``_step_tokens``): ``slot`` maps each row onto its cache slot,
        ``live`` gates padding rows out of cache writes. Returns per-row
        next-token samples; the host reads each slot's last scheduled row.
        Per-slot recurrent gating (``select_rows``) is unnecessary here:
        eligible families are attention-only, and every cache mutation is a
        scatter already gated by ``write_ok``."""
        cfg = self.cfg
        decode = model_zoo.decode_fn(cfg)
        temperature = self.temperature
        vocab = cfg.vocab_size
        paged = self._paged
        attn_impl = self.attn_impl
        if paged is not None:
            block_size, ring_width = paged.block_size, paged.ring_width
            max_seq = self.max_seq

        def step(params, cache, tokens, slot, pos, live, key, table,
                 ring_table):
            tok = jnp.where(live, tokens, 0).astype(jnp.int32)
            if paged is not None:
                ctx = {
                    # per-token tables: row i is token i's slot's table
                    "table": table, "ring_table": ring_table,
                    "write_ok": live, "block_size": block_size,
                    "ring_width": ring_width, "max_seq": max_seq,
                    "impl": attn_impl,
                }
                logits, cache = decode(params, tok, cache, pos, paged=ctx,
                                       slot=slot, write_ok=live)
            else:
                logits, cache = decode(params, tok, cache, pos,
                                       slot=slot, write_ok=live)
            logits = logits[:, :vocab].astype(jnp.float32)
            if temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits / temperature,
                                             axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            return cache, nxt.astype(jnp.int32), key

        return step

    # -- stepping ---------------------------------------------------------------
    @property
    def step_no(self) -> int:
        """Monotonic fused-step count so far — the clock trace replay
        (``serve.faults.replay_trace``) schedules arrivals against."""
        return self._step_no

    def step(self):
        """Apply scheduled faults, admit into free slots (unless stalled),
        then one fused decode step. Wall time (``metrics.wall_s``) covers
        the whole step, admission included; ``last_admit_s`` records the
        admission portion so the split stays assertable."""
        t0 = self._clock()
        with obs.span("serve.admit"):
            if self._faults is not None:
                self._faults.apply(self, self._step_no)
            if self._admit_stall > 0:
                # admission stalled by a fault: deadlines still sweep (a
                # stalled server must still shed load) but nothing enters a
                # slot
                self._admit_stall -= 1
                self._sweep_deadlines(self._clock())
            else:
                self._admit()
            self.last_admit_s = self._clock() - t0
        if self.step_mode == "tokens":
            self._step_tokens(t0)
        else:
            self._step_chunked(t0)
        self._step_no += 1
        if self.debug_checks and self._paged is not None:
            # allocator invariants checked at the step that broke them, not
            # steps later when a recycled block shows up in two tables
            self._paged.check()

    def _ensure_or_preempt(self, slot: int, pos: int, n: int,
                           cow_pairs: list | None = None) -> bool:
        """``ensure_step`` + copy-on-write that never lets ``PoolExhausted``
        escape: mid-run pressure (a fault plan shrinking the pool out from
        under admission's reservations) evicts victims until the write fits,
        the failing slot itself last. Shared blocks in the write range are
        COW-split here — ``cow_pairs`` accumulates the (old, new) splits the
        caller must device-copy before the step (splits that landed before a
        mid-loop eviction stay in the list: copying a row that was since
        freed is harmless, unwritten rows are masked invalid for any later
        owner). Returns True when any table changed (mapping, split OR
        eviction)."""
        changed = False
        while True:
            try:
                changed |= self._paged.ensure_step(slot, pos, n)
                if cow_pairs is not None and self.prefix_cache:
                    before = len(cow_pairs)
                    self._paged.cow_step(slot, pos, n, out=cow_pairs)
                    changed |= len(cow_pairs) > before
                return changed
            except PoolExhausted:
                # a partial mapping/split may have landed before the raise
                changed = True
                victim = sched.pick_victim(self.active, below=None)
                if victim is None or victim == slot:
                    # nobody else to evict: the failing slot yields and
                    # resumes once the pool heals/frees
                    self._preempt(slot)
                    return changed
                self._preempt(victim)

    def _apply_cow(self, pairs: list[tuple[int, int]]):
        """Run the device-side half of the COW splits: copy each old block's
        rows into the new private block before the fused step scatters into
        it. Index vectors pad to 4-entry buckets (src clamps to a real
        block, dst pads out-of-range so the scatter drops it) to bound the
        compiled-shape set."""
        nb = self._paged.pool.num_blocks
        self.metrics.cow_splits += len(pairs)
        self.metrics.kv_bytes_written += (
            len(pairs) * self._paged.block_size * self._kv_row_bytes
        )
        for k in range(0, len(pairs), 4):
            batch = pairs[k:k + 4]
            src = np.zeros(4, np.int32)
            dst = np.full(4, nb, np.int32)
            src[:len(batch)] = [p[0] for p in batch]
            dst[:len(batch)] = [p[1] for p in batch]
            ctx = (meshes.use_mesh(self.mesh) if self.mesh is not None
                   else contextlib.nullcontext())
            with ctx:
                self.cache = self._cow_fn(self.cache, jnp.asarray(src),
                                          jnp.asarray(dst))

    def _register_prefix(self, slot: int):
        """Advance ``slot``'s prefix-index registration watermark: feed
        blocks whose last row the slot's position has passed are fully
        written (shared ones were already valid) and become shareable. Runs
        before any finish-time release — a released block is evicted from
        the index by the refcount-zero hook, never registered dead."""
        keys = self._slot_keys[slot]
        if keys is None:
            return
        upto = min(int(self._positions[slot]) // self._paged.block_size,
                   len(keys))
        if upto > self._reg_upto[slot]:
            self._reg_upto[slot] = self._paged.register_blocks(
                slot, keys, int(self._reg_upto[slot]), upto
            )

    def _step_chunked(self, t0: float):
        """C uniform masked sub-steps across all slots (the reference)."""
        with obs.span("serve.prep"):
            # block allocation counts into wall time too: the paged-only host
            # work (ensure_step + table upload) must count against paged wall
            # time, or the CI-gated paged-vs-dense tok/s ratio flatters paged
            if self._paged is not None:
                # alloc-on-write: map blocks for the rows each slot writes this
                # step (guaranteed to succeed when the pool is unfaulted —
                # admission reserved the worst case; under injected shrinkage
                # _ensure_or_preempt evicts to fit), COW-splitting any block
                # still shared with another slot before the scatter lands
                changed = False
                cow_pairs: list[tuple[int, int]] = []
                for i in range(self.slots):
                    if self.active[i] is None:
                        continue
                    pos = int(self._positions[i])
                    n = min(self.prefill_chunk, self.max_seq - pos)
                    if n > 0:
                        changed |= self._ensure_or_preempt(i, pos, n, cow_pairs)
                if cow_pairs:
                    self._apply_cow(cow_pairs)
                if changed or not self._tables_fresh:
                    tf, tr = self._paged.tables()
                    self._table_dev = jnp.asarray(tf)
                    self._ring_dev = (jnp.asarray(tr) if tr is not None
                                      else self._no_table)
                    self._tables_fresh = True
                self.metrics.kv_blocks_peak = max(
                    self.metrics.kv_blocks_peak, self._paged.pool.blocks_in_use
                )
        old_pos = self._positions.copy()
        ctx = (meshes.use_mesh(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            out = self._step_fn(
                self.params, self.cache,
                jnp.asarray(self._positions), self._prompt_buf_dev,
                jnp.asarray(self._prompt_len), jnp.asarray(self._last_tok),
                jnp.asarray(self._active_mask), self.key,
                self._table_dev, self._ring_dev,
            )
        self.cache, positions, last_tok, self.key, toks, emits = out
        toks = np.asarray(toks)  # (C, B)
        emits = np.asarray(emits)  # sync point: one per step
        # np.array (not asarray): device arrays view as read-only numpy, and
        # _admit writes these in place on admission
        self._positions = np.array(positions)
        self._last_tok = np.array(last_tok)
        with obs.span("serve.emit"):
            now = self._clock()

            n_active = 0
            generated = 0
            for i, req in enumerate(self.active):
                if req is None:
                    continue
                n_active += 1
                req.steps += 1
                plen = int(self._prompt_len[i])
                # prefill vs decode token split: prompt tokens fed this step
                # (chunked stepping feeds up to C), generations counted on emit
                fed = (min(int(self._positions[i]), plen)
                       - min(int(old_pos[i]), plen))
                self.metrics.prompt_tokens += fed
                ten = self.metrics.tenant(req.tenant)
                ten["prompt_tokens"] += fed
                emitted = 0
                for j in range(toks.shape[0]):
                    # truncate at max_new: the device may over-generate up to
                    # C-1 tokens in the final chunk of a request
                    if not emits[j, i] or len(req.out) >= req.max_new_tokens:
                        continue
                    req.out.append(int(toks[j, i]))
                    emitted += 1
                    if req.ttft_s is None:
                        self._record_first_token(req, now)
                generated += emitted
                ten["tokens_generated"] += emitted
                # index the newly completed feed blocks BEFORE any finish-time
                # release: freed blocks must never enter the index
                self._register_prefix(i)
                if (len(req.out) >= req.max_new_tokens
                        or int(self._positions[i]) >= self.max_seq):
                    self._finish(req, i)
            self.metrics.steps += 1
            self.metrics.active_slot_steps += n_active
            self.metrics.tokens_generated += generated
            # chunked honesty: the fused program computes every slot row for all
            # C sub-steps, live or not
            self.metrics.batched_tokens += self.slots * self.prefill_chunk
            # KV traffic: every advanced position scattered one row into each
            # cache region (COW copy bytes were added by _apply_cow)
            self.metrics.kv_bytes_written += (
                int((self._positions - old_pos).sum()) * self._kv_row_bytes
            )
            self.metrics.wall_s += now - t0

    def _step_tokens(self, t0: float):
        """One variable-composition token batch (vLLM-style): prefilling
        slots schedule ``min(C, remaining prompt)`` rows, decoding slots one
        row each, flattened into a single fused decode whose FLOPs scale
        with live tokens. Token-exact against chunked stepping — every
        scheduled row is the same one-token decode at the same position —
        with two differences that cannot change tokens: prompt-overshoot
        rows are never scheduled, and idle slots contribute no rows."""
        with obs.span("serve.prep"):
            chunk = self.prefill_chunk
            work: list[tuple[int, int, int]] = []  # (slot, start_pos, n_rows)
            for i, req in enumerate(self.active):
                if req is None:
                    continue
                p = int(self._positions[i])
                plen = int(self._prompt_len[i])
                n = min(chunk, plen - p) if p < plen else 1
                n = min(n, self.max_seq - p)
                work.append((i, p, n))
            if self._paged is not None:
                # map blocks BEFORE building the flat batch: under injected pool
                # shrinkage _ensure_or_preempt may evict slots, and an evicted
                # slot must not schedule rows this step. COW splits land here
                # too — before the per-token tables are gathered
                cow_pairs: list[tuple[int, int]] = []
                for i, p, n in work:
                    if self.active[i] is not None:
                        self._ensure_or_preempt(i, p, n, cow_pairs)
                if cow_pairs:
                    self._apply_cow(cow_pairs)
                work = [(i, p, n) for i, p, n in work
                        if self.active[i] is not None]
            t_live = sum(n for _, _, n in work)
            if t_live == 0:
                # nothing runnable this step (empty batch); still a step
                self.metrics.steps += 1
                self.metrics.wall_s += self._clock() - t0
                return
            # pad the batch to an 8-token bucket: bounds the set of distinct
            # shapes the jitted step compiles for; padding rows are dead (live
            # False gates their writes, their samples are never read)
            t_pad = max(8, -(-t_live // 8) * 8)
            tokens = np.zeros(t_pad, np.int32)
            slot_ids = np.zeros(t_pad, np.int32)
            pos = np.zeros(t_pad, np.int32)
            live = np.zeros(t_pad, bool)
            last_row: dict[int, int] = {}
            k = 0
            for i, p, n in work:
                plen = int(self._prompt_len[i])
                if p < plen:
                    tokens[k:k + n] = self._prompt_buf[i, p:p + n]
                else:
                    tokens[k] = self._last_tok[i]
                slot_ids[k:k + n] = i
                pos[k:k + n] = np.arange(p, p + n, dtype=np.int32)
                live[k:k + n] = True
                last_row[i] = k + n - 1
                k += n
            if self._paged is not None:
                tf, tr = self._paged.token_tables(slot_ids)
                table_dev = jnp.asarray(tf)
                ring_dev = (jnp.asarray(tr) if tr is not None
                            else self._no_table)
                self.metrics.kv_blocks_peak = max(
                    self.metrics.kv_blocks_peak, self._paged.pool.blocks_in_use
                )
            else:
                table_dev = ring_dev = self._no_table
        ctx = (meshes.use_mesh(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            self.cache, nxt, self.key = self._token_step_fn(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(slot_ids), jnp.asarray(pos), jnp.asarray(live),
                self.key, table_dev, ring_dev,
            )
        nxt = np.asarray(nxt)  # sync point: one per step
        with obs.span("serve.emit"):
            now = self._clock()

            n_active = 0
            generated = 0
            for i, p, n in work:
                req = self.active[i]
                n_active += 1
                req.steps += 1
                plen = int(self._prompt_len[i])
                new_p = p + n
                self._positions[i] = new_p
                fed = min(new_p, plen) - min(p, plen)
                self.metrics.prompt_tokens += fed
                ten = self.metrics.tenant(req.tenant)
                ten["prompt_tokens"] += fed
                if new_p >= plen:
                    # the slot's last scheduled row sits at the final prompt
                    # position or beyond: its sample is a real generation
                    tok = int(nxt[last_row[i]])
                    self._last_tok[i] = tok
                    if len(req.out) < req.max_new_tokens:
                        req.out.append(tok)
                        generated += 1
                        ten["tokens_generated"] += 1
                        if req.ttft_s is None:
                            self._record_first_token(req, now)
                # index the newly completed feed blocks BEFORE any finish-time
                # release: freed blocks must never enter the index
                self._register_prefix(i)
                if (len(req.out) >= req.max_new_tokens
                        or new_p >= self.max_seq):
                    self._finish(req, i)
            self.metrics.steps += 1
            self.metrics.active_slot_steps += n_active
            self.metrics.tokens_generated += generated
            self.metrics.batched_tokens += t_live
            # KV traffic: every live row scattered once into each cache region
            # (COW copy bytes were added by _apply_cow)
            self.metrics.kv_bytes_written += t_live * self._kv_row_bytes
            self.metrics.wall_s += now - t0

    def reset_metrics(self):
        kv_total = self.metrics.kv_blocks_total
        self.metrics = ServeMetrics(slots=self.slots, kv_blocks_total=kv_total)

    def run(self, max_steps: int | None = None) -> list[Request]:
        """Step until queue and slots drain (or ``max_steps``); returns ALL
        terminal requests so far (``FINISHED`` and ``CANCELLED_DEADLINE``
        both land in ``finished``), in deterministic ``rid`` order."""
        steps = 0
        while (self.queue or any(r is not None for r in self.active)) and (
            max_steps is None or steps < max_steps
        ):
            self.step()
            steps += 1
        return sorted(self.finished, key=lambda r: r.rid)


def generate_greedy(cfg: ModelConfig, params, prompts: list[list[int]],
                    max_new_tokens: int, max_seq: int | None = None):
    """Convenience: run a batch of prompts to completion, return token lists
    (rid order == prompt order, straight from ``run``)."""
    # `is None`, not `or`: max_seq=0 must reach BatchedServer's >= 1 check
    # as the caller's value, not silently become a derived default
    if max_seq is None:
        max_seq = max(len(p) for p in prompts) + max_new_tokens + 1
    server = BatchedServer(cfg, params, batch_slots=len(prompts), max_seq=max_seq)
    for i, p in enumerate(prompts):
        server.submit(Request(rid=i, prompt=list(p), max_new_tokens=max_new_tokens))
    return [r.out for r in server.run()]


def score_tokens(cfg: ModelConfig, params, prompts: list[list[int]],
                 max_new_tokens: int, batch_slots: int | None = None,
                 max_seq: int | None = None, **server_kwargs):
    """Batch-scoring session for the db/ PREDICT path: run all prompts to
    completion on a short-lived server and return ``(outputs, metrics)``.

    Outputs are token lists in prompt order; ``metrics`` is the session's
    ``ServeMetrics`` (None when there were no prompts — e.g. a WHERE clause
    filtered every row, so nothing ever reaches the server). Unlike
    ``generate_greedy`` the slot count is capped, so a million-row scoring
    query doesn't try to allocate a million slots: continuous batching
    refills slots as prompts finish.
    """
    if not prompts:
        return [], None
    if max_seq is None:
        max_seq = max(len(p) for p in prompts) + max_new_tokens + 1
    if batch_slots is None:
        batch_slots = min(len(prompts), 8)
    server = BatchedServer(
        cfg, params, batch_slots=batch_slots, max_seq=max_seq, **server_kwargs
    )
    for i, p in enumerate(prompts):
        server.submit(Request(rid=i, prompt=list(p), max_new_tokens=max_new_tokens))
    outs = [r.out for r in server.run()]
    return outs, server.metrics
