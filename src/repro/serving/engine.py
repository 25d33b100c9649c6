"""Overlapped serving engine: host-plan / device-step split with
dispatch-ahead decode and continuous chunked-prefill batching.

Three-part architecture (see also serving/scheduler.py):

  1. ``Scheduler`` — host-pure admission, preemption, finish detection,
     and page planning.  ``plan_tick()`` emits a ``TickPlan`` computed
     entirely from host state: which requests admit (and which COW pages
     fork), one prompt chunk per PREFILLING slot, one decode row per
     DECODING slot, plus the per-slot sampling-parameter / rng-key
     arrays.

  2. the fused device step — per-layer ``backend.paged_decode`` dispatch
     + paged cache write + vectorized keyed sampling run inside ONE jit
     per tick, so the sampled token ids (one ``(B,)`` int32 array) are
     the only host<->device readback of a decode tick.  The step's input
     tokens come from the ON-DEVICE token buffer of the previous tick
     (double-buffered), merged with this tick's prefill first-token
     samples — never from a host round-trip.

  3. the loop — ``mode="sync"`` reads each tick's tokens immediately
     (plan -> dispatch -> read); ``mode="overlap"`` dispatches tick
     ``t+1`` from the not-yet-read token buffer of tick ``t``, then
     reads tick ``t`` while ``t+1`` executes, overlapping host
     scheduling/bookkeeping with the device forward.  Host visibility of
     token VALUES is deferred one tick; everything value-independent
     (positions, page budgets, max_new finishes) is planned exactly as
     in sync mode, so the two modes are token-for-token identical (same
     per-request rng: sampling is keyed by ``(seed, rid, index)``).
     A stop-token finish is value-dependent, so the overlapped loop runs
     at most one extra "zombie" tick for that slot — its writes land in
     pages the slot still owns and its sampled token is discarded at
     ingest, never surfaced.

Continuous batching: with ``prefill_slice=N`` a joining request prefills
in N-token (page-sized) chunks across ticks while existing slots keep
decoding, instead of a stop-the-world whole-prompt prefill
(``prefill_slice=None``, the default, preserves the classic regime).

ONE cache regime: every config serves from the paged KV cache
(serving/kv_cache.py) with backend-polymorphic page layouts, COW prefix
sharing, and LRU prefix retention; see that module.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Iterator, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec

from repro.launch.mesh import make_tp_mesh
from repro.launch.steps import cast_params
from repro.models.transformer import dtype_of
from repro.serving import sampler as S
from repro.serving import sharded
from repro.serving import speculate
from repro.serving.faults import NO_FAULTS, FaultPlan
from repro.serving.kv_cache import PagedKVCache, pages_for
from repro.serving.request import (Request, RequestOutput, RequestState,
                                   SamplingParams)
from repro.serving.scheduler import Admission, Emit, Scheduler, TickPlan

__all__ = ["Request", "SamplingParams", "RequestState", "RequestOutput",
           "Admission", "Scheduler", "ServeEngine"]

log = logging.getLogger("repro.serving.engine")

# Right-pad prompt batches to a multiple of this (bounds jit retraces).
PREFILL_BUCKET = 16


def fork_page(caches, src, dst):
    """Copy physical page ``src`` -> ``dst`` across every layer's page
    pools (the device half of a COW fork).  Page leaves are recognized by
    the ``*_pages`` naming contract of ``AttentionBackend.page_spec``;
    per-slot leaves (``k_scale``) are untouched."""

    def one(layer, axis):
        out = {}
        for name, arr in layer.items():
            if name.endswith("_pages"):
                sl = (slice(None),) * axis
                out[name] = arr.at[sl + (dst,)].set(arr[sl + (src,)])
            else:
                out[name] = arr
        return out

    if isinstance(caches, tuple):  # mixed layer_backends: per-layer trees
        return tuple(one(layer, 0) for layer in caches)
    return one(caches, 1)  # uniform: leading `layers` axis


def slot_axes(specs):
    """Per pool leaf, the axis indexed by slot (the ``"batch"`` logical
    axis of its ``page_spec``: camformer's ``k_scale``, ``k_means``), or
    None for a leaf indexed by page.  Mirrors the pool tree."""

    def one(layer):
        return {name: axes.index("batch") if "batch" in axes else None
                for name, (_, axes) in layer.items()}

    if isinstance(specs, tuple):  # mixed layer_backends: per-layer trees
        return tuple(one(layer) for layer in specs)
    return one(specs)


def _per_slot(fn, axes, *trees):
    """Apply ``fn(axis, *leaves)`` to the slot-indexed leaves of pool
    trees; every other leaf is taken from the last tree."""
    if isinstance(axes, tuple):
        return tuple(_per_slot(fn, a, *ts) for a, *ts in zip(axes, *trees))
    return {k: trees[-1][k] if ax is None else fn(ax, *(t[k] for t in trees))
            for k, ax in axes.items()}


def packed_prefill(step, max_batch: int, axes):
    """Run a prefill step on a packed batch whose row ``j`` is slot
    ``slots[j]``.

    ``step(params, tokens, lens, offsets, scale_base, *pools, pt, keys,
    index, temps, top_ks, top_ps) -> (first, *pools)`` sees every array
    in row order.  The wrapper takes ``slots`` after ``params``, gathers
    the slot-indexed pool leaves (``axes``, one tree per pool) and the
    per-slot sampling arrays to rows on entry, scatters the leaves back
    on exit, and returns ``first`` in slot order, ``(max_batch,)``.
    Padding rows carry the out-of-range slot ``max_batch``: their
    scatters are dropped, so they touch no slot."""
    n = len(axes)

    def packed(params, slots, tokens, lens, offsets, scale_base, *rest):
        pools, (pt, keys, index, temps, top_ks, top_ps) = rest[:n], rest[n:]

        def rows(a, axis=0):
            return jnp.take(a, slots, axis=axis, mode="clip")

        def unrows(axis, old, new):
            at = (slice(None),) * axis + (slots,)
            return old.at[at].set(new, mode="drop")

        first, *out = step(
            params, tokens, lens, offsets, scale_base,
            *(_per_slot(lambda ax, leaf: rows(leaf, ax), a, p)
              for a, p in zip(axes, pools)),
            pt, rows(keys), index, rows(temps), rows(top_ks), rows(top_ps))
        out = [_per_slot(unrows, a, p, o) for a, p, o in zip(axes, pools, out)]
        first = jnp.zeros((max_batch,), first.dtype).at[slots].set(
            first, mode="drop")
        return (first, *out)

    # the trace names the module after the step (``jit_prefill_step``)
    packed.__name__ = packed.__qualname__ = step.__name__
    return packed


class _InFlight(NamedTuple):
    """Device handles of one dispatched tick, read back one tick later
    (overlap) or immediately (sync)."""

    prefill_tok: Optional[jax.Array]  # (B,) sampled first tokens
    prefill_emit: Tuple[Emit, ...]
    decode_tok: Optional[jax.Array]  # (B,) sampled decode tokens, or the
    #                                   (B, m+1) spec pack (samples ++ n_valid)
    decode_emit: Tuple[Emit, ...]
    spec: bool = False  # decode_tok is a speculative multi-token pack
    tick: int = 0  # the engine's ``ticks`` when this tick was dispatched

    @property
    def empty(self) -> bool:
        return not (self.prefill_emit or self.decode_emit)


class ServeEngine:
    def __init__(self, md, cfg, params, *, max_batch: int = 8,
                 max_len: int = 512, seed: int = 0,
                 page_size: int = 64, n_pages: Optional[int] = None,
                 prefix_sharing: bool = True, mode: str = "overlap",
                 prefill_slice: Optional[int] = None,
                 paged_impl: Optional[str] = None,
                 prefill_impl: Optional[str] = None,
                 spec_k: Optional[int] = None,
                 spec_backend: Optional[str] = None,
                 tp: int = 1, max_queue: Optional[int] = None,
                 faults: Optional[FaultPlan] = None):
        if paged_impl is not None:
            # per-engine override of the decode realization: "fused"
            # (Pallas paged flash/CAM kernels, the default) vs "gather"
            # (the XLA page-gather reference) — rides on cfg so every
            # layer's backend.paged_decode inside the fused device step
            # sees it; ModelConfig validates the value
            cfg = cfg.replace(paged_impl=paged_impl)
        if prefill_impl is not None:
            # per-engine override of the Sq>1 chunk realization
            # (chunked prefill / speculative verify): "auto" follows
            # paged_impl, "fused"/"gather" pin it independently
            cfg = cfg.replace(prefill_impl=prefill_impl)
        if spec_k is not None or spec_backend is not None:
            # per-engine override of the speculative-decoding policy —
            # rides on cfg like paged_impl (ModelConfig validates)
            cfg = cfg.replace(
                spec_k=cfg.spec_k if spec_k is None else spec_k,
                spec_backend=(cfg.spec_backend if spec_backend is None
                              else spec_backend))
        if md.page_specs is None:
            raise ValueError(
                f"{cfg.name!r} (family {cfg.family!r}) does not expose the "
                "paged serving interface (page_specs / prefill_paged / "
                "decode_paged) required by ServeEngine")
        if mode not in ("sync", "overlap"):
            raise ValueError(f"mode must be 'sync' or 'overlap', got {mode!r}")
        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        self.tp = tp
        self.mesh = None
        self._pool_pspecs = self._draft_pspecs = None
        if tp > 1:
            # tensor-parallel sharded serving (serving/sharded.py): the
            # page pools head-shard over a 1-axis tp mesh and every
            # fused step runs shard_map-wrapped.  tp == 1 takes none of
            # these branches — it IS today's single-device engine, same
            # code path (self.mesh stays None; the identity tests assert
            # both).
            if jax.device_count() < tp:
                raise ValueError(
                    f"tp={tp} needs at least {tp} devices, have "
                    f"{jax.device_count()} (CPU: set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={tp})")
            self.mesh = make_tp_mesh(tp)
        self.md, self.cfg = md, cfg
        self.params = cast_params(params, dtype_of(cfg))
        self.max_batch, self.max_len = max_batch, max_len
        self.mode = mode

        # prefill pads prompt batches to prefill_chunk multiples capped
        # at max_len; an indivisible max_len would silently skip the
        # chunked path (and its activation-memory bound) at the cap
        chunk = cfg.prefill_chunk
        if chunk and max_len % chunk != 0:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"prefill_chunk={chunk} for paged serving")
        per_seq = pages_for(max_len, page_size)
        if n_pages is None:
            # Default: full residency (every slot can reach max_len).
            # Smaller pools trade capacity for admission backpressure.
            n_pages = 1 + max_batch * per_seq  # +1: trash page
        # chaos harness: no-op-by-default fault hooks (serving/faults.py),
        # threaded through the allocator and consulted once per tick
        self.faults = NO_FAULTS if faults is None else faults
        self.kv = PagedKVCache(n_pages, page_size, max_batch, per_seq,
                               faults=self.faults)
        self.spec_k = cfg.spec_k
        self.sched = Scheduler(
            self.kv, max_batch=max_batch, max_len=max_len, seed=seed,
            prefix_sharing=prefix_sharing, prefill_slice=prefill_slice,
            prefill_bucket=chunk or PREFILL_BUCKET, spec_k=self.spec_k,
            max_queue=max_queue)
        specs = md.page_specs(cfg, n_pages, page_size, max_batch)
        is_leaf = lambda x: (isinstance(x, tuple) and len(x) == 2
                             and isinstance(x[0], jax.ShapeDtypeStruct))
        zeros = lambda t: jnp.zeros(t[0].shape, t[0].dtype)
        if tp > 1:  # validate head divisibility BEFORE allocating pools
            self._pool_pspecs = sharded.pool_partition_specs(specs, tp)
        self.caches = jax.tree.map(zeros, specs, is_leaf=is_leaf)
        self._slot_axes = (slot_axes(specs),)  # per pool tree, for prefill
        if tp > 1:  # one NamedSharding per page_spec leaf
            self.caches = sharded.shard_pools(self.caches,
                                              self._pool_pspecs, self.mesh)
        # speculative decoding: the drafter stack (same weights, every
        # layer forced to cfg.spec_backend) keeps its OWN page pools on
        # the SAME page table, so admission / COW forks / rollback are
        # planned once for both (serving/speculate.py)
        self.draft_caches = None
        self._draft_cfg = None
        if self.spec_k:
            if md.verify_paged is None:
                raise ValueError(
                    f"{cfg.name!r} does not expose verify_paged "
                    "(all-position logits), required for spec_k > 0")
            self._draft_cfg = speculate.draft_config(cfg)
            dspecs = md.page_specs(self._draft_cfg, n_pages, page_size,
                                   max_batch)
            if tp > 1:
                self._draft_pspecs = sharded.pool_partition_specs(dspecs, tp)
            self.draft_caches = jax.tree.map(zeros, dspecs, is_leaf=is_leaf)
            self._slot_axes += (slot_axes(dspecs),)
            if tp > 1:
                self.draft_caches = sharded.shard_pools(
                    self.draft_caches, self._draft_pspecs, self.mesh)
        self._prefill_jits = {}  # hot -> jitted fused prefill-chunk step
        self._decode_jits = {}  # hot -> jitted fused decode step
        self._spec_jits = {}  # hot -> jitted fused draft+verify step
        if tp == 1:
            self._fork = jax.jit(fork_page)
            self._fork_draft = self._fork
        else:
            # the COW fork copies along the PAGE axis, never the head
            # axis, so the same body runs on the local pool shards; the
            # target and drafter trees need separate wraps only because
            # their spec trees differ (e.g. mixed target, uniform draft)
            R = PartitionSpec()
            self._fork = jax.jit(sharded.shard_step(
                fork_page, self.mesh, (self._pool_pspecs, R, R),
                self._pool_pspecs))
            self._fork_draft = None if self._draft_pspecs is None else (
                jax.jit(sharded.shard_step(
                    fork_page, self.mesh, (self._draft_pspecs, R, R),
                    self._draft_pspecs)))
        # double-buffered on-device token state: the decode step's input
        # tokens are the previous step's output, never a host round-trip
        self._tok_buf = jnp.zeros((max_batch,), jnp.int32)
        self._zero_tok = jnp.zeros((max_batch,), jnp.int32)
        if tp > 1:
            # params and token state are replicated residents of the
            # mesh; only the page pools shard
            self.params = sharded.replicate(self.params, self.mesh)
            self._tok_buf = sharded.replicate(self._tok_buf, self.mesh)
            self._zero_tok = sharded.replicate(self._zero_tok, self.mesh)

        # overlap-mode dispatch-ahead state: the tick whose tokens have
        # been dispatched but not read yet (None in sync mode / idle)
        self._pending: Optional[_InFlight] = None

        # crash containment: emits of the tick currently being read, with
        # settled entries removed, so a readback that dies midway can
        # drop exactly the remainder (see _collect / _fail_tick)
        self._settling: List[Emit] = []

        # instrumentation (benchmarks / the single-readback invariant)
        self.readbacks = 0  # device->host transfers (token id arrays)
        self.blocked_s = 0.0  # host time spent blocked on readbacks
        self.ticks = 0  # decode steps dispatched
        self.tick_errors = 0  # device ticks that failed and were contained
        self.last_error: Optional[str] = None  # most recent contained error
        # host time of the engine thread, integer ns, at the boundaries of
        # the engine.plan / engine.dispatch / engine.ingest trace spans
        self.plan_ns = 0  # Scheduler.plan_tick + take_events
        self.dispatch_ns = 0  # enqueueing a tick's device steps
        self.ingest_ns = 0  # settling read tokens, sink callbacks included

    # ------------------------------------------------------------------
    # scheduler delegation (host state lives on self.sched)
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> int:
        return self.sched.submit(req)

    def cancel(self, rid: int) -> Optional[RequestOutput]:
        return self.sched.cancel(rid)

    def schedule(self) -> List[Admission]:
        """Admission policy alone (no model computation) — see
        ``Scheduler.admit``."""
        return self.sched.admit()

    @property
    def queue(self) -> List[Request]:
        return self.sched.queue

    @property
    def active(self) -> List[Optional[Request]]:
        return self.sched.active

    @property
    def done(self) -> List[Request]:
        return self.sched.done

    @property
    def peak_pages(self) -> int:
        return self.sched.peak_pages

    @property
    def has_work(self) -> bool:
        return self.sched.has_work

    @property
    def has_pending(self) -> bool:
        """True while a dispatched-ahead tick's tokens are still unread
        (``mode="overlap"``); a driver loop must keep polling until both
        ``has_work`` and ``has_pending`` clear."""
        return self._pending is not None

    @property
    def preemptions(self) -> int:
        return self.sched.preemptions

    @property
    def prefill_tokens(self) -> int:
        """Prompt tokens materialized through chunked-prefill steps."""
        return self.sched.prefill_tokens

    @property
    def prefill_ticks(self) -> int:
        """Engine ticks that carried a prefill chunk (TTFT attribution:
        flat chunk counters under a TTFT regression point at the decode
        or queueing path, rising ones at the prefill path)."""
        return self.sched.prefill_ticks

    @property
    def prefill_rows(self) -> int:
        """Rows of the prefill steps dispatched, bucket padding included:
        1 - prefill_tokens / (prefill_rows x prefill_slice) is the share
        of prefill rows x positions computed as padding."""
        return self.sched.prefill_rows

    @property
    def spec_proposed(self) -> int:
        """Draft tokens proposed by the speculative drafter stack."""
        return self.sched.spec_proposed

    @property
    def spec_accepted(self) -> int:
        """Proposed draft tokens the target stack verified and kept."""
        return self.sched.spec_accepted

    @property
    def spec_acceptance(self) -> float:
        """spec_accepted / spec_proposed (0.0 before any speculation)."""
        return self.sched.spec_acceptance

    # ------------------------------------------------------------------
    # the fused device step (everything per tick inside one jit)
    # ------------------------------------------------------------------
    def _shardify(self, fn, n_before, n_after, caches_out_prefix=1):
        """tp > 1: shard_map the fused step over the tp mesh before jit.

        Every step fn takes (``n_before`` replicated args, the target
        pool tree[, the drafter pool tree], ``n_after`` replicated args)
        and returns (``caches_out_prefix`` replicated outputs, then the
        pool tree(s) in the same order).  The pool trees are the ONLY
        sharded operands — the model compute is replicated per device
        except the head-sliced paged attention (models/attention.py),
        whose all_gather restores replication, so replicated out_specs
        for the sampled tokens are exact.
        """
        R = PartitionSpec()
        pools = ((self._pool_pspecs,) if self._draft_pspecs is None
                 else (self._pool_pspecs, self._draft_pspecs))
        in_specs = (R,) * n_before + pools + (R,) * n_after
        out_specs = (R,) * caches_out_prefix + pools
        return sharded.shard_step(fn, self.mesh, in_specs, out_specs)

    def _prefill_jit(self, hot: bool):
        if hot not in self._prefill_jits:
            md, cfg = self.md, self.cfg
            if self.spec_k:
                fn = speculate.build_spec_prefill(md, cfg, self._draft_cfg,
                                                  hot)
            else:

                def prefill_step(params, tokens, lens, offsets, scale_base,
                                 caches, pt, keys, index, temps, top_ks,
                                 top_ps):
                    batch = {"tokens": tokens, "lens": lens,
                             "offsets": offsets, "scale_base": scale_base}
                    logits, caches = md.prefill_paged(params, batch, caches,
                                                      pt, cfg)
                    if hot:
                        first = S.sample_step_keyed(logits, keys, index,
                                                    temps, top_ks, top_ps)
                    else:
                        first = S.greedy(logits)
                    return first, caches

                fn = prefill_step

            # the batch holds only the prefilling slots, so jit compiles
            # the step once per (row bucket, hot)
            fn = packed_prefill(fn, self.max_batch, self._slot_axes)
            if self.tp > 1:
                # (params..scale_base | pools | pt..top_ps) -> (first, pools)
                fn = self._shardify(fn, 6, 6)
            self._prefill_jits[hot] = jax.jit(fn)
        return self._prefill_jits[hot]

    def _spec_jit(self, hot: bool):
        if hot not in self._spec_jits:
            fn = speculate.build_spec_step(
                self.md, self.cfg, self._draft_cfg, self.spec_k + 1, hot)
            if self.tp > 1:
                # (params..n_tok | pools | pt..top_ps)
                #   -> (packed, tok_buf, pools)
                fn = self._shardify(fn, 7, 7, caches_out_prefix=2)
            self._spec_jits[hot] = jax.jit(fn)
        return self._spec_jits[hot]

    def _decode_jit(self, hot: bool):
        if hot not in self._decode_jits:
            md, cfg = self.md, self.cfg

            def decode_step(params, tok_prev, fresh, fresh_mask, live_mask,
                            pos, kv_len, caches, pt, base, keys, index, temps,
                            top_ks, top_ps):
                # merge the double-buffered token state on-device: rows
                # that finished prefill THIS tick take their freshly
                # sampled first token, continuing rows take the previous
                # step's output, inert rows are pinned to 0 (keeps the
                # batch contents identical to the sync loop's)
                tokens = jnp.where(live_mask,
                                   jnp.where(fresh_mask, fresh, tok_prev), 0)
                logits, caches = md.decode_paged(
                    params, tokens, pos, kv_len, caches, pt, cfg, base=base)
                if hot:
                    nxt = S.sample_step_keyed(logits, keys, index, temps,
                                              top_ks, top_ps)
                else:
                    nxt = S.greedy(logits)
                return nxt, caches

            fn = decode_step
            if self.tp > 1:
                # (params..kv_len | pools | pt..top_ps) -> (nxt, pools)
                fn = self._shardify(fn, 7, 7)
            self._decode_jits[hot] = jax.jit(fn)
        return self._decode_jits[hot]

    # ------------------------------------------------------------------
    # host-side spans and counters of one tick
    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def _timed(self, span: str, counter: str, **args):
        """Time a leaf of the engine thread's tick: a profiler span named
        ``span`` (on the device trace's clock while a trace runs) and the
        integer-ns counter ``counter`` (always)."""
        t0 = time.perf_counter_ns()
        try:
            with TraceAnnotation(span, **args):
                yield
        finally:
            setattr(self, counter,
                    getattr(self, counter) + time.perf_counter_ns() - t0)

    def _plan(self, **kw) -> Tuple[TickPlan, List[RequestOutput]]:
        """``Scheduler.plan_tick`` plus the events it expired."""
        with self._timed("engine.plan", "plan_ns", tick=self.ticks):
            plan = self.sched.plan_tick(**kw)
            return plan, self.sched.take_events()

    def _step_names(self, plan: TickPlan) -> str:
        """The jitted steps a plan enqueues, as their modules are named."""
        spec = "spec_" if self.spec_k else ""
        names = ["fork_page"] if plan.forks else []
        if plan.prefill is not None:
            names.append(spec + "prefill_step")
        if plan.decode is not None:
            names.append("spec_step" if spec else "decode_step")
        return "+".join(names)

    def _dispatch(self, plan: TickPlan) -> _InFlight:
        """Enqueue one tick's device work; returns unread token handles."""
        rows = {} if plan.prefill is None else {
            "rows": len(plan.prefill.slots)}
        with self._timed("engine.dispatch", "dispatch_ns", tick=self.ticks,
                         steps=self._step_names(plan), **rows):
            return self._enqueue(plan)

    def _enqueue(self, plan: TickPlan) -> _InFlight:
        tick = self.ticks
        self.faults.raise_if("step.error")  # chaos: the fused step dies
        for src, dst in plan.forks:  # COW copies BEFORE any write
            self.caches = self._fork(
                self.caches, jnp.int32(src), jnp.int32(dst))
            if self.draft_caches is not None:  # drafter aliases the same
                self.draft_caches = self._fork_draft(  # page ids: fork both
                    self.draft_caches, jnp.int32(src), jnp.int32(dst))
        keys = jnp.asarray(plan.keys)
        temps = jnp.asarray(plan.temps)
        top_ks = jnp.asarray(plan.top_ks)
        top_ps = jnp.asarray(plan.top_ps)
        prefill_tok = None
        fresh, fresh_mask = self._zero_tok, None
        pf = plan.prefill
        if pf is not None:
            pools = (self.caches,) + (
                (self.draft_caches,) if self.spec_k else ())
            first, self.caches, *draft = self._prefill_jit(pf.hot)(
                self.params, jnp.asarray(pf.slots), jnp.asarray(pf.tokens),
                jnp.asarray(pf.lens), jnp.asarray(pf.offsets),
                jnp.asarray(pf.scale_base), *pools, jnp.asarray(pf.table),
                keys, jnp.asarray(pf.sample_index), temps, top_ks, top_ps)
            if draft:
                (self.draft_caches,) = draft
            if pf.emit:
                prefill_tok = fresh = first
        dc = plan.decode
        decode_tok = None
        if dc is not None and self.spec_k:
            fresh_mask = jnp.asarray(dc.fresh)
            decode_tok, self._tok_buf, self.caches, self.draft_caches = (
                self._spec_jit(dc.hot)(
                    self.params, self._tok_buf, fresh, fresh_mask,
                    jnp.asarray(dc.live), jnp.asarray(dc.pos),
                    jnp.asarray(dc.n_tok), self.caches, self.draft_caches,
                    jnp.asarray(dc.table), jnp.asarray(dc.base), keys,
                    jnp.asarray(dc.sample_index), temps, top_ks, top_ps))
            self.ticks += 1
        elif dc is not None:
            fresh_mask = jnp.asarray(dc.fresh)
            decode_tok, self.caches = self._decode_jit(dc.hot)(
                self.params, self._tok_buf, fresh, fresh_mask,
                jnp.asarray(dc.live), jnp.asarray(dc.pos),
                jnp.asarray(dc.kv_len), self.caches, jnp.asarray(dc.table),
                jnp.asarray(dc.base), keys, jnp.asarray(dc.sample_index),
                temps, top_ks, top_ps)
            self._tok_buf = decode_tok
            self.ticks += 1
        elif pf is not None and pf.emit:
            # prefill completed with no decode tick in the same plan (the
            # prefill()-only driver, or all completions at max_new == 1):
            # fold the first-token samples into the on-device buffer so
            # the NEXT tick's decode still never needs a host round-trip
            mask = np.zeros(self.max_batch, bool)
            mask[[e.slot for e in pf.emit]] = True
            self._tok_buf = jnp.where(jnp.asarray(mask), fresh,
                                      self._tok_buf)
        return _InFlight(prefill_tok, pf.emit if pf else (),
                         decode_tok, dc.emit if dc else (),
                         bool(self.spec_k and dc is not None), tick)

    def _read(self, arr: jax.Array, tick: int) -> np.ndarray:
        """THE host<->device readback (token ids only); instrumented so
        benchmarks report the host-idle fraction and tests can assert the
        one-readback-per-tick invariant."""
        t0 = time.perf_counter()
        with TraceAnnotation("engine.readback", tick=tick):
            out = np.asarray(arr)
        self.blocked_s += time.perf_counter() - t0
        self.readbacks += 1
        return out

    def _collect(self, inflight: _InFlight) -> List[RequestOutput]:
        """Read a dispatched tick's sampled ids and surface them (first
        prefill samples, then decode samples — the sync event order).

        Speculative ticks read ONE packed (B, m+1) array — per-slot
        target samples plus the accepted count — and settle each slot's
        emit run through ``Scheduler.resolve_spec`` (accepted prefix
        ingested, rejected suffix dropped + rolled back).

        ``self._settling`` mirrors the not-yet-settled emits (in settle
        order) so crash containment can balance the in-flight accounting
        when a readback raises partway through."""
        events: List[RequestOutput] = []
        self._settling = list(inflight.prefill_emit + inflight.decode_emit)
        tick = inflight.tick
        if inflight.prefill_emit:
            vals = self._read(inflight.prefill_tok, tick)
            with self._timed("engine.ingest", "ingest_ns", tick=tick):
                for e in inflight.prefill_emit:
                    out = self.sched.ingest(e, int(vals[e.slot]))
                    self._settling.pop(0)
                    if out is not None:
                        events.append(out)
        if inflight.decode_emit:
            vals = self._read(inflight.decode_tok, tick)
            with self._timed("engine.ingest", "ingest_ns", tick=tick):
                if inflight.spec:
                    groups: "dict[int, List[Emit]]" = {}
                    for e in inflight.decode_emit:  # slot-major consecutive
                        groups.setdefault(e.slot, []).append(e)
                    for slot, ems in groups.items():  # insertion == settle
                        events.extend(self.sched.resolve_spec(
                            slot, tuple(ems), vals[slot],
                            int(vals[slot, -1])))
                        del self._settling[:len(ems)]
                else:
                    for e in inflight.decode_emit:
                        out = self.sched.ingest(e, int(vals[e.slot]))
                        self._settling.pop(0)
                        if out is not None:
                            events.append(out)
        return events

    # ------------------------------------------------------------------
    # crash containment (the engine loops below route through this)
    # ------------------------------------------------------------------
    def _fail_tick(self, exc: BaseException,
                   unsettled: List[Emit]) -> List[RequestOutput]:
        """Contain one failed device tick: settle the in-flight
        accounting for every sample that will never be read (`unsettled`
        plus anything still dispatched-ahead), reset the on-device token
        buffer, and fail the ACTIVE/RETIRING requests with
        ``finish_reason="error"`` (pages invalidated + freed; see
        ``Scheduler.fail_active``).  QUEUED requests are untouched — a
        preempted request's lost sample regenerates bit-identically on
        resume (keyed sampling) — so the engine keeps serving."""
        for e in unsettled:
            self.sched.drop(e)
        if self._pending is not None:  # the dispatched-ahead tick is lost
            for e in self._pending.prefill_emit + self._pending.decode_emit:
                self.sched.drop(e)
            self._pending = None
        self._settling = []
        self._tok_buf = self._zero_tok  # device token state is suspect
        self.tick_errors += 1
        self.last_error = f"{type(exc).__name__}: {exc}"
        log.warning("device tick failed (%s); failing in-flight requests "
                    "and continuing", self.last_error, exc_info=exc)
        return self.sched.fail_active(self.last_error)

    @staticmethod
    def _plan_emits(plan: TickPlan) -> List[Emit]:
        ems: List[Emit] = []
        if plan.prefill is not None:
            ems.extend(plan.prefill.emit)
        if plan.decode is not None:
            ems.extend(plan.decode.emit)
        return ems

    def _run_plan(self, plan: TickPlan) -> List[RequestOutput]:
        """Dispatch + read one plan, containing device failures (the
        sync-path tick body).  Planning itself stays OUTSIDE containment:
        it is host-pure, so an exception there is a scheduler bug, not a
        device fault to absorb."""
        try:
            inflight = self._dispatch(plan)
        except Exception as e:
            return self._fail_tick(e, self._plan_emits(plan))
        try:
            return self._collect(inflight)
        except Exception as e:
            return self._fail_tick(e, list(self._settling))

    # ------------------------------------------------------------------
    # the engine loops
    # ------------------------------------------------------------------
    def step(self) -> List[RequestOutput]:
        """One SYNCHRONOUS engine tick: plan, dispatch, read.  Returns
        this tick's streamed outputs (empty when the engine is idle)."""
        self.faults.advance()
        self._fault_delay()
        plan, events = self._plan()  # events: timeouts expired at plan time
        events.extend(self._run_plan(plan))
        return events

    def prefill(self, admitted: Optional[List[Admission]] = None
                ) -> List[RequestOutput]:
        """Drive the PREFILLING slots to completion (no admissions, no
        decode ticks) and return their first-token outputs.  ``admitted``
        is accepted for API compatibility with ``prefill(schedule())``;
        the scheduler already tracks the slots."""
        del admitted
        events: List[RequestOutput] = []
        while self.sched.has_prefilling:
            self.faults.advance()
            plan, expired = self._plan(admit=False, decode=False)
            events.extend(expired)
            events.extend(self._run_plan(plan))
        return events

    def _fault_delay(self) -> None:
        d = self.faults.delay("tick.delay")
        if d > 0:
            time.sleep(d)  # chaos: a straggling device / slow shard

    def poll(self) -> List[RequestOutput]:
        """ONE engine iteration honoring ``mode``; the unit external
        drivers (``stream()``, the network gateway's pump thread, the
        traffic-SLO load benchmark) build their loops from.  Safe to call
        when idle (returns ``[]``); new submissions between polls join
        the next tick — continuous-batching admission under live traffic.

        ``mode="sync"``: plan + dispatch + read one tick.
        ``mode="overlap"``: dispatch tick ``t+1`` BEFORE reading tick
        ``t`` — the device starts on the next forward while the host
        ingests tokens, detects finishes, and plans (the overlap the
        paper's pipelined search/contextualization story calls for).
        The returned outputs are therefore those of the PREVIOUS poll's
        tick; keep polling until ``has_pending`` clears to drain.

        A device failure in either mode is CONTAINED: the tick's
        in-flight requests finish with ``finish_reason="error"``, their
        pages free, and the engine keeps serving (``tick_errors``
        counts; see ``_fail_tick``)."""
        if self.mode == "sync":
            return self.step() if self.has_work else []
        self.faults.advance()
        self._fault_delay()
        inflight = None
        events: List[RequestOutput] = []
        if self.has_work:
            plan, events = self._plan()
            try:
                inflight = self._dispatch(plan)
            except Exception as e:
                events.extend(self._fail_tick(e, self._plan_emits(plan)))
                return events
        pending, self._pending = self._pending, None
        if pending is not None:
            try:
                events.extend(self._collect(pending))
            except Exception as e:
                unsettled = list(self._settling)
                if inflight is not None:  # the new tick dies with the device
                    unsettled.extend(inflight.prefill_emit
                                     + inflight.decode_emit)
                events.extend(self._fail_tick(e, unsettled))
                return events
        self._pending = (None if inflight is None or inflight.empty
                         else inflight)
        return events

    def stream(self, *requests: Request) -> Iterator[RequestOutput]:
        """Submit `requests` (if given) and drive the engine, yielding
        each generated token as a RequestOutput until the pool drains.
        Token-for-token identical between ``mode="sync"`` and
        ``mode="overlap"`` (and to ``run()``): same per-request rng, same
        per-request tick schedule."""
        for r in requests:
            self.submit(r)
        while self.has_work or self.has_pending:
            yield from self.poll()

    def run(self) -> List[Request]:
        """Drain the engine; returns completed requests in finish order."""
        for _ in self.stream():
            pass
        return self.sched.done
