"""Continuous-batching serving engine on the consensus model.

vLLM-style slot management on top of the model zoo's decode path:

* a fixed pool of ``max_slots`` cache slots (attention K/V ring buffers,
  SSM/RG-LRU states — whatever the arch family uses), preallocated once;
* requests are admitted whenever a slot is free: the prompt is prefilled
  into a fresh cache (bucketed/padded lengths keep the jit cache warm) and
  spliced into the pool at the slot index;
* every engine tick decodes ONE token for ALL active slots in a single
  vmapped decode step with **per-slot positions** — sequences of different
  lengths progress independently;
* finished requests (max tokens or EOS) release their slot immediately.

The serving **fast path** (on by default, ``fastpath=False`` restores the
original per-request engine bit-for-bit) adds three wall-clock levers that
leave every tick-denominated metric untouched — admission order, completion
ticks and generated tokens are bit-identical, only host seconds change:

* **prefix KV cache** — post-prefill cache slices keyed by the exact prompt
  (bucketed), LRU-bounded (``prefix_cache`` entries), invalidated whenever
  ``engine.params`` is reassigned (hot reload), and bypassed for
  recurrent/windowed archs whose exact-length prefill semantics make a
  cached slice position-dependent.  A hit skips the prefill forward
  entirely (``prefill_skipped``); Zipf traffic makes hot prompts common, so
  the workload's own skew becomes throughput.
* **batched prefill** — all same-bucket pending requests admitted this tick
  run as ONE forward (batch padded to a power of two for a bounded trace
  set) instead of a batch=1 jit call per request.
* **active-slot decode** — at low occupancy the decode gathers the active
  slots (rounded up to a power of two) instead of paying the full
  ``max_slots`` vmapped step; results scatter back with out-of-bounds pad
  rows dropped.  Gathered decode is bit-identical to the full-pool step.

Fast-path programs (prefill/decode/splice) live in a **module-level
LRU-bounded program cache** (``PROGRAMS``) keyed by config + shapes, so a
fleet of engines with the same model shares one compiled program per shape
instead of recompiling per engine — compile time dominated the pre-fastpath
suite.  The legacy path's per-engine ``_prefills`` dict is LRU-bounded too
(``max_prefill_programs``) so many distinct exact-length prefills
(recurrent/windowed archs) can no longer grow the jit cache without bound;
``engine.stats()`` exposes sizes and evictions.

Admission is strictly FIFO: each tick runs an admit/finish fixpoint, so a
request that completes *at prefill* (single-token budget, or EOS emitted as
the final prompt-prefill token) releases its slot the same tick and the
next pending request is admitted into it — slot contention never reorders
or starves the queue.  Every ``Request`` carries tick- and wall-clock
timestamps (submit/admit/first-token/finish) consumed by the fleet metrics
layer (`repro.serving.metrics`); ``prefill_traces`` / ``decode_traces``
count program builds triggered by this engine so the bounded-trace-set
claim stays testable (clear ``PROGRAMS`` first when pinning counts).

Each tick records host spans on the JAX profiler's clock
(``jax.profiler.TraceAnnotation``), so a trace ties every device op and idle
gap to what the engine was doing: ``engine.step`` (the tick; stats ``tick``,
``admitted``, ``decoded``, ``built``) and, on the fast path, inside it
``engine.admit`` (the admit/finish fixpoint), ``engine.prefill`` (one per
bucket group; ``bucket``, ``rows``, ``bpad``), ``engine.decode`` (input upload
and dispatch; ``bpad``, ``occupancy``), ``engine.sample`` (key split, sampler,
host read of the tokens) and ``engine.retire`` (per-slot bookkeeping). With
the profiler off a span is a no-op; spans add no device sync.

This is the production shape of the ``decode_32k`` dry-run: the engine is
the host-side loop, the vmapped decode step is the device program.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import OrderedDict, deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import transformer as T
from repro.models.config import ModelConfig

__all__ = ["Request", "ServeEngine", "ProgramCache", "PROGRAMS"]


@dataclasses.dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 16
    eos_id: int | None = None
    # filled by the engine
    rid: int = -1
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # lifecycle + timing, stamped by the engine/fleet (ticks are engine
    # steps; walls are host seconds).  The first token is emitted by the
    # prefill at admit, so TTFT in ticks = admit_tick - submit_tick = queue
    # wait; first_wall is stamped when that token reaches the host, so wall
    # TTFT = queue wait + prefill.
    status: str = "queued"  # queued | active | done | rejected | shed
    submit_tick: int = -1
    admit_tick: int = -1
    finish_tick: int = -1
    submit_wall: float = 0.0
    first_wall: float = 0.0
    finish_wall: float = 0.0

    @property
    def ttft_ticks(self) -> int:
        """Time-to-first-token in engine ticks (queue wait; -1 if unserved)."""
        if self.admit_tick < 0 or self.submit_tick < 0:
            return -1
        return self.admit_tick - self.submit_tick


def _leaf_axis(path) -> int:
    """Per-leaf batch axis of a cache pytree: 1 under stacked 'blocks', else 0."""
    names = [getattr(p, "key", None) for p in path]
    return 1 if "blocks" in names else 0


def _batch_axes(cache) -> object:
    """Per-leaf vmap axis of the batch dim: 1 under stacked 'blocks', else 0."""
    return jax.tree_util.tree_map_with_path(lambda p, _: _leaf_axis(p), cache)


def _round_up(n: int, unit: int) -> int:
    return max(unit, -(-n // unit) * unit)


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class ProgramCache:
    """LRU-bounded map from (config, shape signature) -> compiled program.

    Shared by every ``ServeEngine`` in the process: a fleet of engines over
    the same model compiles each prefill/decode/splice shape once instead of
    per engine.  ``get`` returns ``(program, built)`` where ``built`` marks
    a fresh compile (the caller's retrace counter); eviction of the
    least-recently-used program is counted, mirroring the per-engine
    ``_prefills`` bound of the legacy path.
    """

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self._programs: OrderedDict[tuple, Callable] = OrderedDict()
        self.builds = 0
        self.evictions = 0

    def get(self, key: tuple, build: Callable[[], Callable]):
        if key in self._programs:
            self._programs.move_to_end(key)
            return self._programs[key], False
        fn = build()
        self.builds += 1
        self._programs[key] = fn
        if len(self._programs) > self.maxsize:
            self._programs.popitem(last=False)
            self.evictions += 1
        return fn, True

    def __len__(self) -> int:
        return len(self._programs)

    def clear(self) -> None:
        self._programs.clear()
        self.builds = self.evictions = 0


#: process-wide fast-path program cache (tests pinning trace counts should
#: ``PROGRAMS.clear()`` first so a previously built shape does not mask them)
PROGRAMS = ProgramCache()


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_slots: int = 4,
        cache_len: int = 256,
        prompt_bucket: int = 32,
        sample: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
        extra_inputs: dict | None = None,
        fastpath: bool = True,
        prefix_cache: int = 64,
        batched_prefill: bool | None = None,
        active_decode: bool | None = None,
        max_prefill_programs: int = 32,
    ):
        self.cfg = cfg
        self.max_slots = max_slots
        self.cache_len = cache_len
        self.prompt_bucket = prompt_bucket
        self.extra_inputs = extra_inputs or {}
        # fast-path knobs: the master toggle defaults the individual levers;
        # fastpath=False with everything defaulted IS the original engine
        self._fast = bool(fastpath)
        self._batched_prefill = self._fast if batched_prefill is None else batched_prefill
        self._active_decode = self._fast if active_decode is None else active_decode
        self._prefix_max = int(prefix_cache) if self._fast else 0
        self._max_prefill_programs = max_prefill_programs

        self.cache = T.init_cache(cfg, max_slots, cache_len)
        self._axes = _batch_axes(self.cache)
        self.pos = np.zeros(max_slots, np.int32)  # context length per slot
        self.last_tok = np.zeros(max_slots, np.int32)
        self.active: dict[int, Request] = {}
        self.pending: deque[Request] = deque()
        self._ids = itertools.count()
        self._steps = 0
        # program-build counters: one prefill build per (bucket, batch)
        # shape, a log2-bounded decode set, is the warm-cache contract
        # pinned by tests/test_serving.py (fast path counts builds this
        # engine triggered in the shared PROGRAMS cache)
        self.prefill_traces = 0
        self.decode_traces = 0
        self.tokens_generated = 0
        # prefix-cache state + telemetry
        self._prefix: OrderedDict[tuple, tuple] = OrderedDict()
        self.params_version = 0
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_evictions = 0
        self.prefix_invalidations = 0
        self.prefill_skipped = 0
        self.prefill_evictions = 0

        self._params = params
        self._prefills: OrderedDict[int, Callable] = OrderedDict()
        self._decode = None  # legacy per-engine decode program, built lazily
        self._sample = sample or (lambda logits, key: jnp.argmax(logits, -1).astype(jnp.int32))
        self._key = jax.random.PRNGKey(0)
        mixers = {cfg.mixer_for_layer(i) for i in range(cfg.num_layers)}
        self._recurrent = bool(mixers & {"mamba2", "rglru"})
        # windowed ring buffers: once the window wraps, every slot is
        # attendable, so bucket-padding garbage would poison the cache —
        # such archs also prefill at exact prompt length
        self._windowed = ("local_attn" in mixers) or (
            cfg.long_context_window is not None and cache_len > cfg.long_context_window
        )
        # shared-program key prefix: config identity + shapes the programs
        # close over (ModelConfig is a frozen dataclass — repr is total)
        extras = tuple(sorted(
            (k, tuple(np.shape(v)) if hasattr(v, "ndim") else v)
            for k, v in self.extra_inputs.items()
        ))
        self._sig = (repr(cfg), cache_len, extras)

    # ------------------------------------------------------------- params
    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, new):
        """Hot-reload hook: swapping weights invalidates every cached prefix
        (the slices were computed under the old params and would silently
        garble generations otherwise)."""
        self._params = new
        self.params_version += 1
        if self._prefix:
            self.prefix_invalidations += 1
            self._prefix.clear()

    # ---------------------------------------------------------- telemetry
    def stats(self) -> dict:
        """Engine-side fast-path telemetry (floats, fleet-aggregatable)."""
        lookups = self.prefix_hits + self.prefix_misses
        return {
            "prefix_hits": float(self.prefix_hits),
            "prefix_misses": float(self.prefix_misses),
            "prefix_entries": float(len(self._prefix)),
            "prefix_evictions": float(self.prefix_evictions),
            "prefix_invalidations": float(self.prefix_invalidations),
            "cache_hit_rate": (self.prefix_hits / lookups) if lookups else 0.0,
            "prefill_skipped": float(self.prefill_skipped),
            "prefill_programs": float(
                len(PROGRAMS) if self._fast else len(self._prefills)
            ),
            "prefill_evictions": float(
                PROGRAMS.evictions if self._fast else self.prefill_evictions
            ),
            "prefill_traces": float(self.prefill_traces),
            "decode_traces": float(self.decode_traces),
        }

    # ------------------------------------------------------------- slots
    def _slot_view(self, cache, slot):
        """Extract slot `slot` as a batchless cache pytree."""
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: jax.lax.index_in_dim(
                leaf, slot, axis=_leaf_axis(p), keepdims=False
            ),
            cache,
        )

    def _insert_slot(self, cache, cache1, slot):
        """Splice a batch-1 cache into the pool at `slot` (legacy, unjitted)."""

        def put(path, pool, new):
            idx = [0] * pool.ndim
            idx[_leaf_axis(path)] = slot
            return jax.lax.dynamic_update_slice(pool, new.astype(pool.dtype), tuple(idx))

        flat_pool, _ = jax.tree_util.tree_flatten_with_path(cache)
        flat_new = jax.tree_util.tree_leaves(cache1)
        out = [put(p, pool, new) for (p, pool), new in zip(flat_pool, flat_new)]
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(cache), out)

    # ----------------------------------------------- fast-path programs
    def _program(self, kind: str, *shape, counter: str | None = None):
        """Fetch/build a shared program; bump this engine's build counter."""
        key = (kind, self._sig, self.max_slots, *shape)
        fn, built = PROGRAMS.get(key, lambda: self._build(kind, *shape))
        if built and counter is not None:
            setattr(self, counter, getattr(self, counter) + 1)
        return fn

    def _build(self, kind: str, *shape):
        cfg, cache_len, max_slots = self.cfg, self.cache_len, self.max_slots
        axes = self._axes

        if kind == "prefill":  # shape = (bucket, bpad)
            def prefill(params, batch):
                return T.prefill(params, batch, cfg, cache_len=cache_len)

            return jax.jit(prefill)

        if kind == "splice":  # batch-1 cache row -> pool slot (traced index)
            def splice(pool, row, slot):
                def put(path, pool_leaf, new_leaf):
                    idx = [0] * pool_leaf.ndim
                    idx[_leaf_axis(path)] = slot
                    return jax.lax.dynamic_update_slice(
                        pool_leaf, new_leaf.astype(pool_leaf.dtype), tuple(idx)
                    )

                flat, _ = jax.tree_util.tree_flatten_with_path(pool)
                new = jax.tree_util.tree_leaves(row)
                out = [put(p, pl, nl) for (p, pl), nl in zip(flat, new)]
                return jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(pool), out
                )

            return jax.jit(splice)

        if kind == "scatter":  # shape = (bpad,): batched cache rows -> slots
            def scatter(pool, cache_b, sidx):
                # sidx [bpad]: target slot per row; pad rows carry max_slots,
                # dropped by out-of-bounds scatter (deterministic: live slot
                # indices are distinct)
                def put(path, pool_leaf, new_leaf):
                    new_leaf = new_leaf.astype(pool_leaf.dtype)
                    if _leaf_axis(path) == 1:
                        return pool_leaf.at[:, sidx].set(
                            new_leaf, mode="drop", unique_indices=False
                        )
                    return pool_leaf.at[sidx].set(
                        new_leaf, mode="drop", unique_indices=False
                    )

                flat, _ = jax.tree_util.tree_flatten_with_path(pool)
                new = jax.tree_util.tree_leaves(cache_b)
                out = [put(p, pl, nl) for (p, pl), nl in zip(flat, new)]
                return jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(pool), out
                )

            return jax.jit(scatter)

        if kind == "takerow":  # shape = (bpad,): one batch-1 row of a batch
            def takerow(cache_b, row):
                return jax.tree_util.tree_map_with_path(
                    lambda p, leaf: jax.lax.dynamic_slice_in_dim(
                        leaf, row, 1, axis=_leaf_axis(p)
                    ),
                    cache_b,
                )

            return jax.jit(takerow)

        def _expand(path, leaf):
            return jnp.expand_dims(leaf, _leaf_axis(path))

        def _squeeze(path, leaf):
            return jax.lax.index_in_dim(leaf, 0, axis=_leaf_axis(path), keepdims=False)

        def decode_one(params, tok, cache_slot, pos):
            cache_b = jax.tree_util.tree_map_with_path(_expand, cache_slot)
            logits, new_cache = T.decode_step(params, tok[None, None], cache_b, pos, cfg)
            return logits[0, 0], jax.tree_util.tree_map_with_path(_squeeze, new_cache)

        if kind == "decode":  # full-pool vmapped decode (shared legacy shape)
            return jax.jit(
                jax.vmap(decode_one, in_axes=(None, 0, axes, 0), out_axes=(0, axes))
            )

        if kind == "decodeg":  # shape = (bpad,): gather -> decode -> scatter
            def decode_gathered(params, toks, cache, pos, gidx, sidx):
                sub = jax.tree_util.tree_map_with_path(
                    lambda p, leaf: jnp.take(leaf, gidx, axis=_leaf_axis(p)),
                    cache,
                )
                logits, new_sub = jax.vmap(
                    decode_one, in_axes=(None, 0, axes, 0), out_axes=(0, axes)
                )(params, toks, sub, pos)

                def put(path, pool_leaf, new_leaf):
                    new_leaf = new_leaf.astype(pool_leaf.dtype)
                    if _leaf_axis(path) == 1:
                        return pool_leaf.at[:, sidx].set(new_leaf, mode="drop")
                    return pool_leaf.at[sidx].set(new_leaf, mode="drop")

                flat, _ = jax.tree_util.tree_flatten_with_path(cache)
                new = jax.tree_util.tree_leaves(new_sub)
                out = [put(p, pl, nl) for (p, pl), nl in zip(flat, new)]
                new_cache = jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(cache), out
                )
                return logits, new_cache

            return jax.jit(decode_gathered)

        raise ValueError(f"unknown program kind {kind!r}")

    # ----------------------------------------------------------- prefill
    def _prefill_fn(self, length: int):
        """Legacy per-engine batch-1 prefill program, LRU-bounded (many
        distinct exact lengths — recurrent/windowed archs — no longer grow
        the jit cache without bound)."""
        if length in self._prefills:
            self._prefills.move_to_end(length)
            return self._prefills[length]
        cfg = self.cfg

        def fn(params, batch):
            self.prefill_traces += 1  # trace-time side effect (retrace counter)
            return T.prefill(params, batch, cfg, cache_len=self.cache_len)

        self._prefills[length] = jax.jit(fn)
        if len(self._prefills) > self._max_prefill_programs:
            self._prefills.popitem(last=False)
            self.prefill_evictions += 1
        return self._prefills[length]

    def _bucket_for(self, req: Request) -> int:
        plen = len(req.prompt)
        if self._recurrent or self._windowed:
            # recurrent states absorb every consumed token, and wrapped ring
            # buffers attend every slot — both need exact-length prefill
            # (mamba2 additionally needs chunk-divisible lengths)
            if self.cfg.ssm_state:
                assert plen % self.cfg.ssm_chunk == 0, (
                    f"mamba2 prompts must be multiples of ssm_chunk={self.cfg.ssm_chunk}"
                )
            return plen
        return min(_round_up(plen, self.prompt_bucket), self.cache_len)

    def _post_admit(self, req: Request, slot: int, first: int, plen: int) -> None:
        # the first token is on the host now (read from the prefill, or kept
        # by the prefix cache): wall TTFT includes the prefill
        req.first_wall = time.time()
        # NOTE: bucket-padded positions beyond plen hold garbage K/V; decode
        # masks by position (pos = plen), so they are never attended.
        self.pos[slot] = plen
        self.last_tok[slot] = first
        req.output.append(first)
        self.tokens_generated += 1
        self.active[slot] = req

    def _admit(self, req: Request, slot: int) -> None:
        """Legacy admission: one batch-1 prefill forward per request."""
        req.admit_tick = self._steps
        req.status = "active"
        plen = len(req.prompt)
        bucket = self._bucket_for(req)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = req.prompt
        batch = {"tokens": jnp.asarray(toks), **{
            k: v[None] if hasattr(v, "ndim") else v for k, v in self.extra_inputs.items()
        }}
        logits, cache1 = self._prefill_fn(bucket)(self.params, batch)
        # first generated token comes from the last REAL prompt position
        first = int(jnp.argmax(logits[0, plen - 1]))
        # cache1 keeps its size-1 batch dim (already at the per-leaf batch
        # axis), so the splice below is a rank-preserving dynamic_update_slice
        self.cache = self._insert_slot(self.cache, cache1, slot)
        self._post_admit(req, slot, first, plen)

    def _admit_many(self, pairs: list) -> None:
        """Fast-path admission: prefix-cache hits splice a stored slice, the
        misses run grouped per bucket as ONE batched prefill forward each.

        Bit-identity with the legacy path: the prefill forward is
        deterministic and batch rows are independent, so per-request first
        tokens and cache rows match the batch-1 result exactly — only the
        number of dispatches (and host seconds) changes.
        """
        hits, misses = [], []
        for req, slot in pairs:
            req.admit_tick = self._steps
            req.status = "active"
            plen = len(req.prompt)
            bucket = self._bucket_for(req)
            # bypass: exact-length archs (a cached slice is position/window
            # dependent) and extra-input models (the prompt alone does not
            # key the forward)
            cacheable = (
                self._prefix_max > 0
                and not (self._recurrent or self._windowed)
                and not self.extra_inputs
            )
            # keyed by quantization mode too: an int8 cached slice must never
            # splice into an f32 pool after a config flip (or vice versa)
            key = (
                (bucket, bool(getattr(self.cfg, "quantized_kv", False)), tuple(req.prompt))
                if cacheable
                else None
            )
            if key is not None and key in self._prefix:
                row, first = self._prefix[key]
                self._prefix.move_to_end(key)
                self.prefix_hits += 1
                self.prefill_skipped += 1
                hits.append((req, slot, row, first, plen))
            else:
                if key is not None:
                    self.prefix_misses += 1
                misses.append((req, slot, bucket, key, plen))

        splice = None
        for req, slot, row, first, plen in hits:
            if splice is None:
                splice = self._program("splice")
            self.cache = splice(self.cache, row, np.int32(slot))
            self._post_admit(req, slot, first, plen)

        groups: dict[int, list] = {}
        for item in misses:
            groups.setdefault(item[2], []).append(item)
        for bucket, group in groups.items():
            self._prefill_group(bucket, group)

    def _prefill_group(self, bucket: int, group: list) -> None:
        # batch padded to a power of two: the trace set stays log-bounded
        # in the admission burst size
        bpad = _pow2(len(group)) if self._batched_prefill else 1
        chunks = (
            [group] if self._batched_prefill
            else [[item] for item in group]
        )
        with TraceAnnotation("engine.prefill", bucket=bucket, rows=len(group), bpad=bpad):
            for chunk in chunks:
                toks = np.zeros((bpad, bucket), np.int32)
                last = np.zeros(bpad, np.int32)
                for r, (req, _, _, _, plen) in enumerate(chunk):
                    toks[r, :plen] = req.prompt
                    last[r] = plen - 1
                batch = {"tokens": jnp.asarray(toks), **{
                    k: (jnp.broadcast_to(jnp.asarray(v)[None],
                                         (bpad,) + tuple(np.shape(v)))
                        if hasattr(v, "ndim") else v)
                    for k, v in self.extra_inputs.items()
                }}
                prefill = self._program(
                    "prefill", bucket, bpad, counter="prefill_traces"
                )
                logits, cache_b = prefill(self.params, batch)
                # first generated token per row: argmax at its last REAL position
                firsts = np.asarray(jnp.argmax(
                    logits[jnp.arange(bpad), jnp.asarray(last)], axis=-1
                ))
                # one scatter splices every row into its slot; pad rows target
                # max_slots and are dropped out-of-bounds
                sidx = np.full(bpad, self.max_slots, np.int32)
                for r, (_, slot, _, _, _) in enumerate(chunk):
                    sidx[r] = slot
                scatter = self._program("scatter", bpad)
                self.cache = scatter(self.cache, cache_b, jnp.asarray(sidx))
                takerow = None
                for r, (req, slot, _, key, plen) in enumerate(chunk):
                    if key is not None and key not in self._prefix:
                        if takerow is None:
                            takerow = self._program("takerow", bpad)
                        self._prefix[key] = (takerow(cache_b, np.int32(r)),
                                             int(firsts[r]))
                        if len(self._prefix) > self._prefix_max:
                            self._prefix.popitem(last=False)
                            self.prefix_evictions += 1
                    self._post_admit(req, slot, int(firsts[r]), plen)

    # -------------------------------------------------------------- API
    def submit(self, req: Request) -> int:
        req.rid = next(self._ids)
        if req.submit_tick < 0:  # the fleet may pre-stamp the arrival tick
            req.submit_tick = self._steps
            req.submit_wall = time.time()
        self.pending.append(req)
        return req.rid

    def _finish(self, slot: int) -> None:
        r = self.active[slot]
        r.done = True
        r.status = "done"
        r.finish_tick = self._steps
        r.finish_wall = time.time()
        del self.active[slot]
        self.pos[slot] = 0

    def _complete(self, r: Request) -> bool:
        return len(r.output) >= r.max_new_tokens or (
            r.eos_id is not None and bool(r.output) and r.output[-1] == r.eos_id
        )

    def _decode_active(self) -> None:
        """One token for every active slot.

        Fast path: when occupancy is below the pool size, gather the active
        slots (padded to a power of two — pad rows re-decode slot order[0]
        and are dropped at scatter) so low-occupancy ticks stop paying the
        full ``max_slots`` vmap.  The sampler sees one logits row per active
        slot in slot order; the default argmax sampler is row-independent,
        so sampled tokens are bit-identical to the full-pool step.
        """
        order = sorted(self.active)
        n = len(order)
        bpad = _pow2(n) if (self._active_decode and n < self.max_slots) else self.max_slots
        with TraceAnnotation("engine.decode", bpad=bpad, occupancy=n):
            if bpad >= self.max_slots:
                decode = self._program("decode", counter="decode_traces")
                logits, self.cache = decode(
                    self.params, jnp.asarray(self.last_tok), self.cache,
                    jnp.asarray(self.pos),
                )
                rows = {slot: slot for slot in order}
            else:
                gidx = np.empty(bpad, np.int32)
                gidx[:n] = order
                gidx[n:] = order[0]
                sidx = np.full(bpad, self.max_slots, np.int32)
                sidx[:n] = order
                decode = self._program("decodeg", bpad, counter="decode_traces")
                logits, self.cache = decode(
                    self.params, jnp.asarray(self.last_tok[gidx]), self.cache,
                    jnp.asarray(self.pos[gidx]), jnp.asarray(gidx),
                    jnp.asarray(sidx),
                )
                rows = {slot: r for r, slot in enumerate(order)}
        with TraceAnnotation("engine.sample"):
            self._key, sub = jax.random.split(self._key)
            next_tok = np.asarray(self._sample(logits, sub))
        with TraceAnnotation("engine.retire"):
            for slot in order:
                r = self.active[slot]
                tok = int(next_tok[rows[slot]])
                r.output.append(tok)
                self.tokens_generated += 1
                self.pos[slot] += 1
                self.last_tok[slot] = tok
                if self._complete(r):
                    self._finish(slot)

    def step(self) -> None:
        """One engine tick: admit (FIFO), decode one token for all active slots.

        Admission runs to a fixpoint with completion: a request that is
        already complete after its prefill (single-token budget, or EOS
        emitted as the final prompt-prefill token) releases its slot THIS
        tick and the next pending request is admitted into it, in strict
        submit order.  Each loop iteration either admits at least one
        pending request or breaks, so the fixpoint terminates.
        """
        built = self._built()
        with TraceAnnotation("engine.step", tick=self._steps) as span:
            if self._fast:
                with TraceAnnotation("engine.admit"):
                    admitted = self._admit_fixpoint()
            else:
                admitted = self._admit_fixpoint()
            decoded = len(self.active)
            if decoded:
                if self._fast:
                    self._decode_active()
                else:
                    self._decode_legacy()
            span.set_metadata(admitted=admitted, decoded=decoded,
                              built=self._built() - built)
        self._steps += 1

    def _built(self) -> int:
        """Programs built so far: the shared cache's (fast path), or this
        engine's traces (legacy path)."""
        return PROGRAMS.builds if self._fast else self.prefill_traces + self.decode_traces

    def _admit_fixpoint(self) -> int:
        """Admit pending requests into free slots until none fits; returns
        how many were admitted."""
        admitted = 0
        while True:
            for slot in list(self.active):
                if self._complete(self.active[slot]):
                    self._finish(slot)
            free = [s for s in range(self.max_slots) if s not in self.active]
            if not (self.pending and free):
                return admitted
            if self._fast:
                pairs = []
                for slot in free:
                    if not self.pending:
                        break
                    pairs.append((self.pending.popleft(), slot))
                self._admit_many(pairs)
                admitted += len(pairs)
            else:
                for slot in free:
                    if not self.pending:
                        break
                    self._admit(self.pending.popleft(), slot)
                    admitted += 1

    def _decode_legacy(self) -> None:
        """One token for every slot of the pool with the per-engine decode
        program (built lazily; counts retraces at trace time like the
        original engine)."""
        if self._decode is None:
            def _expand(path, leaf):
                return jnp.expand_dims(leaf, _leaf_axis(path))

            def _squeeze(path, leaf):
                return jax.lax.index_in_dim(
                    leaf, 0, axis=_leaf_axis(path), keepdims=False
                )

            cfg = self.cfg

            def decode_one(params, tok, cache_slot, pos):
                self.decode_traces += 1  # trace-time side effect
                cache_b = jax.tree_util.tree_map_with_path(_expand, cache_slot)
                logits, new_cache = T.decode_step(
                    params, tok[None, None], cache_b, pos, cfg
                )
                return logits[0, 0], jax.tree_util.tree_map_with_path(
                    _squeeze, new_cache
                )

            self._decode = jax.jit(jax.vmap(
                decode_one, in_axes=(None, 0, self._axes, 0),
                out_axes=(0, self._axes),
            ))
        logits, new_cache = self._decode(
            self.params, jnp.asarray(self.last_tok), self.cache,
            jnp.asarray(self.pos),
        )
        self.cache = new_cache
        self._key, sub = jax.random.split(self._key)
        next_tok = np.asarray(self._sample(logits, sub))
        for slot in list(self.active):
            r = self.active[slot]
            tok = int(next_tok[slot])
            r.output.append(tok)
            self.tokens_generated += 1
            self.pos[slot] += 1
            self.last_tok[slot] = tok
            if self._complete(r):
                self._finish(slot)

    def run(self, requests: list[Request], max_ticks: int = 10_000) -> list[Request]:
        """Submit everything and tick until done.  Returns the requests."""
        for r in requests:
            self.submit(r)
        ticks = 0
        while (self.pending or self.active) and ticks < max_ticks:
            self.step()
            ticks += 1
        return requests
