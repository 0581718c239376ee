"""Hierarchical Parameter Server orchestration (paper §3).

Lookup path per table: L1 device cache -> L2 volatile DB -> L3 persistent
DB, with promotion on miss at every level. The online-update Consumer
applies trainer messages to L2/L3 AND marks the touched L1 rows dirty;
the hotness-scheduled refresh (driven by the serving loop, see
``serve.server``) then re-pulls them in bounded chunks, hot rows first.

Batched lookup path: each table resolves through a HOST stage (sorted
index probe + ONE coalesced miss fetch) and a DEVICE stage (the one
payload scatter + slot transfer), and the stacked pooled output
``[B, T, D]`` is computed in a SINGLE jitted device call at the end — the
per-table slot arrays are the only host->device transfer, and the pooled
activations never bounce through host memory. With ``pipelined=True`` the
two stages are double-buffered on a dedicated host worker so table
*t+1*'s index probe overlaps table *t*'s device scatter;
``lookup_stream`` extends the same pipeline across consecutive queries
(query *i+1*'s probes run while the host blocks materializing query *i*'s
result — the serving-loop shape; ``materialize=False`` hands the caller
un-synced device arrays so the serve loop can chain the dense net before
any host sync). ``lookup_stage_sync`` is the no-overlap reference engine
the benchmarks compare against. Pooling honors each table's combiner
(sum or mean); the ``hotness`` argument selects the valid id columns per
table (and is validated against the query shape instead of being silently
ignored).

When the caches are built with ``cache_shards=N`` (optionally over a
``cache_mesh``), the pooled gather reads the striped payload through
``ops.sharded_pooled_lookup`` — same single dispatch, payload distributed
row ``r`` -> stripe ``r % N``.
"""
from __future__ import annotations

import functools
import math
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import EmbeddingTableConfig
from repro.core.hps.embedding_cache import DeviceEmbeddingCache, LookupPlan
from repro.core.hps.message_bus import Consumer, MessageBus
from repro.core.hps.persistent_db import PersistentDB
from repro.core.hps.volatile_db import VolatileDB
from repro.kernels import ops


def bucket_rows(b: int) -> int:
    """The power-of-two row bucket a ``b``-row query runs at (0 for 0):
    the pooled gather, and the server's dense net after it, compile once
    per bucket rather than once per row count."""
    return 1 << (b - 1).bit_length() if b > 0 else 0


@functools.partial(jax.jit, static_argnames=("combiners", "apply_mean",
                                             "shards", "mesh", "axis"))
def _pooled_stack(payloads: Tuple[tuple, ...],
                  slots: Tuple[jax.Array, ...],
                  combiners: Tuple[str, ...],
                  apply_mean: bool = True, shards: int = 1,
                  mesh=None, axis: str = "cache") -> jax.Array:
    """One device dispatch: per-table pooled gathers stacked to [B, T, D].

    Each payload is a ``(payload, scales)`` snapshot pair; compressed
    stores dequantize inside the fused gather kernel, so the stacked
    output is f32 regardless of storage precision — still ONE dispatch.
    """
    outs = []
    for (p, sc), s, comb in zip(payloads, slots, combiners):
        if shards == 1:
            pooled = ops.pooled_cache_lookup(p, s, sc)   # [B, D] sum over H
        else:
            pooled = ops.sharded_pooled_lookup(p, s, scales=sc,
                                               mesh=mesh, axis=axis)
        if comb == "mean" and apply_mean:
            denom = jnp.maximum((s >= 0).sum(axis=1, keepdims=True), 1)
            pooled = pooled / denom.astype(pooled.dtype)
        outs.append(pooled)
    return jnp.stack(outs, axis=1)


class HPS:

    # Checked by `python -m repro.analysis`: the L3 fetch counters have
    # their own lock (probe and refresh fetches race), and the lazy host
    # pool is built under _pool_lock.
    _GUARDED_BY = {
        "_l3_fetch_calls": "_l3_stats_lock",
        "_l3_fetch_rows": "_l3_stats_lock",
        "_host_pool": "_pool_lock",
    }

    def __init__(self, model_name: str,
                 tables: Sequence[EmbeddingTableConfig],
                 pdb: PersistentDB, *,
                 vdb: Optional[VolatileDB] = None,
                 cache_capacity: int = 4096,
                 bus: Optional[MessageBus] = None,
                 cache_shards: int = 1, cache_mesh=None,
                 refresh_chunk_rows: int = 1024,
                 payload_dtype: str = "f32"):
        self.model_name = model_name
        self.tables = tuple(tables)
        self.pdb = pdb
        self.vdb = vdb or VolatileDB()
        self.cache_shards = cache_shards
        self.cache_mesh = cache_mesh
        self.cache_capacity = cache_capacity
        self.payload_dtype = payload_dtype
        # O(1) per-table config (the L2/L3 fetch path runs per miss batch)
        self._table_cfg: Dict[str, EmbeddingTableConfig] = {
            t.name: t for t in tables}
        self._l3_fetch_calls: Dict[str, int] = {t.name: 0 for t in tables}
        self._l3_fetch_rows: Dict[str, int] = {t.name: 0 for t in tables}
        # refresh fetches run with the cache lock released, so the L3
        # counters need their own (probe and refresh can fetch at once)
        self._l3_stats_lock = threading.Lock()
        self.caches: Dict[str, DeviceEmbeddingCache] = {}
        for t in tables:
            self.caches[t.name] = DeviceEmbeddingCache(
                min(cache_capacity, t.vocab_size), t.dim,
                fetch_fn=self._make_fetch(t.name),
                shards=cache_shards, mesh=cache_mesh,
                refresh_chunk_rows=refresh_chunk_rows,
                payload_dtype=payload_dtype)
        self.consumer = Consumer(bus, model_name) if bus else None
        self._host_pool: Optional[ThreadPoolExecutor] = None
        self._pool_lock = threading.Lock()
        #: the lookahead the adaptive ``lookup_stream`` last settled on
        #: (and the deepest it has reached) — observability for the
        #: fetch/compute auto-tuner
        self.stream_depth = 2
        self.stream_depth_peak = 2

    # -- L2/L3 fall-through ------------------------------------------------------

    def _vdb_key(self, table: str) -> str:
        """L2 key namespace: one VolatileDB process can back SEVERAL
        deployed models (the ensemble bundle), so table keys are scoped
        by model — two models' same-named tables never collide, and one
        model's online updates can never touch another's L2 rows."""
        return f"{self.model_name}/{table}"

    def _make_fetch(self, table: str):
        dim = self._table_cfg[table].dim

        def fetch(ids: np.ndarray) -> np.ndarray:
            with tracing.span("hps.miss_fetch", table=table):
                mask, rows = self.vdb.query(self._vdb_key(table), ids)
                if rows is None:
                    rows = np.zeros((len(ids), dim), np.float32)
                if not mask.all():
                    missing = ids[~mask]
                    fetched = self.pdb.fetch(self.model_name, table,
                                             missing)
                    with self._l3_stats_lock:
                        self._l3_fetch_calls[table] += 1
                        self._l3_fetch_rows[table] += len(missing)
                    rows[~mask] = fetched
                    self.vdb.insert(self._vdb_key(table), missing,
                                    fetched)  # promote
                return rows
        return fetch

    def _dim(self, table: str) -> int:
        return self._table_cfg[table].dim

    # -- public lookup ------------------------------------------------------------

    def _split_query(self, cat: np.ndarray,
                     hotness: Optional[List[int]]) -> List[np.ndarray]:
        """Validate the query shape and return per-table id blocks [B, H_t]."""
        T = len(self.tables)
        if cat.ndim == 2:
            if hotness is None:
                raise ValueError(
                    "2-D cat requires hotness=[ids per table] to split "
                    f"the {cat.shape[1]} id columns over {T} tables")
            if len(hotness) != T:
                raise ValueError(
                    f"hotness has {len(hotness)} entries for {T} tables")
            if sum(hotness) != cat.shape[1]:
                raise ValueError(
                    f"sum(hotness)={sum(hotness)} != cat.shape[1]="
                    f"{cat.shape[1]}")
            return np.split(cat, np.cumsum(hotness)[:-1], axis=1)
        if cat.ndim != 3:
            raise ValueError(f"cat must be [B, T, H] or [B, sum(hotness)]; "
                             f"got shape {cat.shape}")
        if cat.shape[1] != T:
            raise ValueError(
                f"cat.shape[1]={cat.shape[1]} does not match the "
                f"{T} tables of model '{self.model_name}'")
        blocks = [cat[:, ti, :] for ti in range(T)]
        if hotness is not None:
            if len(hotness) != T:
                raise ValueError(
                    f"hotness has {len(hotness)} entries for {T} tables")
            for ti, h in enumerate(hotness):
                if h > cat.shape[2]:
                    raise ValueError(
                        f"hotness[{ti}]={h} exceeds id columns "
                        f"{cat.shape[2]}")
                if h < cat.shape[2]:  # mask columns beyond the hotness
                    blk = blocks[ti].copy()
                    blk[:, h:] = -1
                    blocks[ti] = blk
        return blocks

    # -- two-stage lookup pipeline -------------------------------------------------

    def _host_worker(self) -> ThreadPoolExecutor:
        """The host-stage workers: index probes + miss fetches run here
        in pipelined mode while the caller's thread owns the device
        stages. Two workers (the double buffer) let table *t+1*'s index
        probe proceed while table *t*'s miss fetch waits on the lower
        levels (remote-L2/SSD IO releases the GIL). Same-table probes
        stay ordered: a probe holds its cache's lock, and with two
        workers at most one successor can be waiting on it. For a
        single-table model one worker suffices — cross-query overlap
        still applies, and FIFO execution keeps deep streams ordered."""
        with self._pool_lock:
            if self._host_pool is None:
                self._host_pool = ThreadPoolExecutor(
                    max_workers=min(2, len(self.tables)),
                    thread_name_prefix="hps-host")
            return self._host_pool

    def close(self) -> None:
        """Release the host-stage workers (idempotent; a later pipelined
        lookup just recreates them)."""
        with self._pool_lock:
            pool, self._host_pool = self._host_pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    def _probe(self, ti: int, blocks: List[np.ndarray]) -> LookupPlan:
        """HOST stage for table ``ti``: probe + coalesced miss fetch."""
        name = self.tables[ti].name
        with tracing.span("hps.probe", table=name):
            flat = np.ascontiguousarray(blocks[ti], np.int64).reshape(-1)
            return self.caches[name].probe(flat)

    def _device_stage(self, ti: int, plan: LookupPlan, b: int, bp: int,
                      h: int) -> Tuple[jax.Array, jax.Array]:
        """DEVICE stage for table ``ti``: flush the plan's deferred
        scatter, bind its payload snapshot, and ship the slot block."""
        payload = self.caches[self.tables[ti].name].commit(plan)
        slots = np.pad(plan.slots.reshape(b, h), ((0, bp - b), (0, 0)),
                       constant_values=-1)
        return jnp.asarray(slots, jnp.int32), payload

    def _collect_plan(self, ti: int, plan: LookupPlan, b: int, bp: int,
                      blocks: List[np.ndarray],
                      slot_blocks: List[jax.Array],
                      payloads: List[jax.Array],
                      overflow: List[Tuple[int, np.ndarray, np.ndarray,
                                           int]]) -> jax.Array:
        """Run table ``ti``'s device stage and record its outputs — the
        per-plan bookkeeping shared by every engine variant."""
        with tracing.span("hps.device_stage", table=self.tables[ti].name):
            sb, payload = self._device_stage(ti, plan, b, bp,
                                             blocks[ti].shape[1])
        slot_blocks.append(sb)
        payloads.append(payload)
        if len(plan.ov_idx):
            overflow.append((ti, plan.ov_idx, plan.ov_rows,
                             blocks[ti].shape[1]))
        return payload

    def _check_dims(self) -> int:
        dims = {t.dim for t in self.tables}
        if len(dims) != 1:
            raise ValueError(
                f"stacked lookup needs equal table dims, got {sorted(dims)}")
        return dims.pop()

    def _finalize(self, payloads: List[jax.Array],
                  slot_blocks: List[jax.Array],
                  blocks: List[np.ndarray],
                  overflow: List[Tuple[int, np.ndarray, np.ndarray, int]],
                  b: int, padded: bool = False) -> jax.Array:
        """The single jitted pooled-stack dispatch (+ rare overflow fix).

        The block comes out at the slot blocks' bucket ``[bp, T, D]``;
        ``padded=False`` slices it to the query's ``b`` rows. Padded rows
        hold only -1 slots, so they pool to 0 under either combiner."""
        with tracing.span("hps.pooled_stack", rows=b):
            combiners = tuple("mean" if t.combiner == "mean" else "sum"
                              for t in self.tables)
            stack = functools.partial(
                _pooled_stack, tuple(payloads), tuple(slot_blocks),
                combiners, shards=self.cache_shards, mesh=self.cache_mesh)
            if not overflow:
                out = stack()
                return out if padded else out[:b]

            # rare path: some ids exceeded L1 evictable capacity; add
            # their contribution host-side, then apply the mean
            # denominators exactly (zeros and ones on the padded rows)
            out = stack(apply_mean=False)
            bp = out.shape[0]
            corr = np.zeros((bp, len(self.tables), self.tables[0].dim),
                            np.float32)
            for ti, ov_idx, ov_rows, h in overflow:
                np.add.at(corr[:, ti, :], ov_idx // h, ov_rows)
            out = out + jnp.asarray(corr)
            mean_mask = np.asarray([c == "mean" for c in combiners])
            if mean_mask.any():
                denom = np.ones((bp, len(self.tables), 1), np.float32)
                denom[:b, :, 0] = np.stack(
                    [np.maximum((blk >= 0).sum(axis=1), 1)
                     for blk in blocks], axis=1)
                out = jnp.where(jnp.asarray(mean_mask)[None, :, None],
                                out / jnp.asarray(denom), out)
            return out if padded else out[:b]

    def lookup(self, cat: np.ndarray, hotness: Optional[List[int]] = None,
               *, pipelined: bool = False,
               padded: bool = False) -> jax.Array:
        """``cat [B, T, H]`` or ``[B, sum(hotness)]`` (-1 pad) -> pooled
        ``[B, T, D]`` on device, honoring each table's combiner.

        All tables resolve before the single jitted device call; per-table
        misses are coalesced by the L1 cache into one fetch + one scatter.
        Batch sizes are bucketed to powers of two so the variable-size
        serve loop compiles O(log) pooled-gather shapes, not one per
        drained batch size.

        ``pipelined=True`` double-buffers the per-table host stage (index
        probe + miss fetch, on the HPS host worker) against the device
        stage (scatter + slot transfer, on the calling thread): table
        *t+1* is being probed while table *t*'s scatter is in flight.
        Results are identical to the sequential path — each table's plan
        carries a lock-consistent payload snapshot.

        ``padded=True`` returns the block at its bucket,
        ``[bucket_rows(B), T, D]``, with zeros past row ``B``: the server
        runs its dense net at the bucket too, so no program in the step
        compiles per row count.
        """
        cat = np.asarray(cat)
        blocks = self._split_query(cat, hotness)
        self._check_dims()
        T = len(self.tables)
        b = cat.shape[0]
        if b == 0:
            return jnp.zeros((0, T, self.tables[0].dim), jnp.float32)
        bp = bucket_rows(b)

        slot_blocks: List[jax.Array] = []
        payloads: List[jax.Array] = []
        overflow: List[Tuple[int, np.ndarray, np.ndarray, int]] = []

        if pipelined and T > 1:
            pool = self._host_worker()
            futs: Dict[int, Future] = {
                ti: pool.submit(self._probe, ti, blocks)
                for ti in range(min(3, T))}          # 2 running + 1 queued
            for ti in range(T):
                plan = futs.pop(ti).result()
                if ti + 3 < T:
                    futs[ti + 3] = pool.submit(self._probe, ti + 3, blocks)
                self._collect_plan(ti, plan, b, bp, blocks, slot_blocks,
                                   payloads, overflow)
        else:
            for ti in range(T):
                self._collect_plan(ti, self._probe(ti, blocks), b, bp,
                                   blocks, slot_blocks, payloads, overflow)

        return self._finalize(payloads, slot_blocks, blocks, overflow, b,
                              padded)

    def lookup_stage_sync(self, cat: np.ndarray,
                          hotness: Optional[List[int]] = None, *,
                          padded: bool = False) -> jax.Array:
        """Fully stage-synchronous lookup: BLOCK on each table's device
        scatter before the next host probe, and block on the pooled
        stack before returning — zero overlap of any kind, not even
        XLA's async dispatch. The no-overlap reference engine the
        pipelining benchmarks (and the ``stage_sync`` server engine)
        compare against; bit-identical outputs to :meth:`lookup`, whose
        ``padded`` it takes."""
        cat = np.asarray(cat)
        blocks = self._split_query(cat, hotness)
        self._check_dims()
        b = cat.shape[0]
        if b == 0:
            return jnp.zeros((0, len(self.tables), self.tables[0].dim),
                             jnp.float32)
        bp = bucket_rows(b)
        slot_blocks: List[jax.Array] = []
        payloads: List[jax.Array] = []
        overflow: List[Tuple[int, np.ndarray, np.ndarray, int]] = []
        for ti in range(len(self.tables)):
            payload = self._collect_plan(ti, self._probe(ti, blocks), b,
                                         bp, blocks, slot_blocks,
                                         payloads, overflow)
            jax.block_until_ready(payload)             # no overlap
        return jax.block_until_ready(
            self._finalize(payloads, slot_blocks, blocks, overflow, b,
                           padded))

    def _timed_probe(self, ti: int, blocks: List[np.ndarray],
                     rec: List[float]) -> LookupPlan:
        """Host stage + its wall time (pure work, queueing excluded) —
        the fetch half of the stream auto-tuner's fetch/compute ratio."""
        t0 = time.perf_counter()
        plan = self._probe(ti, blocks)
        rec.append(time.perf_counter() - t0)
        return plan

    def lookup_stream(self, cats: Iterable[np.ndarray],
                      hotness: Optional[List[int]] = None, *,
                      depth: Optional[int] = None, max_depth: int = 8,
                      materialize: bool = True,
                      padded: bool = False) -> Iterator:
        """Serve a stream of queries through the two-stage pipeline,
        yielding ``[B, T, D]`` pooled outputs in order.

        Double-buffered on BOTH ends: the host workers run query
        *i+1*'s probes (and their L2/L3 miss fetches) while the calling
        thread handles query *i*'s device stages, and query *i*'s pooled
        output is materialized only after query *i+1*'s device work has
        been dispatched — so the device is computing one query while the
        host probes another, the serving loop of the paper's HPS.

        ``depth`` bounds the lookahead (queries whose fetched rows may
        be held in flight). The default (``None``) AUTO-TUNES it from
        the observed fetch/compute ratio: each query records its host
        stage's work time (probe + coalesced L2/L3 miss fetch) and the
        consumer-side time until the next query is taken, and the
        lookahead tracks ``ceil(fetch/compute) + 1`` within
        ``[2, max_depth]`` — a deep-RTT L2 (remote Redis-style fetches)
        admits more in-flight queries so misses overlap, while a warm
        cache stays at the classic double buffer. The depth last settled
        on (and the peak) is exposed as ``stream_depth`` /
        ``stream_depth_peak`` and in :meth:`stats`. Pass an ``int`` to
        pin the lookahead.

        ``materialize=False`` yields the un-synced DEVICE arrays instead
        of numpy, immediately after each query's device dispatch — the
        stream-fed server feeds these straight into the jitted dense net
        and owns the delay point itself, so the prediction (not the
        embedding) is what finally synchronizes the pipeline and NOTHING
        bounces through host memory between lookup and dense compute.
        ``padded`` is :meth:`lookup`'s.
        """
        self._check_dims()
        pool = self._host_worker()
        it = iter(cats)
        #: (b, blocks, probe futures, probe-time record) per query
        pending: "deque" = deque()
        exhausted = False
        adaptive = depth is None
        cur_depth = 2 if adaptive else max(1, depth)
        cap = max(cur_depth, max_depth)
        workers = max(1, min(2, len(self.tables)))
        ema_fetch: Optional[float] = None
        ema_compute: Optional[float] = None
        self.stream_depth = cur_depth        # pinned or adaptive start
        self.stream_depth_peak = max(self.stream_depth_peak, cur_depth)

        def admit():
            nonlocal exhausted
            while not exhausted and len(pending) < max(1, cur_depth):
                try:
                    cat = np.asarray(next(it))
                except StopIteration:
                    exhausted = True
                    return
                blocks = self._split_query(cat, hotness)
                rec: List[float] = []
                futs = [pool.submit(self._timed_probe, ti, blocks, rec)
                        for ti in range(len(self.tables))]
                pending.append((cat.shape[0], blocks, futs, rec))

        in_flight: List[jax.Array] = []     # dispatched, not yet synced
        try:
            admit()
            while pending:
                b, blocks, futs, rec = pending.popleft()
                plans = [f.result() for f in futs]
                t0 = time.perf_counter()    # host-stage wait excluded
                bp = bucket_rows(b)
                slot_blocks, payloads, overflow = [], [], []
                for ti, plan in enumerate(plans):
                    self._collect_plan(ti, plan, b, bp, blocks,
                                       slot_blocks, payloads, overflow)
                out = self._finalize(payloads, slot_blocks, blocks,
                                     overflow, b, padded)
                admit()                     # next query probes first ...
                if not materialize:         # ... caller owns the delay
                    yield out
                else:
                    in_flight.append(out)
                    if len(in_flight) > 1:  # ... then sync, one behind:
                        # the device computes query i while the host is
                        # already probing/dispatching query i+1
                        yield np.asarray(in_flight.pop(0))
                if adaptive:
                    # consume time includes the caller's work between
                    # yields (the dense net in the stream-fed server) —
                    # exactly what the fetch must overlap with
                    compute = max(time.perf_counter() - t0, 1e-6)
                    fetch = sum(rec) / workers
                    ema_fetch = fetch if ema_fetch is None \
                        else 0.5 * ema_fetch + 0.5 * fetch
                    ema_compute = compute if ema_compute is None \
                        else 0.5 * ema_compute + 0.5 * compute
                    ratio = ema_fetch / ema_compute
                    cur_depth = int(min(cap, max(
                        2, math.ceil(ratio) + 1)))
                    self.stream_depth = cur_depth
                    self.stream_depth_peak = max(self.stream_depth_peak,
                                                 cur_depth)
            for out in in_flight:
                yield np.asarray(out)
        finally:
            for _, _, futs, _ in pending:   # abandoned mid-stream
                for f in futs:
                    f.cancel()

    # -- online updates -------------------------------------------------------------

    def apply_updates(self) -> int:
        """Poll the message bus into VDB+PDB and schedule the touched L1
        rows for refresh (the hotness scheduler drains them)."""
        if self.consumer is None:
            return 0

        def apply(table, ids, rows):
            self.pdb.upsert(self.model_name, table, ids, rows)
            self.vdb.insert(self._vdb_key(table), ids, rows)
            cache = self.caches.get(table)
            if cache is not None:
                cache.mark_dirty(ids)

        return self.consumer.poll(apply)

    def schedule_refresh(self) -> int:
        """Mark every resident L1 row stale (poll-cycle fallback when no
        update stream identifies the changed rows)."""
        return sum(c.mark_all_dirty() for c in self.caches.values())

    def refresh_step(self, budget: Optional[int] = None) -> int:
        """Drain one bounded, hotness-ordered chunk of the refresh
        backlog per table — the serving loop calls this between batches."""
        return sum(c.refresh_chunk(budget) for c in self.caches.values())

    def refresh_backlog(self) -> int:
        return sum(c.refresh_backlog() for c in self.caches.values())

    def refresh_caches(self) -> int:
        """Full re-pull of every resident row (offline convenience)."""
        return sum(c.refresh_once() for c in self.caches.values())

    def resize_caches(self, capacity: int) -> int:
        """Rebuild every table's L1 at ``min(capacity, vocab)`` rows,
        keeping the hottest residents (the ensemble budget rebalancer's
        entry point). Returns total rows retained across tables."""
        kept = 0
        for t in self.tables:
            kept += self.caches[t.name].resize(min(capacity, t.vocab_size))
        self.cache_capacity = capacity
        return kept

    def start_refresh(self, interval_s: float):
        for c in self.caches.values():
            c.start_refresh(interval_s)

    def stop_refresh(self):
        for c in self.caches.values():
            c.stop_refresh()

    # -- metrics ---------------------------------------------------------------------

    def stats(self) -> Dict:
        with self._l3_stats_lock:
            l3 = {"calls": dict(self._l3_fetch_calls),
                  "rows": dict(self._l3_fetch_rows)}
        l2 = self.vdb.stats()                 # one locked L2 snapshot
        l1 = {k: c.counters() for k, c in self.caches.items()}
        return {
            "l1_hit_rate": {
                k: (c["hits"] / (c["hits"] + c["misses"])
                    if c["hits"] + c["misses"] else 0.0)
                for k, c in l1.items()},
            "l2_hits": l2["hits"],
            "l2_misses": l2["misses"],
            "l2": l2,
            "l3_fetches": l3,
            "refresh": {
                "rows_refreshed": sum(c["rows_refreshed"]
                                      for c in l1.values()),
                "chunks": sum(c["refresh_chunks"] for c in l1.values()),
                "backlog": self.refresh_backlog(),
            },
            "stream": {"depth": self.stream_depth,
                       "depth_peak": self.stream_depth_peak},
        }
