"""Triton-style batched inference server backed by the HPS.

Request flow (paper Figure 2, red path): requests queue up, a batcher
drains up to ``max_batch`` of them, the HPS resolves embeddings (L1 device
cache -> L2 VDB -> L3 PDB), and the jitted dense net computes predictions.
``deploy_from_training`` exports a trained model into the PDB — the
offline-training deployment path; online updates arrive via the bus.

The serve loop is one STREAM-FED pipeline: drained request groups feed
the dense network directly from ``HPS.lookup_stream`` with no
caller-thread materialization in between — while query *i-1*'s
prediction materializes, query *i*'s pooled embeddings and dense net are
computing on device and query *i+1*'s index probes (and their remote
L2/L3 miss fetches) run on the HPS host workers. The only host sync
point per query is the prediction itself. ``predict()`` is the
synchronous entry point beside it (one lookup, one dense dispatch, one
sync per call), and both give the same bits: the per-plan payload
snapshots make the lookup machinery order-independent, and the dense net
is the same jitted function either way. Both run a group at its
power-of-two row bucket (``hps.bucket_rows``, the pooled gather's) from
pooled block to probability, so the dense net compiles once per bucket,
not once per group; the host keeps the group's own rows after the sync.

ADMISSION CONTROL (the submit path's QoS layer, off by default so the
bare server behaves exactly as before): a server constructed — or
configured via ``set_admission`` — with ``queue_depth`` and/or
``slo_ms`` becomes an admission-controlled endpoint:

- **Bounded queue + graceful shedding.** ``submit`` beyond
  ``queue_depth`` outstanding requests — or after ``close()`` — never
  enqueues: the caller's handle receives a typed
  :class:`ServerOverloaded` IMMEDIATELY (counted in
  ``requests_shed``), so overload degrades into fast typed rejections
  instead of unbounded queueing or hung callers.
- **Deadline-aware dynamic batching.** With ``slo_ms`` declared, the
  batcher sizes each request group from the OLDEST queued request's
  remaining slack (:func:`deadline_batch_target`: grow toward
  ``max_batch`` while the predicted completion fits the SLO, cut early
  when slack is short), and a request whose deadline already passed at
  drain time is shed (``requests_expired``) rather than served late —
  serving it would burn capacity that fresher requests still have a
  chance of using. Delivered requests that still missed the SLO count
  in ``slo_violations``.
- **close() never strands a handle.** ``close()`` refuses new
  admissions, lets the serve loop finish in-flight groups, then drains
  every still-queued handle with the typed rejection.

The serve loop also drives update propagation (no bare timer threads):
between pipeline stages it polls the message bus into L2/L3, marks the
touched L1 rows dirty, and drains one bounded hotness-ordered refresh
chunk per tick — so refresh IO interleaves with serving instead of
stopping the world, and a periodic ``refresh_poll_s`` full-mark sweeps
rows whose updates arrived out of band.

``MultiModelServer`` fronts SEVERAL models from one storage backend —
per-model serve loops and L1 caches over a shared VolatileDB
(model-namespaced keys), a shared PersistentDB (model-namespaced tables)
and a shared message bus (model-scoped topics): the ensemble deployment
unit of the GPU-specialized inference parameter server (arXiv
2210.08804), reconstructed by ``launch.serve.build_server_from_config``
from one ps.json bundle.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from typing import (Callable, Dict, List, Mapping, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import EmbeddingTableConfig, RecsysConfig
from repro.core.hps.hps import HPS, bucket_rows
from repro.core.hps.message_bus import MessageBus
from repro.core.hps.persistent_db import PersistentDB
from repro.core.hps.volatile_db import VolatileDB
from repro.loadgen.metrics import LatencyHistogram


class ServerOverloaded(Exception):
    """Typed rejection delivered to a request handle instead of a
    prediction: the admission queue was full, the request's deadline
    expired before it could be served, or the server was closed.
    Callers distinguish it from a prediction (and from a failed-group
    exception) by type — a shed is an expected overload outcome, not a
    serving bug."""


def deadline_batch_target(oldest_age_ms: float, slo_ms: float,
                          max_batch: int,
                          service_ms_per_row: Optional[float]) -> int:
    """Rows a forming request group may grow to before its OLDEST
    member risks the latency SLO.

    The decision never exceeds the declared budget: the returned
    ``target`` satisfies ``oldest_age_ms + target * service_ms_per_row
    <= slo_ms`` whenever a service estimate exists — or is the floor
    ``1`` (the oldest request always ships; a sub-SLO completion is
    impossible, so ship the smallest group now rather than hold it).
    With plenty of slack the target grows toward ``max_batch``
    (coalescing amortizes the per-group overhead); with no estimate yet
    (cold server) the full ``max_batch`` is allowed until the deadline
    itself has passed.
    """
    if oldest_age_ms >= slo_ms:
        return 1
    if service_ms_per_row is None or service_ms_per_row <= 0:
        return max_batch
    slack = slo_ms - oldest_age_ms
    return max(1, min(max_batch, int(slack / service_ms_per_row)))


class _Req(NamedTuple):
    """One queued request: arrays, the caller's handle, and the
    admission timestamp the SLO accounting measures from."""
    dense: np.ndarray
    cat: np.ndarray
    done: "queue.Queue"
    t_enq: float


def deploy_from_training(model, params: Dict, pdb: PersistentDB,
                         model_name: str) -> None:
    """Export trained embedding tables into the PDB (ground truth copy).

    EVERY collection exports: the deep tables, the dim-1 ``*_wide``
    twins of wide models (wdl/deepfm), and each extra N-group
    collection's tables — so the serving side can stand up one HPS per
    dim class from the PDB alone.
    """
    from repro.models.recsys.model import logical_tables
    for key, coll in model.collections().items():
        for name, full in logical_tables(coll, params[key]).items():
            pdb.create_table(model_name, name, full.shape[0],
                             full.shape[1], initial=full)
    pdb.flush()


class InferenceServer:

    # Checked by `python -m repro.analysis`: serving counters and the
    # latency histogram are written by the serve-loop thread and read by
    # stats/benchmark callers, so they live behind _stats_lock; the
    # admission gate (closed flag + shed counter) is touched from every
    # SUBMITTING thread, so it has its own lock — the two are never
    # nested.
    _GUARDED_BY = {
        "updates_applied": "_stats_lock",
        "rows_refreshed": "_stats_lock",
        "latency_hist": "_stats_lock",
        "requests_delivered": "_stats_lock",
        "requests_expired": "_stats_lock",
        "slo_violations": "_stats_lock",
        "queue_wait_s": "_stats_lock",
        "requests_drained": "_stats_lock",
        "rows_padded": "_stats_lock",
        "_dense_shapes": "_stats_lock",
        "_service_ms_per_row": "_stats_lock",
        "_closed": "_admit_lock",
        "requests_shed": "_admit_lock",
    }

    def __init__(self, model, dense_params: Dict, hps: HPS, *,
                 max_batch: int = 1024, needs_wide: bool = False,
                 wide_hps: Optional[HPS] = None,
                 extra_hps: Optional[Dict[str, HPS]] = None,
                 hotness: Optional[Sequence[int]] = None,
                 refresh_budget: int = 512,
                 refresh_poll_s: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 slo_ms: Optional[float] = None,
                 deadline_batching: bool = True):
        self.model = model
        self.hps = hps
        self.wide_hps = wide_hps
        #: one HPS per extra N-group embedding collection, keyed by group
        #: name — each reads its own cat column span (see ``_cols``)
        self.extra_hps: Dict[str, HPS] = dict(extra_hps or {})
        #: cat column span per embedding group. Populated only for
        #: N-group models (extras present); single-group servers keep it
        #: empty and every lookup sees the full cat block, exactly as
        #: before.
        self._cols: Dict[str, Tuple[int, int]] = \
            dict(model.group_columns()) if self.extra_hps else {}
        #: optional per-table hotness forwarded to HPS.lookup (validated
        #: there against the request shape); covers ALL cat columns in
        #: group order and is sliced per group alongside cat
        self.hotness = list(hotness) if hotness is not None else None
        self.dense_params = dense_params
        self.max_batch = max_batch
        #: rows re-pulled per refresh chunk between drained batches
        self.refresh_budget = refresh_budget
        #: period of the full-mark sweep (None = only bus-marked rows)
        self.refresh_poll_s = refresh_poll_s
        #: admission policy (None = unbounded / no SLO — legacy behavior)
        self.queue_depth = queue_depth
        self.slo_ms = slo_ms
        self.deadline_batching = deadline_batching
        self._stats_lock = threading.Lock()
        self.updates_applied = 0
        self.rows_refreshed = 0
        #: bounded-memory per-group latency store (mergeable log-bucketed
        #: histogram — a soak test costs the same KiBs as a smoke run)
        self.latency_hist = LatencyHistogram()
        self.requests_delivered = 0
        self.requests_expired = 0
        self.slo_violations = 0
        #: seconds drained requests waited in the queue, summed, and how
        #: many requests that sum covers (``repro.tracing`` lists both)
        self.queue_wait_s = 0.0
        self.requests_drained = 0
        #: padding rows the dense net computed (groups run at their
        #: ``bucket_rows`` bucket), and the row counts it was dispatched at
        self.rows_padded = 0
        self._dense_shapes: set = set()
        #: sequence number of the latest request group coalesced; only
        #: the serve-loop thread touches it (the id of the spans' groups)
        self._group = -1
        #: EWMA of observed service time per delivered row, feeding the
        #: deadline batcher's cut decision (None until the first group)
        self._service_ms_per_row: Optional[float] = None
        self._admit_lock = threading.Lock()
        self._closed = False
        self.requests_shed = 0
        self._last_poll = time.monotonic()
        # the dense net and its sigmoid, one program per row bucket
        if self.extra_hps:
            net = lambda p, d, e, w, x: jax.nn.sigmoid(  # noqa: E731
                model.apply_dense(p, d, e, w, extras=x))
        else:
            net = lambda p, d, e, w, x: jax.nn.sigmoid(  # noqa: E731
                model.apply_dense(p, d, e, w))
        self._predict = jax.jit(net)
        self._q: queue.Queue = queue.Queue(maxsize=queue_depth or 0)
        self._stop = threading.Event()
        self._worker: Optional[threading.Thread] = None
        #: control-plane hook run at the end of every ``_refresh_tick``
        #: (the ensemble budget rebalancer registers itself here); must
        #: be cheap or internally rate-limited — it runs on the serve
        #: loop between pipeline stages
        self.on_tick: Optional[Callable[[], None]] = None

    def set_admission(self, *, queue_depth: Optional[int] = None,
                      slo_ms: Optional[float] = None,
                      deadline_batching: bool = True) -> None:
        """Declare (or replace) the admission policy on an idle server —
        the request queue is swapped for one with the new bound, so this
        must run before ``start()`` / concurrent submits. Requests
        already queued carry over; any overflow beyond the new bound is
        shed with the typed rejection."""
        if self._worker is not None:
            raise RuntimeError("set_admission() requires a stopped "
                               "server: call it before start()")
        self.queue_depth = queue_depth
        self.slo_ms = slo_ms
        self.deadline_batching = deadline_batching
        newq: queue.Queue = queue.Queue(maxsize=queue_depth or 0)
        shed = 0
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            try:
                newq.put_nowait(req)
            except queue.Full:
                self._put_rejection(req, "queue bound shrank")
                shed += 1
        self._q = newq
        if shed:
            with self._admit_lock:
                self.requests_shed += shed

    def _record_latency(self, t0: float, rows: int = 0) -> None:
        """Record one group's service time, from ``t0`` (its drain from
        the queue, or the start of a direct ``predict``) to now. The time
        its requests waited in the queue before that is ``queue_wait_s``."""
        ms = (time.perf_counter() - t0) * 1e3
        with self._stats_lock:
            self.latency_hist.record(ms)
            if rows > 0:        # feed the deadline batcher's estimate
                obs = ms / rows
                self._service_ms_per_row = obs \
                    if self._service_ms_per_row is None \
                    else 0.8 * self._service_ms_per_row + 0.2 * obs

    # -- synchronous path ---------------------------------------------------------

    def _group_cat(self, cat: np.ndarray, key: str) -> np.ndarray:
        """Column slice of a request's cat block for one embedding group
        (identity for single-group servers)."""
        if not self._cols:
            return cat
        lo, hi = self._cols[key]
        return cat[:, lo:hi, :]

    def _group_hot(self, key: str) -> Optional[List[int]]:
        if not self._cols or self.hotness is None:
            return self.hotness
        lo, hi = self._cols[key]
        return self.hotness[lo:hi]

    def _dense_forward(self, dense: np.ndarray, emb: jax.Array,
                       wide: Optional[jax.Array],
                       extras: Optional[Dict[str, jax.Array]] = None,
                       group: int = -1, *, rows: int) -> jax.Array:
        """The one jitted dense-net-and-sigmoid dispatch — shared by
        ``predict`` and the stream pipeline, so their outputs are
        bit-identical.

        ``emb`` (and ``wide``, ``extras``) arrive at the lookup's bucket
        ``bucket_rows(rows)``; ``dense`` is padded to it here, on the
        host, so the program compiles once per bucket. The predictions
        come back at the bucket too: callers keep the first ``rows``
        after the sync. Every served model computes each row on its own,
        so padding changes no real row's answer."""
        with tracing.span("server.dense_forward", group=group):
            b = dense.shape[0]
            if b != rows:
                raise ValueError(f"dense has {b} rows for {rows} rows "
                                 f"of cat")
            bp = bucket_rows(b)
            if bp != b:
                dense = np.pad(dense, [(0, bp - b)]
                               + [(0, 0)] * (dense.ndim - 1))
            out = self._predict(self.dense_params, jnp.asarray(dense), emb,
                                wide, extras or {})
            with self._stats_lock:
                self.rows_padded += bp - b
                self._dense_shapes.add(bp)
            return out

    def _lookups(self, cat: np.ndarray, lookup: Callable
                 ) -> Tuple[jax.Array, Optional[jax.Array],
                            Dict[str, jax.Array]]:
        """Every embedding group's bucket-shaped block for one request
        group, by ``lookup(hps, cat, hotness)``: the deep block, the wide
        twins (which read the deep group's cat columns) and the extras."""
        dcat = self._group_cat(cat, "embedding")
        dhot = self._group_hot("embedding")
        emb = lookup(self.hps, dcat, dhot)
        wide = None
        if self.wide_hps is not None:
            wide = lookup(self.wide_hps, dcat, dhot)
        extras = {
            name: lookup(hps, self._group_cat(cat, f"embedding@{name}"),
                         self._group_hot(f"embedding@{name}"))
            for name, hps in self.extra_hps.items()}
        return emb, wide, extras

    def predict(self, dense: np.ndarray, cat: np.ndarray) -> np.ndarray:
        t0 = time.perf_counter()
        emb, wide, extras = self._lookups(
            cat, lambda hps, c, h: hps.lookup(
                c, h, pipelined=len(hps.tables) > 1, padded=True))
        out = self._dense_forward(dense, emb, wide, extras,
                                  rows=cat.shape[0])
        out = np.asarray(out)[:cat.shape[0]]
        self._record_latency(t0, rows=len(out))
        return out

    # -- refresh scheduling (runs on the serve loop, between batches) -------------

    def _refresh_tick(self) -> None:
        """One serving-loop tick of update propagation: bus -> L2/L3 (+
        dirty marks), a periodic full-mark sweep, and ONE bounded
        hotness-ordered refresh chunk — never a stop-the-world re-pull.
        Covers every HPS this server reads from (deep AND wide).

        Safe to interleave anywhere between pipeline stages: in-flight
        lookup plans carry their own lock-consistent payload snapshots,
        so a refresh scatter landing between a query's probe and its
        device stage can never tear that query's view."""
        with tracing.span("server.refresh_tick", group=self._group):
            sweep = False
            if self.refresh_poll_s is not None:
                now = time.monotonic()
                if now - self._last_poll >= self.refresh_poll_s:
                    self._last_poll = now
                    sweep = True
            applied = refreshed = 0        # the bus/refresh IO runs
            for hps in (self.hps, self.wide_hps,    # unlocked; counters
                        *self.extra_hps.values()):  # update in one step
                if hps is None:
                    continue
                if hps.consumer is not None:
                    applied += hps.apply_updates()
                if sweep:
                    hps.schedule_refresh()
                if hps.refresh_backlog():
                    refreshed += hps.refresh_step(self.refresh_budget)
            if applied or refreshed:
                with self._stats_lock:
                    self.updates_applied += applied
                    self.rows_refreshed += refreshed
            if self.on_tick is not None:
                self.on_tick()

    # -- queued/batched path --------------------------------------------------------

    def submit(self, dense: np.ndarray, cat: np.ndarray) -> "queue.Queue":
        """Queue a request; the returned handle's ``get()`` yields the
        prediction rows (or the exception that failed its batch).

        With admission control on, a full queue or a closed server
        delivers a typed :class:`ServerOverloaded` to the handle
        IMMEDIATELY — the caller never blocks on a request the server
        already decided not to serve."""
        done: queue.Queue = queue.Queue(maxsize=1)
        req = _Req(dense, cat, done, time.perf_counter())
        rejection = None
        with self._admit_lock:
            if self._closed:
                self.requests_shed += 1
                rejection = "server closed"
            else:
                try:
                    self._q.put_nowait(req)
                except queue.Full:
                    self.requests_shed += 1
                    rejection = (f"admission queue full "
                                 f"(depth {self.queue_depth})")
        if rejection is not None:
            self._put_rejection(req, rejection)
        return done

    @staticmethod
    def _put_rejection(req: _Req, why: str) -> None:
        try:
            req.done.put_nowait(ServerOverloaded(why))
        except queue.Full:
            pass

    def _expired(self, req: _Req) -> bool:
        """Deadline shedding applies only with an SLO declared AND
        deadline batching on — with fixed coalescing the server serves
        everything it admitted, however late."""
        if self.slo_ms is None or not self.deadline_batching:
            return False
        return (time.perf_counter() - req.t_enq) * 1e3 >= self.slo_ms

    def _batch_target(self, first: _Req) -> int:
        if self.slo_ms is None or not self.deadline_batching:
            return self.max_batch
        age_ms = (time.perf_counter() - first.t_enq) * 1e3
        with self._stats_lock:
            est = self._service_ms_per_row
        return deadline_batch_target(age_ms, self.slo_ms,
                                     self.max_batch, est)

    def _coalesce(self, first
                  ) -> Optional[Tuple[int, list, np.ndarray, np.ndarray]]:
        """Drain the queue behind ``first`` into one coalesced request
        group ``(group id, requests, dense, cat)`` (the batcher of the
        paper's Figure 2 — one group is one device batch), bounded by
        ``max_batch`` rows or, with an SLO declared, by the oldest
        request's remaining slack (:func:`deadline_batch_target`; the
        group may overshoot the target by at most the last drained
        request, since a drained request is never re-queued). An expired head is shed with the
        typed rejection instead of served late. Requests that cannot be
        concatenated (mismatched widths) get the error delivered to
        their handles here and ``None`` comes back — the serve loop must
        keep running. Every request drained, served or shed, adds its
        wait in the queue to ``queue_wait_s``."""
        self._group += 1
        drained = [first]
        try:
            with tracing.span("server.coalesce", group=self._group):
                while self._expired(first):
                    self._put_rejection(first, f"deadline expired "
                                               f"(slo {self.slo_ms}ms)")
                    with self._stats_lock:
                        self.requests_expired += 1
                    try:
                        first = self._q.get_nowait()
                    except queue.Empty:
                        return None
                    drained.append(first)
                reqs = [first]
                rows = first.dense.shape[0]
                target = self._batch_target(first)
                while rows < target:
                    try:
                        nxt = self._q.get_nowait()
                    except queue.Empty:
                        break
                    reqs.append(nxt)
                    drained.append(nxt)
                    rows += nxt.dense.shape[0]
                try:
                    dense = np.concatenate([r.dense for r in reqs])
                    cat = np.concatenate([r.cat for r in reqs])
                except Exception as exc:
                    self._deliver_error(reqs, exc)
                    return None
                return self._group, reqs, dense, cat
        finally:
            self._record_drained(drained)

    def _record_drained(self, drained: List[_Req]) -> None:
        now = time.perf_counter()
        wait = sum(now - r.t_enq for r in drained)
        with self._stats_lock:
            self.queue_wait_s += wait
            self.requests_drained += len(drained)

    def _deliver(self, reqs: list, preds: np.ndarray) -> None:
        off = 0
        now = time.perf_counter()
        delivered = violations = 0
        for r in reqs:
            n = r.dense.shape[0]
            r.done.put(preds[off:off + n])
            off += n
            delivered += 1
            if self.slo_ms is not None and \
                    (now - r.t_enq) * 1e3 > self.slo_ms:
                violations += 1
        with self._stats_lock:
            self.requests_delivered += delivered
            self.slo_violations += violations

    @staticmethod
    def _deliver_error(reqs: list, exc: BaseException) -> None:
        for r in reqs:
            try:
                r.done.put_nowait(exc)
            except queue.Full:
                pass

    # -- the stream-fed pipeline ---------------------------------------------------

    def _serve_burst_stream(self, first) -> None:
        """Pipeline one burst of requests end-to-end: request groups are
        admitted into ``HPS.lookup_stream`` (host probes + remote
        fetches run ahead on the HPS workers), each yielded DEVICE
        embedding block feeds the jitted dense net immediately, and
        predictions materialize ONE GROUP BEHIND the dense dispatch —
        group *i+1* probes the host index while group *i*'s payload
        scatters + dense net run and group *i-1*'s prediction leaves for
        its callers. ``_refresh_tick`` interleaves between stages. The
        burst ends when the request queue goes empty; the pipeline then
        drains in order.
        """
        fifo: deque = deque()   # (group, reqs, dense, rows, t0) in order
        head = [first]

        def cats():
            while True:
                if head:        # ALWAYS serve the already-dequeued
                    nxt = head.pop()    # request, even under stop()
                elif self._stop.is_set():
                    return      # stop only gates NEW admissions
                else:
                    try:
                        nxt = self._q.get_nowait()
                    except queue.Empty:
                        return
                group = self._coalesce(nxt)
                if group is None:           # un-concatenatable: errors
                    continue                # already delivered
                gid, reqs, dense, cat = group
                if dense.shape[0] == 0:     # degenerate empty group
                    self._deliver(reqs, np.zeros((0,), np.float32))
                    continue
                fifo.append((gid, reqs, dense, cat.shape[0],
                             time.perf_counter()))
                yield cat

        def group_src(src, key):
            """Wrap one tee branch with the group's column slice (the
            identity for single-group servers)."""
            if not self._cols:
                return src
            lo, hi = self._cols[key]
            return (c[:, lo:hi, :] for c in src)

        extra_names = list(self.extra_hps)
        n_wide = 1 if self.wide_hps is not None else 0
        srcs = iter(itertools.tee(cats(), 1 + n_wide + len(extra_names)))
        streams = [self.hps.lookup_stream(
            group_src(next(srcs), "embedding"),
            self._group_hot("embedding"), materialize=False, padded=True)]
        if self.wide_hps is not None:       # wide twins read the deep
            streams.append(self.wide_hps.lookup_stream(  # group's columns
                group_src(next(srcs), "embedding"),
                self._group_hot("embedding"), materialize=False,
                padded=True))
        for name in extra_names:
            key = f"embedding@{name}"
            streams.append(self.extra_hps[name].lookup_stream(
                group_src(next(srcs), key), self._group_hot(key),
                materialize=False, padded=True))

        in_flight: deque = deque()   # (group, reqs, rows, t0, device preds)
        current = None                      # group between fifo/in_flight
        try:
            for vals in zip(*streams):
                emb = vals[0]
                wide = vals[1] if n_wide else None
                extras = dict(zip(extra_names, vals[1 + n_wide:]))
                current = fifo.popleft()
                gid, reqs, dense, rows, t0 = current
                out = self._dense_forward(dense, emb, wide, extras,
                                          group=gid, rows=rows)
                in_flight.append((gid, reqs, rows, t0, out))
                current = None
                self._refresh_tick()        # between pipeline stages
                if len(in_flight) > 1:      # materialize one behind
                    self._materialize(in_flight.popleft())
            while in_flight:
                self._materialize(in_flight.popleft())
        except Exception as exc:            # a poisoned group kills the
            if current is not None:         # burst: surface the error to
                self._deliver_error(current[1], exc)  # EVERY undelivered
            for _, reqs, *_ in in_flight:   # handle (the failing group's
                self._deliver_error(reqs, exc)   # own included) instead
            for _, reqs, *_ in fifo:        # of hanging callers
                self._deliver_error(reqs, exc)

    def _materialize(self, item) -> None:
        gid, reqs, rows, t0, pred = item
        with tracing.span("server.materialize", group=gid):
            try:
                preds = np.asarray(pred)    # the one sync point per group
            except Exception as exc:        # deferred device error: this
                self._deliver_error(reqs, exc)  # group's handles first,
                raise                       # the burst handler the rest
            preds = preds[:rows]            # the bucket's real rows
            self._record_latency(t0, rows=rows)
            self._deliver(reqs, preds)

    # -- serve loop -----------------------------------------------------------------

    def _serve_loop(self):
        while not self._stop.is_set():
            try:
                with tracing.span("server.idle_wait", group=self._group):
                    first = self._q.get(timeout=0.05)
            except queue.Empty:
                self._refresh_tick()     # idle: drain the refresh backlog
                continue
            self._serve_burst_stream(first)

    def start(self):
        with self._admit_lock:
            if self._closed:
                raise RuntimeError("server is closed")
        self._worker = threading.Thread(target=self._serve_loop, daemon=True)
        self._worker.start()

    def stop(self):
        self._stop.set()
        if self._worker:
            self._worker.join()
            self._worker = None
        self._stop.clear()

    def close(self):
        """Terminal shutdown that never strands a caller: refuse new
        admissions (submits from here on get the typed rejection), let
        the serve loop finish the groups it already pulled, then deliver
        :class:`ServerOverloaded` to every handle still in the queue —
        after ``close()`` returns, every handle ever issued holds a
        prediction or an exception."""
        with self._admit_lock:
            self._closed = True
        self.stop()
        shed = 0
        while True:         # no racing producers: _closed gates submit
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            self._put_rejection(req, "server closed")
            shed += 1
        if shed:
            with self._admit_lock:
                self.requests_shed += shed

    def latency_percentiles(self) -> Dict[str, float]:
        """Percentiles of the groups' service times (``_record_latency``):
        from a group's drain, not from a request's admission. The queue
        wait before the drain is in ``counters()``' ``queue_wait_s``."""
        with self._stats_lock:
            hist = self.latency_hist.snapshot()
        if hist.count == 0:
            return {}
        s = hist.summary()
        return {"p50": s["p50"], "p95": s["p95"], "p99": s["p99"],
                "p999": s["p999"], "mean": s["mean"]}

    def reset_latencies(self) -> None:
        """Drop accumulated latency samples (benchmark warmup reset)."""
        with self._stats_lock:
            self.latency_hist.reset()

    def reset_serving_stats(self) -> None:
        """Zero latency samples AND admission counters — the load-test
        harness calls this between warmup and the measured phase."""
        with self._stats_lock:
            self.latency_hist.reset()
            self.requests_delivered = 0
            self.requests_expired = 0
            self.slo_violations = 0
            self.queue_wait_s = 0.0
            self.requests_drained = 0
            self.rows_padded = 0
            self._dense_shapes = set()
        with self._admit_lock:
            self.requests_shed = 0

    def update_versions(self) -> Dict[str, int]:
        """Highest online-update version applied per table, across every
        HPS this server reads from — the serving half of the freshness
        contract (``repro.online.UpdatePublisher`` stamps the versions;
        a freshness probe polls this until the published version lands)."""
        out: Dict[str, int] = {}
        for hps in (self.hps, self.wide_hps, *self.extra_hps.values()):
            if hps is None or hps.consumer is None:
                continue
            out.update(hps.consumer.last_versions)
        return out

    def counters(self) -> Dict[str, float]:
        """Lock-consistent snapshot of the serving counters."""
        with self._stats_lock:
            out = {"updates_applied": self.updates_applied,
                   "rows_refreshed": self.rows_refreshed,
                   "groups_served": self.latency_hist.count,
                   "requests_delivered": self.requests_delivered,
                   "requests_expired": self.requests_expired,
                   "slo_violations": self.slo_violations,
                   "queue_wait_s": self.queue_wait_s,
                   "requests_drained": self.requests_drained,
                   "rows_padded": self.rows_padded,
                   "dense_shapes": len(self._dense_shapes)}
        with self._admit_lock:
            out["requests_shed"] = self.requests_shed
        return out


class MultiModelServer:
    """Several models served from ONE parameter-server process.

    Each member keeps its own serve loop, dense net and L1 device caches
    (embedding working sets must not thrash each other); the storage
    levels below are SHARED — one VolatileDB (keys namespaced
    ``model/table`` by the HPS), one PersistentDB (tables namespaced per
    model on disk) and one message bus (topics scoped
    ``hps.<model>.<table>``) — so adding a model to a deployment adds
    L1 state only, and one model's online updates can never touch
    another's tables at any level. Predictions are bit-exact with
    per-model in-process servers: sharing storage shares bytes, not
    values.

    With ``cache_budget`` AND ``rebalance_interval_s`` set, the shared
    L1 row budget is periodically RE-SPLIT from observed per-model miss
    pressure (the deploy-time split is static declared hotness —
    ``api.hotness_cache_capacities``): each member's serve loop tick
    calls into the rebalancer, which at most once per interval re-splits
    the budget proportional to each model's L1 miss delta since the last
    split and resizes the member caches (hottest rows retained). Opt-in
    because a resize recompiles the pooled gather for the new payload
    shape — leave it off when the hot-path sanitizer's zero-recompile
    contract matters more than cache efficiency.

    Admission control is per member: declare each model's SLO and queue
    bound via ``server[name].set_admission(...)`` — the members' shed /
    violation counters surface in ``stats()``.
    """

    # Checked by `python -m repro.analysis`: rebalance bookkeeping is
    # touched from every member's serve loop, so it lives behind the
    # rebalance lock (acquired non-blocking — serving never waits on it).
    _GUARDED_BY = {
        "_last_counts": "_rebalance_lock",
        "_last_rebalance": "_rebalance_lock",
        "rebalances": "_rebalance_lock",
    }

    def __init__(self, servers: Mapping[str, InferenceServer], *,
                 vdb: Optional[VolatileDB] = None,
                 pdb: Optional[PersistentDB] = None,
                 bus: Optional[MessageBus] = None,
                 cache_budget: Optional[int] = None,
                 rebalance_interval_s: Optional[float] = None,
                 rebalance_floor: int = 64):
        if not servers:
            raise ValueError("MultiModelServer needs at least one model")
        self.servers: Dict[str, InferenceServer] = dict(servers)
        self.vdb = vdb
        self.pdb = pdb
        self.bus = bus
        self.cache_budget = cache_budget
        self.rebalance_interval_s = rebalance_interval_s
        self.rebalance_floor = rebalance_floor
        self.rebalances = 0
        self._rebalance_lock = threading.Lock()
        self._last_counts: Dict[str, Tuple[int, int]] = {}
        self._last_rebalance = time.monotonic()
        if cache_budget is not None and rebalance_interval_s is not None:
            for s in self.servers.values():
                s.on_tick = self._rebalance_tick

    @property
    def models(self) -> List[str]:
        return list(self.servers)

    def __getitem__(self, model: str) -> InferenceServer:
        return self._server(model)

    def _server(self, model: str) -> InferenceServer:
        try:
            return self.servers[model]
        except KeyError:
            raise KeyError(f"unknown model {model!r}; serving "
                           f"{self.models}") from None

    def predict(self, model: str, dense: np.ndarray,
                cat: np.ndarray) -> np.ndarray:
        return self._server(model).predict(dense, cat)

    def submit(self, model: str, dense: np.ndarray,
               cat: np.ndarray) -> "queue.Queue":
        return self._server(model).submit(dense, cat)

    # -- observed-hit-rate budget rebalance ----------------------------------

    def _rebalance_tick(self) -> None:
        """Serve-loop hook: re-split the shared L1 budget at most once
        per ``rebalance_interval_s``. Non-blocking — if another member's
        loop is mid-rebalance, this tick just returns."""
        if not self._rebalance_lock.acquire(blocking=False):
            return
        try:  # the non-blocking acquire above holds the lock through here
            now = time.monotonic()
            # lock-ok: LOCK001 inside acquire(blocking=False)/finally-release — held, just not a with-block
            if now - self._last_rebalance < self.rebalance_interval_s:
                return
            # lock-ok: LOCK001 inside acquire(blocking=False)/finally-release — held, just not a with-block
            self._last_rebalance = now
            # lock-ok: LOCK004 inside acquire(blocking=False)/finally-release — held, just not a with-block
            self._rebalance_locked()
        finally:
            self._rebalance_lock.release()

    def rebalance_now(self) -> Dict[str, int]:
        """Force one budget re-split immediately (tests / operators);
        returns the per-model capacities now in effect."""
        with self._rebalance_lock:
            self._last_rebalance = time.monotonic()
            self._rebalance_locked()
        return {name: s.hps.cache_capacity
                for name, s in self.servers.items()}

    def _rebalance_locked(self) -> None:
        """Split ``cache_budget`` proportional to each model's observed
        L1 miss delta since the last split (+1 smoothing so an idle
        member keeps a foothold), floored so a cold member still serves,
        and resize members whose share moved more than 10% — small
        drifts are not worth the resize's gather recompile."""
        demand: Dict[str, int] = {}
        for name, s in self.servers.items():
            hits = misses = 0
            for c in s.hps.caches.values():
                cnt = c.counters()
                hits += cnt["hits"]
                misses += cnt["misses"]
            _, pm = self._last_counts.get(name, (0, 0))
            self._last_counts[name] = (hits, misses)
            demand[name] = (misses - pm) + 1
        total = sum(demand.values())
        moved = 0
        for name, d in demand.items():
            s = self.servers[name]
            floor = max(self.rebalance_floor, s.hps.cache_shards)
            cap = max(floor, int(round(self.cache_budget * d / total)))
            cur = s.hps.cache_capacity
            if abs(cap - cur) <= max(1, int(0.1 * cur)):
                continue
            s.hps.resize_caches(cap)
            if s.wide_hps is not None:
                s.wide_hps.resize_caches(cap)
            for ehps in s.extra_hps.values():
                ehps.resize_caches(cap)
            moved += 1
        if moved:
            self.rebalances += 1

    def start(self):
        for s in self.servers.values():
            s.start()

    def stop(self):
        for s in self.servers.values():
            s.stop()

    def close(self):
        """Close every member: refuse new work, finish in-flight groups,
        reject every still-queued handle — no caller blocks forever."""
        for s in self.servers.values():
            s.close()

    def stats(self) -> Dict[str, Dict]:
        """Per-model serving picture: L1/L2/L3 + refresh + latency +
        admission (shed / expired / SLO-violation counts)."""
        out = {}
        for name, s in self.servers.items():
            c = s.counters()
            out[name] = {"hps": s.hps.stats(),
                         "cache_capacity": s.hps.cache_capacity,
                         "latency_ms": s.latency_percentiles(),
                         "updates_applied": c["updates_applied"],
                         "rows_refreshed": c["rows_refreshed"],
                         "requests_delivered": c["requests_delivered"],
                         "requests_shed": c["requests_shed"],
                         "requests_expired": c["requests_expired"],
                         "slo_violations": c["slo_violations"]}
        return out

    def rebalance_stats(self) -> Dict:
        """Budget-rebalancer picture: splits performed + current split."""
        with self._rebalance_lock:
            n = self.rebalances
        return {"rebalances": n, "cache_budget": self.cache_budget,
                "capacities": {name: s.hps.cache_capacity
                               for name, s in self.servers.items()}}
