"""Program spans on the profiler's clock.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``"repro/" + name`` whose keyword ids become the event's stats, so every
span lands in the same ``.xplane.pb`` as the device's ``XLA Modules`` and
``XLA Ops`` lines, on one clock, on the host thread that ran it. JAX's own
``backend_compile_and_load`` event nests inside the span around the call
that compiled. With no profiler session active a span records nothing and
costs about a microsecond, so the spans stay in the code unconditionally.
They sit at group, table, step or fetch granularity, never per row or per
request.

Spans, with their id tags:

Serving (``serve/server.py``); ``group`` is the server's sequence number
of a request group (-1 for a ``predict`` call made outside the stream
engine's groups); ``refresh_tick`` and ``idle_wait`` carry the latest
group coalesced:

  server.coalesce        draining the queue into one request group
  server.dense_forward   the jitted dense net's dispatch, sigmoid inside,
                         at the group's row bucket
  server.materialize     the one host sync per group and the delivery
  server.refresh_tick    bus polling and one bounded L1 refresh chunk
  server.idle_wait       the serve loop waiting for a first request

HPS (``core/hps/hps.py``, ``payload_store.py``); ``table`` is the table's
name, ``rows`` a row count:

  hps.probe              one table's host index probe, on an HPS host
                         worker in the pipelined lookups (``table``)
  hps.miss_fetch         the L2 query, L3 fetch and L2 promotion of one
                         table's misses (``table``)
  hps.device_stage       one table's deferred scatter flush and slot
                         transfer (``table``)
  hps.l1_scatter         the payload scatter, inside ``hps.device_stage``
                         or inside a probe or refresh that flushes
                         (``rows``: the scattered rows, before bucketing)
  hps.pooled_stack       the pooled-gather dispatch, the overflow fix and,
                         for callers that want ``b`` rows, the ``[:b]``
                         slice (``rows``: the group's rows)

Training (``train/trainer.py``); ``step`` is the trainer's step number,
on ``train.data`` and ``train.put_batch`` the step of the batch they build:

  train.step             one loop iteration, holding the five below
  train.dispatch         the train step's dispatch
  train.data             the host batch, ``data_fn(step)``: after the
                         dispatch, the next step's batch, built while the
                         device runs this one; before it only where no
                         batch was built ahead (a call's first step)
  train.put_batch        that batch's host-to-device transfer
  train.sync             the wait for the step's loss, after the next
                         batch is built
  train.checkpoint       handing a checkpoint to the async saver

Device scopes (``jax.named_scope``: not host spans, but the op metadata
of the device trace's ``XLA Ops``, backward ops included):

  mp.exchange            an embedding strategy's exchange
                         (``core/embedding/strategies.py``): id bucketing,
                         the all-to-alls or all-gather and reduce-scatter,
                         the owner's gather
  mp.sparse_update       the sparse optimizer's update of the tables
                         (``train/train_step.py``)

Counters (``Trainer.counters()``), combined since the trainer was built
from each step's metrics (``core/embedding/strategies.py::exchange_stats``
and its one rule, ``EXCHANGE_COUNTERS``), read with the loss at the step's
sync; a step on one device returns none, and they stay at zero:

  exchange_ids           non-padding ids an exchange routed to an owner,
                         summed over the devices (an id replicated over
                         ``model`` counts once per device of that axis)
  exchange_dropped       those that overflowed their owner's all-to-all
                         bucket and read a zero vector
  exchange_peak_load     the fullest all-to-all bucket over the mean one,
                         the largest of any step; ids drop once it passes
                         the capacity factor

Counters (``Trainer.input_counters()``), steps dispatched since the
trainer was built, kept apart from ``counters()``:

  batches_ahead          steps whose batch was built while the step
                         before ran
  batches_in_line        steps whose batch was built with no step in
                         flight: a clean call of n steps adds n - 1 and 1

Counters (``InferenceServer.counters()``), summed since the server was
made or ``reset_serving_stats`` last ran:

  queue_wait_s           over drained requests (served or shed on
                         expiry): drain time minus admission time
  requests_drained       the requests that sum covers
  rows_padded            padding rows the dense net computed: each group
                         runs at its power-of-two row bucket
  dense_shapes           distinct row counts the dense net was dispatched
                         at (a snapshot's count, not a sum)
"""
from __future__ import annotations

import jax

PREFIX = "repro/"


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span named ``PREFIX + name`` with ``ids`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)
