"""The no-overlap serving reference, for tests (not collected: no
``test_`` prefix).

Every device stage blocks before the next host stage runs: each table's
probe, then its payload scatter, then the pooled-stack dispatch, then the
dense net — nothing is left to XLA's async dispatch or to the HPS host
workers. It drives the same ``HPS`` and ``InferenceServer`` stages as the
production paths, so its answers must equal theirs bit for bit, and its
device->host syncs are many per group (the hot-path sanitizer's positive
control).
"""
import jax
import numpy as np

from repro.core.hps.hps import bucket_rows


def lookup_stage_sync(hps, cat, hotness=None):
    """``hps.lookup(cat, hotness, padded=True)``, one blocked stage at a
    time: the pooled ``[bucket_rows(B), T, D]`` block."""
    cat = np.asarray(cat)
    blocks = hps._split_query(cat, hotness)
    hps._check_dims()
    b = cat.shape[0]
    bp = bucket_rows(b)
    slot_blocks, payloads, overflow = [], [], []
    for ti in range(len(hps.tables)):
        payload = hps._collect_plan(ti, hps._probe(ti, blocks), b, bp,
                                    blocks, slot_blocks, payloads, overflow)
        jax.block_until_ready(payload)
    return jax.block_until_ready(
        hps._finalize(payloads, slot_blocks, blocks, overflow, b,
                      padded=True))


def predict_stage_sync(server, dense, cat):
    """``server.predict(dense, cat)`` with every embedding lookup and the
    dense net blocked before the next stage: the group's predictions."""
    rows = cat.shape[0]
    emb, wide, extras = server._lookups(cat, lookup_stage_sync)
    out = jax.block_until_ready(
        server._dense_forward(dense, emb, wide, extras, rows=rows))
    return np.asarray(out)[:rows]
