"""Host spans around the program's layers, for traced runs only.

The benchmark wraps a few of the program's calls in
``jax.profiler.TraceAnnotation`` so that the trace can say what the host was
doing during each idle gap of the device. The wrappers change no behaviour;
a call that a later version of the program renames is skipped with a note
on standard error.
"""
from __future__ import annotations

import functools
import importlib
import sys

import jax

#: (module, class, method, span name)
LAYER_CALLS = (
    ("repro.serve.server", "InferenceServer", "_coalesce",
     "bench/server.coalesce"),
    ("repro.serve.server", "InferenceServer", "_dense_forward",
     "bench/server.dense_forward"),
    ("repro.serve.server", "InferenceServer", "_materialize",
     "bench/server.materialize"),
    ("repro.core.hps.hps", "HPS", "_probe", "bench/hps.probe"),
    ("repro.core.hps.hps", "HPS", "_collect_plan", "bench/hps.device_stage"),
    ("repro.core.hps.hps", "HPS", "_finalize", "bench/hps.pooled_stack"),
    ("repro.train.trainer", None, "put_batch", "bench/train.put_batch"),
)


def _wrap(fn, name):
    @functools.wraps(fn)
    def inner(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return inner


def install() -> None:
    for mod_name, cls_name, attr, span in LAYER_CALLS:
        try:
            owner = importlib.import_module(mod_name)
            if cls_name is not None:
                owner = getattr(owner, cls_name)
            setattr(owner, attr, _wrap(getattr(owner, attr), span))
        except (ImportError, AttributeError) as e:
            print(f"spans: {mod_name}.{cls_name}.{attr} not wrapped: {e}",
                  file=sys.stderr)
