"""Program spans (``repro.tracing``) in a profiled CPU run: every span
the module documents appears under exactly its name, with its id tags as
stats, nested as documented; and the server's queue-wait counter."""
import glob
import re
import time

import jax
import pytest

from repro import tracing
from repro.configs.base import TrainConfig
from repro.configs.registry import RECSYS_ARCHS, reduce_recsys_for_smoke
from repro.core.hps.hps import HPS
from repro.core.hps.persistent_db import PersistentDB
from repro.data.synthetic import SyntheticCTR
from repro.launch.mesh import make_test_mesh
from repro.models.recsys.model import RecsysModel
from repro.serve.server import InferenceServer, deploy_from_training
from repro.train.trainer import Trainer

#: the id tag each span family carries
TAGS = {"server.": "group", "hps.probe": "table", "hps.miss_fetch": "table",
        "hps.device_stage": "table", "hps.l1_scatter": "rows",
        "hps.pooled_stack": "rows", "train.": "step"}


def _documented():
    return set(re.findall(r"^  ((?:server|hps|train)\.\w+) ",
                          tracing.__doc__, re.M))


def _tag(name):
    return next(v for k, v in TAGS.items() if name.startswith(k))


@pytest.fixture(scope="module")
def smoke():
    cfg = reduce_recsys_for_smoke(RECSYS_ARCHS["dlrm-criteo"])
    return cfg, make_test_mesh((1, 1))


def _server(cfg, mesh, tmp, **kw):
    with mesh:
        model = RecsysModel(cfg, mesh, global_batch=16)
        params = model.init(jax.random.PRNGKey(0))
        pdb = PersistentDB(str(tmp))
        deploy_from_training(model, params, pdb, "dlrm")
        hps = HPS("dlrm", cfg.tables, pdb, cache_capacity=64)
        dense = {k: v for k, v in params.items() if k != "embedding"}
        return InferenceServer(model, dense, hps, **kw)


@pytest.fixture(scope="module")
def events(smoke, tmp_path_factory):
    """``(name, stats, start_ns, end_ns, line)`` of every program span in
    one profile of a stream-engine server and a 2-step training run."""
    cfg, mesh = smoke
    server = _server(cfg, mesh, tmp_path_factory.mktemp("pdb"))
    model = RecsysModel(cfg, mesh, global_batch=16)
    trainer = Trainer(model, TrainConfig(learning_rate=1e-2), mesh,
                      SyntheticCTR(cfg, 16).batch,
                      ckpt_dir=str(tmp_path_factory.mktemp("ckpt")),
                      ckpt_interval=1)
    logdir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(logdir))
    try:
        server.start()
        time.sleep(0.1)                     # an idle wait before requests
        batches = [SyntheticCTR(cfg, 8, seed=i).batch(0) for i in range(4)]
        for h in [server.submit(b["dense"], b["cat"]) for b in batches]:
            assert h.get(timeout=120).shape == (8,)
        server.stop()
        server.hps.close()
        trainer.train(2)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)[-1]
    out = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    out.append((e.name[len(tracing.PREFIX):],
                                dict(e.stats), int(e.start_ns),
                                int(e.end_ns), (p, li)))
    return out


def _inside(ev, outer, events):
    """Whether ``ev`` lies in a span named ``outer`` on its own thread."""
    return any(o[0] == outer and o[4] == ev[4] and o[2] <= ev[2]
               and ev[3] <= o[3] for o in events)


def test_every_documented_span_appears_under_its_name(events):
    doc = _documented()
    assert len(doc) == 16
    assert {e[0] for e in events} == doc


def test_ids_are_stats_not_part_of_the_name(events):
    for name, stats, *_ in events:
        assert set(stats) == {_tag(name)}, name
    tables = {e[1]["table"] for e in events if e[0] == "hps.probe"}
    assert len(tables) > 1
    steps = sorted({e[1]["step"] for e in events if e[0] == "train.step"})
    assert steps == [0, 1]


def test_nesting_and_threads(events):
    by = {}
    for e in events:
        by.setdefault(e[0], []).append(e)
    scatters = by["hps.l1_scatter"]
    assert all(_inside(e, "hps.device_stage", events)
               or _inside(e, "hps.probe", events) for e in scatters)
    assert any(_inside(e, "hps.device_stage", events) for e in scatters)
    # the probes run on the HPS host workers, not on the serve loop
    loop = {e[4] for e in by["server.coalesce"]}
    assert {e[4] for e in by["hps.probe"]}.isdisjoint(loop)
    assert all(_inside(e, "hps.probe", events) for e in by["hps.miss_fetch"])
    groups = {e[1]["group"] for e in by["server.coalesce"]}
    assert {e[1]["group"] for e in by["server.dense_forward"]} <= groups
    for phase in ("data", "put_batch", "dispatch", "sync"):
        for e in by[f"train.{phase}"]:
            assert _inside(e, "train.step", events)


def test_queue_wait_counts_the_time_before_the_drain(smoke, tmp_path):
    cfg, mesh = smoke
    server = _server(cfg, mesh, tmp_path)
    batches = [SyntheticCTR(cfg, 4, seed=i).batch(0) for i in range(3)]
    handles = [server.submit(b["dense"], b["cat"]) for b in batches]
    wait = 0.3
    time.sleep(wait)                        # queued, nothing draining
    server.start()
    try:
        for h in handles:
            assert h.get(timeout=120).shape == (4,)
    finally:
        server.stop()
        server.hps.close()
    c = server.counters()
    assert c["requests_drained"] == 3
    assert c["queue_wait_s"] >= 3 * wait
    server.reset_serving_stats()
    c = server.counters()
    assert c["requests_drained"] == 0 and c["queue_wait_s"] == 0.0
