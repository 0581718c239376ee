"""Closed loop: ``clients`` callers, each sending its next request when its
reply arrives, for as long as the window lasts. A fixed rate cannot
overtake it, so it reads the server's capacity: ``serve_rows_per_s`` is the
prediction rows delivered inside the window over the window's length.

Request ``i`` is the ``i``-th taken by any client; its size comes from the
seed's order of the mix's fixed multiset (``pool`` sizes, reused in order
past the pool's end), its ids and dense values from the seed.
"""
import threading
import time

import serving


def run(r):
    mix = r.mix
    srv = serving.Serving(r, count=mix["pool"])
    r.setup_done()
    col = serving.Collector()
    lock = threading.Lock()
    nxt = [0]
    lat = []
    closed = threading.Event()

    def client():
        while not closed.is_set():
            with lock:
                i = nxt[0]
                nxt[0] += 1
            dense, cat = srv.requests.make(i)
            t = time.perf_counter()
            h = srv.server.submit(dense, cat)
            col.add(i, h)
            while not closed.is_set() and i not in col.done:
                time.sleep(col.poll_s)
            if i in col.done:
                lat.append((col.done[i][0] - t) * 1e3)

    hits0 = srv.l1_counts()
    with r.window() as w:
        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(mix["clients"])]
        for th in threads:
            th.start()
        time.sleep(max(0.0, w.t0 + r.seconds - time.perf_counter()))
        closed.set()
    hits1 = srv.l1_counts()
    for th in threads:
        th.join()
    col.drain(w.t1 + serving.DRAIN_S)
    ok, bad = col.answers()
    in_window = [(i, p) for i, t, p in ok if t <= w.t1]
    rows = sum(len(p) for _, p in in_window)
    r.attempted, r.failed = nxt[0], len(bad)
    r.e2e["serve_rows_per_s"] = rows / w.seconds
    r.e2e["serve_p95_ms"] = serving.percentile(
        lat + [float("inf")] * len(bad), 95)
    r.layer.update(serving.layer_record(r, rows, w.seconds, hits0, hits1))
    r.read_memory()
    r.checks.update(srv.check([(i, p) for i, _, p in ok], len(bad)))
    srv.close()
