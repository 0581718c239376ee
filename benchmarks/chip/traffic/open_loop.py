"""Open loop: independent users. Requests arrive at the mix's fixed rate on
a Poisson-like schedule, whatever the server is doing, and each is timed
from its scheduled arrival, so a stall also delays the requests behind it.

The window holds ``round(rate_rps * seconds)`` requests; their gaps are a
fixed multiset of exponential draws (from the mix's ``base_seed``) scaled to
end inside the window, in the seed's order. ``serve_p95_ms`` is the nearest-
rank 95th percentile over every request scheduled in the window; a failed or
lost request counts as infinitely late.
"""
import sys
import time

import numpy as np

import serving


def run(r):
    mix = r.mix
    n = max(1, round(mix["rate_rps"] * r.seconds))
    base = np.random.default_rng((mix["base_seed"], 1))
    gaps = base.exponential(1.0, n + 1)
    order = np.random.default_rng((r.seed, 5)).permutation(n + 1)
    gaps = gaps[order] * (r.seconds / gaps.sum())
    sched = np.cumsum(gaps)[:n]                 # all inside (0, seconds)
    srv = serving.Serving(r, count=n)
    reqs = [srv.requests.make(i) for i in range(n)]
    r.setup_done()
    col = serving.Collector()
    late = 0.0
    hits0 = srv.l1_counts()
    with r.window() as w:
        t0 = w.t0
        for i, (dense, cat) in enumerate(reqs):
            now = time.perf_counter() - t0
            if sched[i] > now:
                time.sleep(sched[i] - now)
            else:
                late = max(late, now - sched[i])
            col.add(i, srv.server.submit(dense, cat))
        rest = t0 + r.seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
    hits1 = srv.l1_counts()
    col.drain(w.t1 + serving.DRAIN_S)
    ok, bad = col.answers()
    lat = [(t - t0 - sched[i]) * 1e3 for i, t, _ in ok]
    lat += [float("inf")] * len(bad)
    in_window = [(i, p) for i, t, p in ok if t <= w.t1]
    rows = sum(len(p) for _, p in in_window)
    r.attempted, r.failed = n, len(bad)
    r.e2e["serve_p95_ms"] = serving.percentile(lat, 95)
    r.e2e["serve_rows_per_s"] = rows / w.seconds
    r.layer.update(serving.layer_record(r, rows, w.seconds, hits0, hits1))
    print(f"open loop: submits ran up to {late * 1e3:.1f} ms late",
          file=sys.stderr)
    r.read_memory()
    r.checks.update(srv.check([(i, p) for i, _, p in ok], len(bad)))
    srv.close()
