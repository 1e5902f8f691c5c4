"""Traffic kind ``open_loop``: independent users sending one query each.

Requests go to ``serve.Server.submit`` at ``rate_qps``, whatever the
server answers: exactly ``round(rate_qps * seconds)`` arrivals, Poisson
gaps drawn from the seed and scaled to end at ``seconds``, each carrying
one pool query drawn from the seed, with ``k`` from the configuration.
Each request is timed from when it was due until its answer arrives;
``p50_ms`` and ``p99_ms`` are nearest-rank percentiles over all of them.
After the last arrival every outstanding request gets ``drain_s`` more
seconds; a request refused, failed or never answered is a miss: it counts
above any answered latency, and as ``failed``, which ``correct`` holds to
0 (the mix's queue holds every request of a window, so a sound server
refuses none). The server's knobs are the mix's ``server``
group; it warms exactly the batch buckets of its ladder at ``k``.
"""

from __future__ import annotations

import functools
import math
import time

import numpy as np

# the latency a miss counts as, in ms: above any answered request
MISS_MS = 3.6e6


def arrivals(rate_qps: float, seconds: float, pool: int, seed: int):
    """(due offsets in s, pool indices): the same count for every seed."""
    rng = np.random.default_rng(seed)
    n = max(int(round(rate_qps * seconds)), 1)
    t = np.cumsum(rng.exponential(size=n))
    return t * (seconds / t[-1]), rng.integers(0, pool, n)


def setup(run, entry, index, x, q) -> dict:
    from raft_tpu import serve

    tr, k = run.traffic, int(run.cfg["k"])
    knobs = dict(tr["server"])
    srv = serve.Server(serve.ServeParams(max_k=k, warmup=False, **knobs))
    try:
        srv.add_index("bench", index, algo=entry.ALGO,
                      **entry.serve_kwargs(run.cfg, index, x))
        qh = np.asarray(q)
        # one block request per bucket: each dispatches that bucket's
        # program at this k, the only shapes the traffic can hit
        for b in serve.bucket_ladder(knobs["max_batch_rows"]):
            srv.submit(qh[:b], k, index="bench").result(timeout=600)
    except BaseException:
        srv.close()
        raise
    return {"srv": srv, "q": qh, "k": k}


class _Answers:
    """Where each request's answer lands, written by the future's
    callback: the load generator holds no future and no result object,
    so that its own garbage does not stall the process it measures."""

    def __init__(self, n: int, k: int):
        self.done = np.full(n, np.nan)
        self.ids = np.full((n, k), -1, np.int64)
        self.dist = np.full((n, k), np.inf, np.float32)
        self.error = np.zeros(n, bool)

    def land(self, i: int, fut) -> None:
        t = time.perf_counter()
        try:
            d, idx = fut.result()
        except Exception:  # noqa: BLE001 - a failed request is a miss
            self.error[i] = True
            return
        self.ids[i], self.dist[i] = idx[0], d[0]
        self.done[i] = t


def window(run, st: dict, seconds: float) -> dict:
    from jax.profiler import TraceAnnotation
    from raft_tpu import serve

    srv, qh, k = st["srv"], st["q"], st["k"]
    rate = float(run.traffic["rate_qps"])
    due, qi = arrivals(rate, seconds, qh.shape[0], run.seed)
    n = len(due)
    ans = _Answers(n, k)
    late = np.zeros(n)
    refused = np.zeros(n, bool)
    t0 = time.perf_counter()
    due = due + t0
    for i in range(n):
        wait = due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late[i] = time.perf_counter() - due[i]
        try:
            with TraceAnnotation("bench.submit"):
                f = srv.submit(qh[qi[i]], k, index="bench")
        except serve.Overloaded:
            refused[i] = True
            continue
        f.add_done_callback(functools.partial(ans.land, i))
        del f
    sent_s = time.perf_counter() - t0
    deadline = due[-1] + float(run.traffic["drain_s"])
    # every request still out gets until the deadline
    while (np.isnan(ans.done) & ~ans.error & ~refused).any():
        if time.perf_counter() >= deadline:
            break
        time.sleep(0.01)
    ok = np.isfinite(ans.done)
    lat = np.where(ok, (ans.done - due) * 1e3, MISS_MS)
    order = np.sort(lat)
    p99 = float(order[math.ceil(0.99 * n) - 1])
    answered = int(ok.sum())
    errors, n_refused = int(ans.error.sum()), int(refused.sum())
    lost = n - answered - n_refused - errors
    last = np.nanmax(ans.done) if answered else t0
    return {
        "readings": {"p50_ms": float(order[math.ceil(0.5 * n) - 1]),
                     "p99_ms": p99},
        "attempted": n, "failed": n - answered,
        "latency_ms": lat,
        "answers": (qi[ok], ans.ids[ok], ans.dist[ok]),
        "info": {"requests": n, "answered": answered, "refused": n_refused,
                 "errors": errors, "lost": lost,
                 "p99_ms": p99,
                 "offered_qps": n / seconds,
                 "completed_qps": answered / max(last - t0, 1e-9),
                 "send_s": sent_s,
                 "late_ms_mean": float(late.mean() * 1e3),
                 "late_ms_p99": float(np.sort(late)[math.ceil(0.99 * n) - 1]
                                      * 1e3),
                 "late_ms_max": float(late.max() * 1e3)},
    }


def close(st: dict) -> None:
    srv = st.pop("srv", None)
    if srv is not None:
        srv.close()
