"""Traffic kind ``batch``: one caller in a closed loop.

Each call searches ``batch`` queries of the pool through the entry's
public search, and the ids and distances come back to the host before the
next call. The batches are ``rotations`` rotations of the pool, made on
the device in set-up, so that no two calls in a row are identical.
``qps`` is every query answered over the whole window: from the first
call to the return of the last, which may end after ``seconds``.
"""

from __future__ import annotations

import time

import numpy as np


def setup(run, entry, index, x, q) -> dict:
    import jax
    import jax.numpy as jnp

    batch, rot = int(run.traffic["batch"]), int(run.traffic["rotations"])
    pool = q.shape[0]
    shift = max(pool // rot, 1)
    qidx = [(np.arange(batch) + r * shift) % pool for r in range(rot)]
    batches = [jax.block_until_ready(q[jnp.asarray(i, jnp.int32)])
               for i in qidx]
    search = entry.searcher(run.cfg, index, x)
    # every call has one shape: one warm call compiles it
    jax.device_get(search(batches[0]))
    return {"search": search, "qidx": qidx, "batches": batches}


def window(run, st: dict, seconds: float) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    search, batches, qidx = st["search"], st["batches"], st["qidx"]
    done: list = []
    t0 = time.perf_counter()
    while True:
        r = len(done) % len(batches)
        with TraceAnnotation("bench.step"):
            out = search(batches[r])
        with TraceAnnotation("bench.fetch"):
            d, i = jax.device_get(out)
        done.append((r, d, i))
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    n = sum(len(qidx[r]) for r, _, _ in done)
    return {
        "readings": {"qps": n / elapsed},
        "attempted": n, "failed": 0,
        "answers": (np.concatenate([qidx[r] for r, _, _ in done]),
                    np.concatenate([i for _, _, i in done]),
                    np.concatenate([d for _, d, _ in done])),
        "batches": [qidx[r] for r, _, _ in done],
        "info": {"batches": len(done), "window_s": elapsed},
    }


def close(st: dict) -> None:
    st.clear()
