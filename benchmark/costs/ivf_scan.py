"""Operations and bytes of the IVF list scan (``ops/ivf_scan.py``) over a
traced window, from shapes and ``list_sizes`` alone.

Per batch of queries: each (query, probed list) pair scores every stored
row of the list, one dot product of ``dot_dim`` terms (2 * dot_dim
operations); every list that some query of the batch probes is read once,
each stored row in the index's own format (``row_bytes``: code or vector,
id, norm). Padding, extraction and the choice of kernel arm are not
counted, so the count is the least the algorithm needs, whichever arm
runs. The probes are the exact ``n_probes`` nearest centers by squared L2,
computed here in plain JAX.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _coarse(q, centers):
    d = (jnp.sum(centers * centers, axis=1)[None, :]
         - 2.0 * jnp.dot(q, centers.T, precision=jax.lax.Precision.HIGHEST))
    return d


def probes(queries, centers, n_probes: int) -> np.ndarray:
    d = np.asarray(_coarse(queries, centers))
    return np.argpartition(d, n_probes - 1, axis=1)[:, :n_probes]


def batch_cost(layout: dict, batch_probes: np.ndarray) -> dict:
    sizes = np.asarray(layout["list_sizes"], np.float64)
    flops = 2.0 * layout["dot_dim"] * float(sizes[batch_probes].sum())
    touched = np.unique(batch_probes)
    return {"flops": flops,
            "bytes": float(sizes[touched].sum()) * layout["row_bytes"]}


def count(run) -> dict:
    """(flops, bytes) of every batch the traced window ran."""
    layout = run.layout
    pr = probes(run.queries, layout["centers"], layout["n_probes"])
    total = {"flops": 0.0, "bytes": 0.0}
    for qidx in run.window["batches"]:
        c = batch_cost(layout, pr[qidx])
        total["flops"] += c["flops"]
        total["bytes"] += c["bytes"]
    return total
