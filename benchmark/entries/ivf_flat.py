"""IVF-Flat through its public entry points: ``ivf_flat.build`` and
``ivf_flat.search``, or ``serve.Server`` over the built index."""

from __future__ import annotations

import numpy as np

ALGO = "ivf_flat"


def build(cfg: dict, x):
    from raft_tpu.neighbors import ivf_flat

    return ivf_flat.build(ivf_flat.IndexParams(
        n_lists=int(cfg["n_lists"]), metric=cfg["metric"]), x)


def search_params(cfg: dict):
    from raft_tpu.neighbors import ivf_flat

    return ivf_flat.SearchParams(n_probes=int(cfg["n_probes"]),
                                 compute_dtype=cfg["compute_dtype"])


def searcher(cfg: dict, index, x):
    """The call the window makes per batch: queries -> (dists, ids)."""
    from raft_tpu.neighbors import ivf_flat

    sp, k = search_params(cfg), int(cfg["k"])
    return lambda q: ivf_flat.search(sp, index, q, k)


def serve_kwargs(cfg: dict, index, x) -> dict:
    """Keyword arguments of ``Server.add_index`` for this index."""
    return {"search_params": search_params(cfg)}


def scan_layout(cfg: dict, index) -> dict:
    """What the list scan reads, for its cost model: per list, its stored
    rows; per row, the stored vector (the index's own dtype), its id and
    its norm; the width of each distance's dot product."""
    st = index.storage
    return {"list_sizes": np.asarray(index.list_sizes),
            "centers": np.asarray(index.centers),
            "n_probes": int(cfg["n_probes"]),
            "dot_dim": int(st.shape[2]),
            "row_bytes": int(st.shape[2] * st.dtype.itemsize) + 4 + 4}
