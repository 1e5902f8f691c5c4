"""IVF-PQ through its public entry points: ``ivf_pq.build`` and
``ivf_pq.search_refined`` (the plan compiler's refined pipeline: a
first-stage scan of the compressed lists, then an exact rerank of the
shortlist from the resident rows)."""

from __future__ import annotations

import numpy as np

ALGO = "ivf_pq"


def build(cfg: dict, x):
    from raft_tpu.neighbors import ivf_pq

    params = ivf_pq.IndexParams(
        n_lists=int(cfg["n_lists"]), metric=cfg["metric"],
        pq_dim=int(cfg["pq_dim"]), pq_bits=int(cfg["pq_bits"]),
        kmeans_trainset_fraction=float(cfg["kmeans_trainset_fraction"]))
    return ivf_pq.build(params, x, batch_size=int(cfg["build_batch_rows"]))


def search_params(cfg: dict):
    from raft_tpu.neighbors import ivf_pq

    return ivf_pq.SearchParams(n_probes=int(cfg["n_probes"]))


def searcher(cfg: dict, index, x):
    """The call the window makes per batch: queries -> (dists, ids)."""
    from raft_tpu.neighbors import ivf_pq

    sp, k, rr = search_params(cfg), int(cfg["k"]), int(cfg["refine_ratio"])
    return lambda q: ivf_pq.search_refined(sp, index, q, k, refine_ratio=rr,
                                           dataset=x)


def scan_layout(cfg: dict, index) -> dict:
    """What the first-stage list scan reads, for its cost model: per list,
    its stored rows; per row, the scanned code block in the index's own
    format (the decoded-residual cache where the index has one, else the
    packed codes), its id and its norm; the width of each dot product."""
    cache = index.recon_cache
    if cache is not None and cache.dtype == np.int8:
        code_bytes, dot_dim = int(cache.shape[2]), int(cache.shape[2])
    else:
        code_bytes = int(index.codes.shape[-1] * 4)
        dot_dim = int(index.rotation.shape[0])
    return {"list_sizes": np.asarray(index.list_sizes),
            "centers": np.asarray(index.centers),
            "n_probes": int(cfg["n_probes"]),
            "dot_dim": dot_dim,
            "row_bytes": code_bytes + 4 + 4}
