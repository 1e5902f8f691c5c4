"""IVF-PQ over the cell's chips through raft_tpu.comms:
``sharded_ivf_pq_build`` (quantizers trained once, each chip encodes its
row shard and holds a quarter of the lists) and ``sharded_ivf_pq_search``
(each chip scans its own lists, the chips' shortlists merge, and the
merged shortlist is reranked exactly from the rows where they lie). The
mesh is the rows' own (``x.sharding.mesh``)."""

from __future__ import annotations

ALGO = "sharded_ivf_pq"


def build(cfg: dict, x):
    from raft_tpu.comms import sharded_ivf_pq_build
    from raft_tpu.neighbors import ivf_pq

    params = ivf_pq.IndexParams(
        n_lists=int(cfg["n_lists"]), metric=cfg["metric"],
        pq_dim=int(cfg["pq_dim"]), pq_bits=int(cfg["pq_bits"]),
        kmeans_trainset_fraction=float(cfg["kmeans_trainset_fraction"]))
    return sharded_ivf_pq_build(params, x, x.sharding.mesh)


def searcher(cfg: dict, index, x):
    """The call the window makes per batch: queries -> (dists, ids)."""
    from raft_tpu.comms import sharded_ivf_pq_search
    from raft_tpu.neighbors import ivf_pq

    sp = ivf_pq.SearchParams(n_probes=int(cfg["n_probes"]))
    k, rr = int(cfg["k"]), int(cfg["refine_ratio"])
    return lambda q: sharded_ivf_pq_search(sp, index, q, k, x.sharding.mesh,
                                           refine_ratio=rr, rerank_source=x)


def scan_layout(cfg: dict, index):
    """None: ``ivf_scan_roofline``'s cost model probes the global
    ``n_probes`` nearest lists, and each chip probes a quarter of them."""
    return None
