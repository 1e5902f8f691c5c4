"""raft_tpu — a TPU-native vector-search and ML-primitives framework.

A brand-new JAX/XLA/Pallas framework with the capabilities of RAPIDS RAFT
(reference: rhdong/raft 24.02; see SURVEY.md): pairwise distances, fused
k-selection, balanced k-means, IVF-Flat / IVF-PQ / CAGRA ANN indexes,
brute-force KNN, refine, nn-descent, sparse primitives, stats, and a
distributed layer built on JAX collectives over ICI/DCN.

Layer map (mirrors the reference's cpp/include/raft/<layer> — SURVEY.md §1):

    core       resources handle, bitset, serialization, logging, tracing
    utils      tiling/alignment math, misc device helpers
    linalg     gemm/svd/eig/qr wrappers, map/reduce/norm engines
    matrix     matrix utilities + the select_k top-k engine
    random     RNG state, make_blobs, rmat, sampling
    distance   pairwise distances (all reference metrics), fused_l2_nn, gram
    sparse     COO/CSR types, sparse linalg/distance, MST, Lanczos
    cluster    kmeans, kmeans_balanced, single_linkage, spectral
    neighbors  brute_force, ivf_flat, ivf_pq, cagra, nn_descent, refine, ...
    stats      summary stats + metrics incl. neighborhood_recall
    solver     linear assignment (LAP), label utilities
    comms      collectives facade over jax.lax/shard_map (NCCL/UCX analog)
    ops        Pallas TPU kernels for the hot paths
    bench      ANN benchmark harness (raft-ann-bench analog)
    obs        graft-scope: spans, metrics registry, flight recorder
    serve      graft-serve: online serving engine — micro-batching,
               versioned index hot-swap, tombstone mutation
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

# Persistent XLA compilation cache: where JAX_COMPILATION_CACHE_DIR (or
# the caller's own config) names one, JAX uses it and nothing is set
# here. Otherwise it lives at one fixed path inside the checkout, the
# same in every process (the path is part of the cache key), git-ignored.
if _jax.config.jax_compilation_cache_dir is None:
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

from raft_tpu.core.resources import Resources, DeviceResources

__all__ = ["Resources", "DeviceResources", "__version__"]
