"""Collective self-tests on the 8-device CPU mesh — the analog of the
reference's comms self-test kernels invoked from Python
(comms/comms_test.hpp via raft-dask comms_utils.pyx:78-244,
test_comms.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from raft_tpu.comms import Comms, local_handle, sharded_knn, sharded_pairwise_distance
from tests.oracles import eval_recall, naive_knn, naive_pairwise


def _run(mesh, fn, in_specs, out_specs, *args):
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    )(*args)


def test_allreduce(eight_device_mesh):
    comms = Comms(eight_device_mesh)
    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)

    out = _run(eight_device_mesh, lambda s: comms.allreduce(s), (P("shard", None),), P("shard", None), x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 28.0))


def test_bcast_and_barrier(eight_device_mesh):
    comms = Comms(eight_device_mesh)
    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)

    def f(s):
        comms.barrier()
        return comms.bcast(s, root=3)

    out = _run(eight_device_mesh, f, (P("shard", None),), P("shard", None), x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.0))


def test_allgather_reducescatter_sendrecv(eight_device_mesh):
    comms = Comms(eight_device_mesh)
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)

    def f(s):
        g = comms.allgather(s, axis=0, tiled=True)  # [8,2] on every shard
        rs = comms.reducescatter(g, scatter_axis=0)  # back to [1,2], x8
        shifted = comms.device_sendrecv(s, shift=1)
        return rs, shifted

    rs, shifted = _run(
        eight_device_mesh, f, (P("shard", None),), (P("shard", None), P("shard", None)), x
    )
    np.testing.assert_allclose(np.asarray(rs), np.asarray(x) * 8)
    np.testing.assert_allclose(np.asarray(shifted), np.roll(np.asarray(x), 1, axis=0))


def test_comm_split_rank(eight_device_mesh):
    h = local_handle(eight_device_mesh)
    assert h.comms.size == 8

    def f(s):
        return (h.comms.rank() + 0 * s[0, 0]).reshape(1, 1).astype(jnp.float32)

    out = _run(eight_device_mesh, f, (P("shard", None),), P("shard", None),
               jnp.zeros((8, 1), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out).ravel(), np.arange(8))


def test_sharded_knn(rng, eight_device_mesh):
    n, m, d, k = 800, 24, 32, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    dist, idx = sharded_knn(q, x, k, eight_device_mesh)
    _, want = naive_knn(q, x, k)
    assert eval_recall(np.asarray(idx), want) > 0.99


def test_sharded_ivf_search(rng, eight_device_mesh):
    from raft_tpu.comms import sharded_ivf_search
    from raft_tpu.neighbors import ivf_flat

    n, m, d, k = 2000, 24, 32, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    params = ivf_flat.IndexParams(
        n_lists=16, kmeans_n_iters=5, kmeans_trainset_fraction=1.0
    )
    index = ivf_flat.build(params, x)
    # full probe across shards -> exact up to list assignment: recall ~1
    sp = ivf_flat.SearchParams(
        n_probes=16, query_group=8, local_recall_target=1.0
    )
    dist, idx = sharded_ivf_search(sp, index, q, k, eight_device_mesh)
    _, want = naive_knn(q, x, k)
    assert eval_recall(np.asarray(idx), want) > 0.99


def test_sharded_pairwise(rng, eight_device_mesh):
    x = rng.standard_normal((64, 16)).astype(np.float32)
    y = rng.standard_normal((40, 16)).astype(np.float32)
    got = np.asarray(sharded_pairwise_distance(x, y, eight_device_mesh, metric="l1"))
    want = naive_pairwise(x, y, "l1")
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_sharded_ivf_pq_search(rng, eight_device_mesh):
    from raft_tpu.comms import sharded_ivf_pq_search
    from raft_tpu.neighbors import ivf_pq

    n, m, d, k = 2048, 24, 32, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    params = ivf_pq.IndexParams(
        n_lists=16, pq_dim=16, pq_bits=8, kmeans_n_iters=5,
        kmeans_trainset_fraction=1.0,
    )
    index = ivf_pq.build(params, x)
    sp = ivf_pq.SearchParams(
        n_probes=16, query_group=8, local_recall_target=1.0
    )
    dist, idx = sharded_ivf_pq_search(sp, index, q, k, eight_device_mesh)
    _, want = naive_knn(q, x, k)
    # PQ distances are approximate: recall bound mirrors test_ivf_pq
    assert eval_recall(np.asarray(idx), want) > 0.7
    # agrees with the single-device search at the same effective probes
    d1, i1 = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=16, local_recall_target=1.0),
        index, q, k)
    assert eval_recall(np.asarray(idx), np.asarray(i1)) > 0.7


@pytest.mark.parametrize("cache", ["i4", "i8raw"])
def test_sharded_ivf_pq_search_refined(rng, eight_device_mesh, cache):
    """refine_ratio>1: per-shard exact re-rank decoded from each shard's
    OWN residual-cache shard (no raw dataset anywhere in the search+refine
    path — the DEEP-1B model where the f32 dataset can never be
    resident). Recall must not drop vs the raw sharded search. The i8raw
    variant is the SHARDED_r05.json headline config in miniature
    (attach_raw_residual_cache dtype='i8', per-list scales sharded)."""
    from raft_tpu.comms import sharded_ivf_pq_search
    from raft_tpu.neighbors import ivf_pq

    n, m, d, k = 2048, 24, 32, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    params = ivf_pq.IndexParams(
        n_lists=16, pq_dim=8, pq_bits=8, kmeans_n_iters=5,
        kmeans_trainset_fraction=1.0,
        cache_dtype="i4" if cache == "i4" else "auto",
        cache_decoded=cache == "i4",
    )
    index = ivf_pq.build(params, x)
    if cache == "i8raw":
        index = ivf_pq.attach_raw_residual_cache(index, x, block_lists=5,
                                                 dtype="i8")
        assert index.cache_kind == "i8"
        assert index.cache_scales is not None
    assert index.recon_cache is not None
    sp = ivf_pq.SearchParams(
        n_probes=16, query_group=8, local_recall_target=1.0
    )
    _, raw_idx = sharded_ivf_pq_search(sp, index, q, k, eight_device_mesh)
    _, idx = sharded_ivf_pq_search(
        sp, index, q, k, eight_device_mesh, refine_ratio=4
    )
    _, want = naive_knn(q, x, k)
    r_raw = eval_recall(np.asarray(raw_idx), want)
    r_ref = eval_recall(np.asarray(idx), want)
    assert r_ref >= r_raw - 0.02
    ii = np.asarray(idx)
    assert ((ii >= 0) & (ii < n)).all()
    # matches the single-device cache-refined search's quality
    _, i1 = ivf_pq.search_refined(
        ivf_pq.SearchParams(n_probes=16, local_recall_target=1.0),
        index, q, k, refine_ratio=4)
    assert abs(eval_recall(np.asarray(i1), want) - r_ref) < 0.1


def test_sharded_cagra_build_search(rng, eight_device_mesh):
    from raft_tpu.comms import sharded_cagra_build, sharded_cagra_search
    from raft_tpu.neighbors import cagra

    centers = rng.uniform(-5, 5, (16, 32)).astype(np.float32)
    n, m, k = 4096, 32, 10
    x = (centers[rng.integers(0, 16, n)]
         + 0.7 * rng.standard_normal((n, 32))).astype(np.float32)
    q = (centers[rng.integers(0, 16, m)]
         + 0.7 * rng.standard_normal((m, 32))).astype(np.float32)
    params = cagra.IndexParams(
        intermediate_graph_degree=32, graph_degree=16, inline_codes=False)
    sidx = sharded_cagra_build(params, x, eight_device_mesh)
    assert sidx.dataset.shape[0] == 8
    sp = cagra.SearchParams(itopk_size=64)
    dist, idx = sharded_cagra_search(sp, sidx, q, k, eight_device_mesh)
    _, want = naive_knn(q, x, k)
    assert eval_recall(np.asarray(idx), want) > 0.9
    # ids must be globally offset & unique per row
    ii = np.asarray(idx)
    for r in range(ii.shape[0]):
        live = ii[r][ii[r] >= 0]
        assert len(set(live.tolist())) == len(live)
        assert live.max() < n


def test_sharded_cagra_fused_beam_parity(rng, eight_device_mesh):
    """The sharded CAGRA search runs the REAL fused Pallas beam kernel
    per shard (stacked inline tables through shard_map, interpret mode
    on the CPU mesh) and must match the scattered exact path's recall —
    VERDICT r4 #6 (previously a placeholder xla_exact fallback)."""
    from raft_tpu.comms import sharded_cagra_build, sharded_cagra_search
    from raft_tpu.neighbors import cagra

    centers = rng.uniform(-5, 5, (16, 32)).astype(np.float32)
    n, m, k = 4096, 32, 10
    x = (centers[rng.integers(0, 16, n)]
         + 0.7 * rng.standard_normal((n, 32))).astype(np.float32)
    q = (centers[rng.integers(0, 16, m)]
         + 0.7 * rng.standard_normal((m, 32))).astype(np.float32)
    params = cagra.IndexParams(
        intermediate_graph_degree=32, graph_degree=16)   # inline default
    sidx = sharded_cagra_build(params, x, eight_device_mesh)
    assert sidx.nbr_pack is not None
    assert sidx.nbr_pack.shape[0] == 8
    assert sidx.flat_codes.dtype == np.int8
    sp_x = cagra.SearchParams(itopk_size=64, scan_impl="xla")
    _, i_x = sharded_cagra_search(sp_x, sidx, q, k, eight_device_mesh)
    sp_p = cagra.SearchParams(itopk_size=64, scan_impl="pallas_interpret")
    _, i_p = sharded_cagra_search(sp_p, sidx, q, k, eight_device_mesh)
    _, want = naive_knn(q, x, k)
    r_x = eval_recall(np.asarray(i_x), want)
    r_p = eval_recall(np.asarray(i_p), want)
    assert r_x > 0.9
    # int8 traversal scoring may reorder near-ties; recall parity is the
    # contract (mirrors the single-device pallas-vs-xla parity test)
    assert r_p > r_x - 0.05, (r_p, r_x)
    ii = np.asarray(i_p)
    assert (ii < n).all()
    for r in range(ii.shape[0]):
        live = ii[r][ii[r] >= 0]
        assert len(set(live.tolist())) == len(live)


def test_sharded_ivf_build_row_search(rng, eight_device_mesh):
    from raft_tpu.comms import sharded_ivf_build, sharded_ivf_row_search
    from raft_tpu.neighbors import ivf_flat

    n, m, d, k = 4096, 24, 32, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    params = ivf_flat.IndexParams(
        n_lists=16, kmeans_n_iters=5, kmeans_trainset_fraction=1.0
    )
    sidx = sharded_ivf_build(params, x, eight_device_mesh)
    assert sidx.centers.shape[0] == 8
    # all shards share shard-0's coarse centers
    np.testing.assert_array_equal(np.asarray(sidx.centers[0]),
                                  np.asarray(sidx.centers[3]))
    sp = ivf_flat.SearchParams(
        n_probes=16, query_group=8, local_recall_target=1.0
    )
    dist, idx = sharded_ivf_row_search(sp, sidx, q, k, eight_device_mesh)
    _, want = naive_knn(q, x, k)
    assert eval_recall(np.asarray(idx), want) > 0.99


@pytest.mark.parametrize("kind", ["subspace", "cluster"])
def test_sharded_ivf_pq_build(rng, eight_device_mesh, kind):
    """Row-sharded encode under shard_map, and per-device packing of the
    owned lists, produce the same index contents as the single-device
    build given identical quantizer training data (shared quantizers ->
    identical codes/bucketing), for both codebook kinds."""
    from raft_tpu.comms import sharded_ivf_pq_build, sharded_ivf_pq_search
    from raft_tpu.neighbors import ivf_pq

    n, m, d, k = 4096, 24, 32, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    params = ivf_pq.IndexParams(
        n_lists=16, pq_dim=16, pq_bits=8, kmeans_n_iters=5,
        kmeans_trainset_fraction=1.0,
        codebook_kind=(ivf_pq.codebook_gen.PER_CLUSTER if kind == "cluster"
                       else ivf_pq.codebook_gen.PER_SUBSPACE),
    )
    got = sharded_ivf_pq_build(params, x, eight_device_mesh)
    ref = ivf_pq.build(params, x)
    for name in ("list_sizes", "codes", "indices"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(ref, name)))
    # per-device programs may sum a norm in another order
    np.testing.assert_allclose(np.asarray(got.rec_norms),
                               np.asarray(ref.rec_norms), rtol=1e-6)
    assert len(got.codes.sharding.device_set) == 8
    # and the built index searches correctly over the mesh
    sp = ivf_pq.SearchParams(n_probes=16, query_group=8,
                             local_recall_target=1.0)
    _, idx = sharded_ivf_pq_search(sp, got, q, k, eight_device_mesh)
    _, want = naive_knn(q, x, k)
    assert eval_recall(np.asarray(idx), want) > 0.7


def test_comms_session_registry(eight_device_mesh):
    """CommsSession.init/destroy + sessionId->handle registry (reference
    raft-dask Comms, raft_dask/common/comms.py:173,248,269)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from raft_tpu.comms import CommsSession, get_comm_state, session_handle

    with CommsSession(eight_device_mesh) as s1:
        s2 = CommsSession(eight_device_mesh).init()
        assert s1.sessionId != s2.sessionId
        h1 = session_handle(s1.sessionId)
        h2 = session_handle(s2.sessionId)
        assert h1 is not None and h2 is not None and h1 is not h2
        assert h1.comms.size == 8

        def f(x, _c=h1.comms):
            return _c.allreduce(x)

        y = jax.jit(shard_map(f, mesh=h1.mesh, in_specs=P("shard"),
                              out_specs=P(), check_vma=False))(jnp.ones((8,), jnp.float32))
        assert float(y[0]) == 8.0
        s2.destroy()
        assert session_handle(s2.sessionId) is None
    # context exit destroyed s1
    assert get_comm_state(None).get(s1.sessionId, {}).get("handle") is None
    # double-init warns and keeps state
    s3 = CommsSession(eight_device_mesh).init()
    import warnings as _w
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        s3.init()
    assert any("already been initialized" in str(r.message) for r in rec)
    s3.destroy()
