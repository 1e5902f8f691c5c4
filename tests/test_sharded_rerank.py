"""The exact rerank from rows sharded by row over the mesh, the held
programs of ``sharded_ivf_pq_search`` / ``sharded_ivf_pq_build``, and the
build's list-sharded int8 cache, on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu import obs
from raft_tpu.comms import sharded
from raft_tpu.neighbors import ivf_pq, tiered
from raft_tpu.neighbors.refine import refine
from tests.oracles import eval_recall

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def _rows_sharded(x, mesh):
    return jax.device_put(x, NamedSharding(mesh, P("shard", None)))


@pytest.mark.parametrize("metric", ["sqeuclidean", "inner_product"])
@pytest.mark.parametrize("block,window", [(None, None), (256, 16)])
def test_row_sharded_rerank_is_bitwise_the_one_device_rerank(
        rng, eight_device_mesh, monkeypatch, metric, block, window):
    """Ids and distances equal DeviceSource's and refine's over the
    unsharded rows, bit for bit: with ``-1`` candidates, a shortlist
    that spans every shard, and (small blocks and windows) many steps
    of the blocked gather."""
    if block is not None:
        monkeypatch.setattr(tiered, "_SHARD_BLOCK_ROWS", block)
        monkeypatch.setattr(tiered, "_SHARD_WINDOW", window)
    n, d, c, k = 8 * 1024, 24, 40, 10
    # a query count of its own per case: the blocked gather's sizes are
    # read when the program is traced
    m = 29 if block is None else 31
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    cand = rng.integers(0, n, (m, c)).astype(np.int32)
    cand[rng.random((m, c)) < 0.15] = -1
    cand[0] = np.arange(c) * (n // c)            # every shard
    cand[1] = -1                                 # nothing valid
    src = tiered.as_source(_rows_sharded(x, eight_device_mesh))
    assert isinstance(src, tiered.RowShardedSource)
    got_d, got_i = (np.asarray(a) for a in src.rerank(q, cand, k, metric))
    want_d, want_i = (np.asarray(a) for a in tiered.DeviceSource(
        jnp.asarray(x)).rerank(q, cand, k, metric))
    ref_d, ref_i = (np.asarray(a) for a in refine(x, q, cand, k, metric))
    assert np.array_equal(got_i, want_i) and np.array_equal(got_i, ref_i)
    assert np.array_equal(got_d, want_d) and np.array_equal(got_d, ref_d)
    assert (got_i[1] == -1).all()


def test_only_rows_split_alone_over_an_axis_are_row_sharded(
        rng, eight_device_mesh):
    x = rng.standard_normal((64, 8)).astype(np.float32)
    mesh = eight_device_mesh
    for spec in (P(), P(None, "shard")):
        a = jax.device_put(x, NamedSharding(mesh, spec))
        assert type(tiered.as_source(a)) is tiered.DeviceSource
    assert type(tiered.as_source(jnp.asarray(x))) is tiered.DeviceSource
    src = tiered.as_source(_rows_sharded(x, mesh))
    assert (src.mesh, src.axis) == (mesh, "shard")


def _index(rng, n=8 * 1024, d=32):
    x = rng.standard_normal((n, d)).astype(np.float32)
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, pq_bits=8,
                                kmeans_n_iters=5,
                                kmeans_trainset_fraction=1.0)
    return x, ivf_pq.build(params, x)


def test_sharded_search_reranks_exactly_from_row_sharded_rows(
        rng, eight_device_mesh):
    """refine_ratio 10 over the row-sharded rows against exact KNN in
    float32 numpy: recall@10 of at least 0.99 (every list probed, a
    shortlist of 100 from PQ distances), and each returned distance is
    the exact distance of its returned id."""
    x, index = _index(rng)
    q = rng.standard_normal((40, x.shape[1])).astype(np.float32)
    k = 10
    sp = ivf_pq.SearchParams(n_probes=16, query_group=8,
                             local_recall_target=1.0)
    dist, idx = sharded.sharded_ivf_pq_search(
        sp, index, q, k, eight_device_mesh, refine_ratio=10,
        rerank_source=_rows_sharded(x, eight_device_mesh))
    dist, idx = np.asarray(dist), np.asarray(idx)
    d2 = ((q * q).sum(1)[:, None] + (x * x).sum(1)[None, :]
          - 2.0 * (q @ x.T))
    want = np.argsort(d2, axis=1, kind="stable")[:, :k]
    assert eval_recall(idx, want) >= 0.99
    assert ((idx >= 0) & (idx < x.shape[0])).all()
    exact = ((x[idx] - q[:, None, :]) ** 2).sum(-1)
    # the rerank scores in float32 by the expanded form (||q||^2 +
    # ||x||^2 - 2 q.x), which loses about 1e-7 of that scale to
    # cancellation; 1e-5 of it leaves room for the summation order
    scale = (q * q).sum(1)[:, None] + (x[idx] ** 2).sum(-1)
    assert (np.abs(dist - exact) <= 1e-5 * scale).all()


def test_a_repeat_call_of_the_same_shapes_traces_nothing(
        rng, eight_device_mesh):
    x, index = _index(rng)
    xs = _rows_sharded(x, eight_device_mesh)
    sp = ivf_pq.SearchParams(n_probes=16, query_group=8,
                             local_recall_target=1.0)
    q1, q2 = (rng.standard_normal((24, x.shape[1])).astype(np.float32)
              for _ in range(2))
    traced = []

    def on_event(event, _secs, **_kw):
        if event == TRACE_EVENT:
            traced.append(event)

    obs.set_mode("on")
    try:
        first = sharded.sharded_ivf_pq_search(
            sp, index, q1, 10, eight_device_mesh, refine_ratio=10,
            rerank_source=xs)
        jax.block_until_ready(first)
        obs.reset()
        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            again = sharded.sharded_ivf_pq_search(
                sp, index, q2, 10, eight_device_mesh, refine_ratio=10,
                rerank_source=xs)
            jax.block_until_ready(again)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        counts = obs.snapshot(runtime_gauges=False)["metrics"]
    finally:
        obs.set_mode(None)
    assert traced == []
    hits = counts["sharded.program_hits"]["points"]
    assert sum(p["value"] for p in hits) == 1.0
    assert "sharded.program_misses" not in counts


def test_held_programs_are_bounded(monkeypatch):
    monkeypatch.setattr(sharded, "_held", type(sharded._held)())
    made = []
    for key in range(sharded._HELD_MAX + 3):
        sharded._hold(("t", key), lambda: made.append(1) or len(made))
    assert len(sharded._held) == sharded._HELD_MAX
    # the newest are kept, and a held key is not made again
    assert sharded._hold(("t", sharded._HELD_MAX + 2), lambda: -1) == \
        sharded._HELD_MAX + 3
    assert sharded._hold(("t", 0), lambda: -1) == -1


@pytest.mark.parametrize("kind", ["subspace", "cluster"])
def test_the_sharded_build_cache_is_list_sharded_and_exact(
        rng, eight_device_mesh, kind):
    """The int8 decoded-residual cache comes back sharded by list and
    equals ``ivf_pq._attach_cache`` on the same codes on one device."""
    n, d = 4096, 32
    x = rng.standard_normal((n, d)).astype(np.float32)
    params = ivf_pq.IndexParams(
        n_lists=16, pq_dim=16, pq_bits=8, kmeans_n_iters=5,
        kmeans_trainset_fraction=1.0,
        codebook_kind=(ivf_pq.codebook_gen.PER_CLUSTER if kind == "cluster"
                       else ivf_pq.codebook_gen.PER_SUBSPACE))
    got = sharded.sharded_ivf_pq_build(params, x, eight_device_mesh)
    assert got.cache_kind == "i8"
    sh = got.recon_cache.sharding
    assert isinstance(sh, NamedSharding)
    assert tuple(sh.spec)[0] == "shard"
    assert len(sh.device_set) == 8
    one = jax.tree_util.tree_map(lambda a: jnp.asarray(np.asarray(a)), got)
    want = ivf_pq._attach_cache(one)
    assert np.array_equal(np.asarray(got.recon_cache),
                          np.asarray(want.recon_cache))
    assert got.recon_scale == want.recon_scale
