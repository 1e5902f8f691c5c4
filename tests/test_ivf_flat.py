"""IVF-Flat tests — reference pattern (cpp/test/neighbors/ann_ivf_flat.cuh):
oracle = naive KNN, assertion = recall >= n_probes/n_lists-derived bound;
plus build-structure, extend, filter and serialization round-trips."""

import numpy as np
import pytest

from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import ivf_flat
from tests.oracles import eval_recall, naive_knn


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(0)
    centers = rng.uniform(-5, 5, (32, 24)).astype(np.float32)
    x = (centers[rng.integers(0, 32, 8000)]
         + 0.8 * rng.standard_normal((8000, 24))).astype(np.float32)
    q = (centers[rng.integers(0, 32, 200)]
         + 0.8 * rng.standard_normal((200, 24))).astype(np.float32)
    return x, q


def _build(x, n_lists=32, metric="sqeuclidean", **kw):
    params = ivf_flat.IndexParams(n_lists=n_lists, metric=metric,
                                  kmeans_n_iters=10, **kw)
    return ivf_flat.build(params, x)


def test_build_structure(dataset):
    x, _ = dataset
    index = _build(x)
    assert index.n_lists == 32
    assert index.size == x.shape[0]
    sizes = np.asarray(index.list_sizes)
    assert sizes.sum() == x.shape[0]
    assert sizes.min() > 0
    # every row lands in exactly one list with its own id
    _, ids = ivf_flat.reconstruct_dataset(index)
    assert sorted(ids.tolist()) == list(range(x.shape[0]))
    # stored vectors must match the source rows
    vecs, ids0 = ivf_flat.get_list_data(index, 0)
    np.testing.assert_array_equal(vecs, x[ids0])


@pytest.mark.parametrize("metric", ["sqeuclidean", "euclidean", "inner_product"])
def test_search_recall_high_probes(dataset, metric):
    x, q = dataset
    k = 10
    index = _build(x, metric=metric)
    # probing every list == exact search
    sp = ivf_flat.SearchParams(n_probes=32, query_group=64, bucket_batch=4,
                               compute_dtype="f32", local_recall_target=1.0)
    dist, idx = ivf_flat.search(sp, index, q, k)
    _, want = naive_knn(q, x, k, metric)
    assert eval_recall(np.asarray(idx), want) > 0.99


def test_search_recall_partial_probes(dataset):
    x, q = dataset
    k = 10
    index = _build(x)
    sp = ivf_flat.SearchParams(n_probes=8, query_group=64, bucket_batch=4)
    _, idx = ivf_flat.search(sp, index, q, k)
    _, want = naive_knn(q, x, k)
    # reference bound: recall >= ~n_probes/n_lists-derived; clustered data
    # with 8/32 probes lands well above 0.8
    assert eval_recall(np.asarray(idx), want) > 0.8


def test_search_distances_match_oracle(dataset):
    x, q = dataset
    k = 5
    index = _build(x)
    sp = ivf_flat.SearchParams(n_probes=32, query_group=64,
                               compute_dtype="f32", local_recall_target=1.0)
    dist, idx = ivf_flat.search(sp, index, q, k)
    d2 = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    want = np.sort(d2, axis=1)[:, :k]
    np.testing.assert_allclose(np.asarray(dist), want, rtol=1e-3, atol=1e-2)


def test_extend(dataset):
    x, q = dataset
    k = 10
    index = _build(x[:4000])
    assert index.size == 4000
    index = ivf_flat.extend(index, x[4000:])
    assert index.size == 8000
    sp = ivf_flat.SearchParams(n_probes=32, query_group=64,
                               compute_dtype="f32", local_recall_target=1.0)
    _, idx = ivf_flat.search(sp, index, q, k)
    _, want = naive_knn(q, x, k)
    assert eval_recall(np.asarray(idx), want) > 0.99


def test_prefilter(dataset):
    x, q = dataset
    k = 10
    n = x.shape[0]
    index = _build(x)
    allowed = np.zeros(n, bool)
    allowed[: n // 4] = True
    bits = Bitset.from_dense(allowed)
    sp = ivf_flat.SearchParams(n_probes=32, query_group=64,
                               compute_dtype="f32", local_recall_target=1.0)
    _, idx = ivf_flat.search(sp, index, q, k, prefilter=bits)
    idx = np.asarray(idx)
    assert (idx < n // 4).all() or ((idx == -1) | (idx < n // 4)).all()
    _, want = naive_knn(q, x[: n // 4], k)
    assert eval_recall(idx, want) > 0.99


def test_extend_then_prefilter(dataset):
    """extend × prefilter (ISSUE 5 satellite): a filter built BEFORE the
    extend still applies afterwards — default "drop" rejects the new
    rows, out_of_range="keep" admits them (tombstone semantics)."""
    from raft_tpu.neighbors.common import BitsetFilter

    x, q = dataset
    k = 10
    n_old = 4000
    index = _build(x[:n_old])
    allowed = np.zeros(n_old, bool)
    allowed[: n_old // 2] = True
    bits = Bitset.from_dense(allowed)          # narrower than the index
    index = ivf_flat.extend(index, x[n_old:])  # ids n_old..8000 appended
    sp = ivf_flat.SearchParams(n_probes=32, query_group=64,
                               compute_dtype="f32", local_recall_target=1.0)

    # default drop: only kept OLD rows can surface
    _, idx = ivf_flat.search(sp, index, q, k, prefilter=bits)
    idx = np.asarray(idx)
    assert ((idx == -1) | ((idx < n_old // 2))).all()
    _, want = naive_knn(q, x[: n_old // 2], k)
    assert eval_recall(idx, want) > 0.99

    # keep: new rows join the allowed set
    _, idx2 = ivf_flat.search(
        sp, index, q, k, prefilter=BitsetFilter(bits, out_of_range="keep"))
    idx2 = np.asarray(idx2)
    assert ((idx2 < n_old // 2) | (idx2 >= n_old)).all()
    sub = np.concatenate([np.arange(n_old // 2),
                          np.arange(n_old, x.shape[0])])
    _, want_sub = naive_knn(q, x[sub], k)
    assert eval_recall(idx2, sub[want_sub]) > 0.99


def test_prefilter_fewer_than_k_valid(dataset):
    """Restrictive filter (< k allowed points): ids at sentinel distance
    must be -1, never a filtered-out id (ADVICE r1 medium finding)."""
    x, q = dataset
    k = 10
    n = x.shape[0]
    index = _build(x)
    allowed = np.zeros(n, bool)
    allowed[:3] = True  # only 3 points pass the filter
    bits = Bitset.from_dense(allowed)
    sp = ivf_flat.SearchParams(n_probes=32, query_group=64,
                               compute_dtype="f32", local_recall_target=1.0)
    _, idx = ivf_flat.search(sp, index, q[:50], k, prefilter=bits)
    idx = np.asarray(idx)
    assert ((idx == -1) | (idx < 3)).all()
    # each query finds exactly the 3 allowed points + 7 sentinels
    assert (np.sort(idx, axis=1)[:, -3:] >= 0).all()
    assert (idx == -1).sum(axis=1).min() == k - 3


def test_cosine_partial_probe_recall():
    """Cosine metric: coarse partition and probe must share the angular
    geometry (ADVICE r1 medium finding) — partial probing keeps recall."""
    rng = np.random.default_rng(3)
    # unnormalized data with magnitude spread: L2 partitions would diverge
    # badly from cosine probes here
    dirs = rng.standard_normal((16, 24)).astype(np.float32)
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    picks = rng.integers(0, 16, 6000)
    scale = rng.uniform(0.5, 20.0, (6000, 1)).astype(np.float32)
    x = (scale * (dirs[picks] + 0.15 * rng.standard_normal((6000, 24)))
         ).astype(np.float32)
    q = (dirs[rng.integers(0, 16, 150)]
         + 0.15 * rng.standard_normal((150, 24))).astype(np.float32)
    index = _build(x, n_lists=16, metric="cosine")
    sp = ivf_flat.SearchParams(n_probes=4, query_group=64,
                               compute_dtype="f32", local_recall_target=1.0)
    _, idx = ivf_flat.search(sp, index, q, 10)
    _, want = naive_knn(q, x, 10, "cosine")
    assert eval_recall(np.asarray(idx), want) > 0.9


def test_small_k_exceeding_list(dataset):
    x, q = dataset
    index = _build(x, n_lists=32)
    cap = index.storage.shape[1]
    # k bigger than any single list but within n_probes * cap
    k = min(2 * cap, 512)
    sp = ivf_flat.SearchParams(n_probes=32, query_group=64,
                               compute_dtype="f32", local_recall_target=1.0)
    _, idx = ivf_flat.search(sp, index, q[:20], k)
    _, want = naive_knn(q[:20], x, k)
    assert eval_recall(np.asarray(idx), want) > 0.99


@pytest.mark.parametrize("metric", ["sqeuclidean", "inner_product", "cosine"])
def test_pallas_scan_interpret_matches_xla(dataset, metric):
    """The fused Pallas list-scan kernel (interpret mode on CPU) must agree
    with the XLA bucketized scan."""
    x, q = dataset
    k = 10
    index = _build(x, metric=metric)
    kw = dict(n_probes=8, query_group=64, bucket_batch=4,
              compute_dtype="f32", local_recall_target=1.0)
    d_x, i_x = ivf_flat.search(
        ivf_flat.SearchParams(scan_impl="xla", **kw), index, q[:50], k)
    d_p, i_p = ivf_flat.search(
        ivf_flat.SearchParams(scan_impl="pallas_interpret", **kw),
        index, q[:50], k)
    agree = np.mean(np.asarray(i_x) == np.asarray(i_p))
    assert agree > 0.95  # ties may reorder; ids must essentially match
    np.testing.assert_allclose(
        np.asarray(d_x), np.asarray(d_p), rtol=2e-2, atol=2e-2
    )


def test_pallas_scan_interpret_filter(dataset):
    """Filter fused into the Pallas kernel keeps the bitset contract."""
    x, q = dataset
    k, n = 10, dataset[0].shape[0]
    index = _build(x)
    allowed = np.zeros(n, bool)
    allowed[: n // 4] = True
    bits = Bitset.from_dense(allowed)
    sp = ivf_flat.SearchParams(n_probes=32, query_group=64,
                               compute_dtype="f32", local_recall_target=1.0,
                               scan_impl="pallas_interpret")
    _, idx = ivf_flat.search(sp, index, q[:50], k, prefilter=bits)
    idx = np.asarray(idx)
    assert ((idx == -1) | (idx < n // 4)).all()
    _, want = naive_knn(q[:50], x[: n // 4], k)
    assert eval_recall(idx, want) > 0.99


def test_serialize_roundtrip(dataset, tmp_path):
    x, q = dataset
    index = _build(x)
    p = str(tmp_path / "ivf.idx")
    ivf_flat.save(p, index)
    loaded = ivf_flat.load(p)
    sp = ivf_flat.SearchParams(n_probes=8, query_group=64)
    d1, i1 = ivf_flat.search(sp, index, q, 10)
    d2, i2 = ivf_flat.search(sp, loaded, q, 10)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


def test_build_without_data_then_extend(dataset):
    x, q = dataset
    params = ivf_flat.IndexParams(n_lists=32, kmeans_n_iters=10,
                                  add_data_on_build=False)
    index = ivf_flat.build(params, x)
    assert index.size == 0
    with pytest.raises(ValueError):
        ivf_flat.search(ivf_flat.SearchParams(n_probes=4), index, q, 5)
    index = ivf_flat.extend(index, x)
    assert index.size == x.shape[0]
    _, idx = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=32, query_group=64,
                              compute_dtype="f32", local_recall_target=1.0),
        index, q, 10)
    _, want = naive_knn(q, x, 10)
    assert eval_recall(np.asarray(idx), want) > 0.99


def test_search_fast_defaults(dataset):
    # default fast path: bf16 matmuls + approx per-list top-k — still high
    # recall when probing everything
    x, q = dataset
    k = 10
    index = _build(x)
    sp = ivf_flat.SearchParams(n_probes=32, query_group=64, bucket_batch=4)
    _, idx = ivf_flat.search(sp, index, q, k)
    _, want = naive_knn(q, x, k)
    assert eval_recall(np.asarray(idx), want) > 0.9


def test_pallas_binned_short_list_ids(dataset):
    """Regression: the binned (approx) extraction must emit real ids even
    when the winner sits at list column 0 and the list is shorter than
    cap (untouched bins share binpos=0 and must not leak their -1 id)."""
    x, q = dataset
    k = 10
    index = _build(x, n_lists=64)  # short, uneven lists vs padded cap
    sp = ivf_flat.SearchParams(n_probes=16, query_group=64, bucket_batch=4,
                               compute_dtype="f32",
                               local_recall_target=0.95,  # approx path
                               scan_impl="pallas_interpret")
    d, i = ivf_flat.search(sp, index, q[:50], k)
    d, i = np.asarray(d), np.asarray(i)
    assert not ((i == -1) & np.isfinite(d)).any()
    assert (i >= 0).all()  # plenty of candidates here — no -1 expected


def test_pallas_large_k_deep_binned(dataset):
    """64 < k <= 256 on the fused approx path uses the R-deep lane
    binning; its per-list loss is ~C(k,R+1)/128^R, so ids must still
    near-match the exact XLA scan."""
    x, q = dataset
    k = 100
    index = _build(x)
    kw = dict(n_probes=16, query_group=64, bucket_batch=4,
              compute_dtype="f32")
    _, i_x = ivf_flat.search(
        ivf_flat.SearchParams(scan_impl="xla", local_recall_target=1.0,
                              **kw), index, q[:30], k)
    _, i_p = ivf_flat.search(
        ivf_flat.SearchParams(scan_impl="pallas_interpret",
                              local_recall_target=0.95, **kw),
        index, q[:30], k)
    i_x, i_p = np.asarray(i_x), np.asarray(i_p)
    overlap = np.mean([
        len(set(i_x[r]) & set(i_p[r])) / k for r in range(i_x.shape[0])
    ])
    assert overlap > 0.9, overlap


def test_bf16_storage_recall(dataset):
    """storage_dtype='bf16' halves scan bytes at near-identical recall
    (the fused kernel is HBM-bound; reference's fp16 instantiation
    analog)."""
    import jax.numpy as jnp

    x, q = dataset
    k = 10
    p32 = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=10)
    pbf = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=10,
                               storage_dtype="bf16")
    i32 = ivf_flat.build(p32, x)
    ibf = ivf_flat.build(pbf, x)
    assert ibf.storage.dtype == jnp.bfloat16
    assert i32.storage.dtype == jnp.float32
    sp = ivf_flat.SearchParams(n_probes=16, query_group=64, bucket_batch=4)
    _, idx32 = ivf_flat.search(sp, i32, q, k)
    _, idxbf = ivf_flat.search(sp, ibf, q, k)
    _, want = naive_knn(q, x, k)
    r32 = eval_recall(np.asarray(idx32), want)
    rbf = eval_recall(np.asarray(idxbf), want)
    assert rbf > r32 - 0.02, (rbf, r32)


def test_bf16_storage_serialize_roundtrip(dataset, tmp_path):
    """bf16 storage survives the .npy container round trip (ml_dtypes
    bfloat16 is not a stock numpy dtype — regression guard)."""
    import jax.numpy as jnp

    x, q = dataset
    idx = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=16, storage_dtype="bf16",
                             kmeans_n_iters=5), x)
    p = str(tmp_path / "bf16.idx")
    ivf_flat.save(p, idx)
    loaded = ivf_flat.load(p)
    assert loaded.storage.dtype == jnp.bfloat16
    sp = ivf_flat.SearchParams(n_probes=16, query_group=64, bucket_batch=4)
    _, i1 = ivf_flat.search(sp, idx, q[:32], 5)
    _, i2 = ivf_flat.search(sp, loaded, q[:32], 5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


# ---------------------------------------------------------------------------
# the list scan's per-slot keep-mask, built once per filter and index
# ---------------------------------------------------------------------------


def _keep_oracle(bits_dense, ids, out_of_range):
    """Numpy keep decision per slot: the id's bit where the filter
    covers it, ``out_of_range`` past it, never a negative id."""
    n = bits_dense.shape[0]
    inside = (ids >= 0) & (ids < n)
    keep = np.where(inside, bits_dense[np.clip(ids, 0, max(n - 1, 0))],
                    out_of_range == "keep")
    return keep & (ids >= 0)


def _fresh_search(sp, index, q, k, bits, out_of_range, impl):
    """The search with a keep-mask computed afresh for this call, the
    uncached reference."""
    import jax.numpy as jnp

    from raft_tpu.neighbors.common import filter_keep

    keep = filter_keep(bits.bits, bits.n_bits, index.indices,
                       out_of_range=out_of_range).astype(jnp.int32)
    group = ivf_flat.adaptive_query_group(q.shape[0], sp.n_probes,
                                          index.n_lists, sp.query_group)
    return ivf_flat._ivf_search(
        jnp.asarray(q), index.centers, index.storage, index.indices,
        index.list_sizes, k, sp.n_probes, int(index.metric), group,
        sp.bucket_batch, sp.compute_dtype, sp.local_recall_target,
        sp.merge_recall_target, index.data_norms, keep, scan_impl=impl)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("case", ["deleted-drop", "deleted-keep",
                                  "extended-drop", "extended-keep",
                                  "all-kept"])
def test_cached_slot_keep_matches_a_fresh_mask(dataset, case, impl):
    """A filtered search through the cached per-slot mask is
    bit-identical to one with the mask computed afresh, on the first
    call (a miss) and the second (a hit), and the mask is the keep
    decision of every slot's id."""
    from raft_tpu.neighbors.common import BitsetFilter

    x, q = dataset
    q = q[:20]
    k, n = 10, x.shape[0]
    rng = np.random.default_rng(11)
    mode = "keep" if case.endswith("keep") else "drop"
    if case.startswith("extended"):
        # the filter covers the first half; the index is extended after
        index = _build(x[: n // 2])
        allowed = rng.random(n // 2) > 0.3
        bits = Bitset.from_dense(allowed)
        index = ivf_flat.extend(index, x[n // 2:])
    else:
        index = _build(x)
        allowed = (np.ones(n, bool) if case == "all-kept"
                   else rng.random(n) > 0.3)
        bits = Bitset.from_dense(allowed)
    filt = BitsetFilter(bits, out_of_range=mode)
    sp = ivf_flat.SearchParams(n_probes=8, query_group=64, bucket_batch=4,
                               compute_dtype="f32", local_recall_target=1.0,
                               scan_impl=impl)
    want_d, want_i = _fresh_search(sp, index, q, k, bits, mode, impl)
    for _ in range(2):
        d, i = ivf_flat.search(sp, index, q, k, prefilter=filt)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
        np.testing.assert_array_equal(np.asarray(d), np.asarray(want_d))
    ids = np.asarray(index.indices)
    np.testing.assert_array_equal(
        np.asarray(ivf_flat._slot_keep(filt, index)) != 0,
        _keep_oracle(allowed, ids, mode))
    got = np.asarray(i)
    assert _keep_oracle(allowed, got[got >= 0], mode).all()


@pytest.mark.parametrize("change", ["none", "set", "flip", "extend",
                                    "out_of_range"])
def test_slot_keep_cache_misses_exactly_on_a_change(dataset, change):
    """The cached mask is reused until the bitset's content version
    (``set``/``flip``), the index's slot-id array (``extend``) or the
    filter's ``out_of_range`` mode changes; the rebuilt mask is the
    fresh one."""
    import jax.numpy as jnp

    from raft_tpu.neighbors.common import BitsetFilter, filter_keep

    x, _ = dataset
    n = x.shape[0] // 2
    index = _build(x[:n])
    bits = Bitset.from_dense(np.arange(n) % 3 != 0)
    filt = BitsetFilter(bits)
    first = ivf_flat._slot_keep(filt, index)
    assert ivf_flat._slot_keep(BitsetFilter(bits), index) is first
    if change == "set":
        bits.set(jnp.asarray([1, 4]), False)
    elif change == "flip":
        bits.flip()
    elif change == "extend":
        index = ivf_flat.extend(index, x[n:])
    elif change == "out_of_range":
        filt = BitsetFilter(bits, out_of_range="keep")
    again = ivf_flat._slot_keep(filt, index)
    if change == "none":
        assert again is first
        return
    assert again is not first
    assert ivf_flat._slot_keep(filt, index) is again
    fresh = filter_keep(bits.bits, bits.n_bits, index.indices,
                        out_of_range=filt.out_of_range)
    np.testing.assert_array_equal(np.asarray(again) != 0, np.asarray(fresh))
