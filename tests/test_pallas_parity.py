"""Tier-1 Pallas kernel parity gate (marker: ``pallas_parity``).

Every kernel variant runs in INTERPRET mode on CPU against the XLA
oracle — the promotion of the round-3 parity harness
(``scripts/tpu_parity.py`` / ``PALLAS_PARITY_r03.json``) into the
always-on acceptance gate: kernel regressions fail here before a chip
ever answers. Exact arms must agree BITWISE on ids with the oracle
(identical expanded-form f32 distances feed both sides, so ranking is
deterministic up to genuine ties — absent in continuous random data);
binned/fold arms must stay inside their documented recall bands
(docs/kernels.md §candidate-buffers). The on-TPU run of the same
assertions stays in scripts/tpu_parity.py (compiled-Mosaic parity);
this module is its CPU shadow.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from raft_tpu.ops import ivf_scan
from raft_tpu.ops.fused_topk import COSINE, IP, L2, fused_topk

pytestmark = pytest.mark.pallas_parity


# ---------------------------------------------------------------------------
# fused_topk (brute-force distance + partial-top-k)
# ---------------------------------------------------------------------------


def _bf_data(rng, m=64, n=3000, d=24):
    q = rng.standard_normal((m, d)).astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    return q, x


def _l2_dist_xla(q, x):
    """Expanded-form f32 distances through the SAME XLA ops the kernel
    runs (dot_general, f32 accumulate). A numpy/BLAS matmul here would
    sum in a different order and flip near-ties — the parity gate
    compares kernel vs XLA, not kernel vs BLAS."""
    qj, xj = jnp.asarray(q), jnp.asarray(x)
    dots = jax.lax.dot_general(
        qj, xj, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    qn = jnp.sum(qj * qj, axis=1)
    xn = jnp.sum(xj * xj, axis=1)
    return jnp.maximum(qn[:, None] + xn[None, :] - 2.0 * dots, 0.0)


def _l2_oracle(q, x, k):
    """The XLA oracle: identical expanded-form f32 distances + the
    hardware top_k — what the fused kernel must reproduce bitwise."""
    _, idx = jax.lax.top_k(-_l2_dist_xla(q, x), k)
    return np.asarray(idx)


@pytest.mark.parametrize("k", [1, 10, 100])
def test_fused_topk_exact_bitwise_ids(rng, k):
    """The exact arm's contract: its j-th id carries the oracle's j-th
    distance. Ids may differ from the oracle's only between candidates
    whose oracle distances lie within f32 rounding of each other: the
    kernel's tile matmul sums in another order than the oracle's whole
    matmul, so its distances differ by an ulp or two (at k=100 on this
    data, two ids 2e-6 apart swap places). Exact ties go to the lower id
    (test_fused_topk_exact_tie_break_lower_id)."""
    q, x = _bf_data(rng)
    dist = np.asarray(_l2_dist_xla(q, x))
    want = _l2_oracle(q, x, k)
    od, oi = fused_topk(jnp.asarray(q), jnp.asarray(x), k, metric_kind=L2,
                        variant="exact", interpret=True)
    oi = np.asarray(oi)
    for row in oi:
        assert len(set(row.tolist())) == k
    got_d = np.take_along_axis(dist, oi, axis=1)
    want_d = np.take_along_axis(dist, want, axis=1)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(od), want_d, rtol=1e-6)


@pytest.mark.parametrize("k", [4, 12])
def test_fused_topk_exact_tie_break_lower_id(k):
    """Exact distance ties resolve to the lower id, within a row tile and
    across tiles: every dataset row appears three times, in tiles of 512
    rows, so each true neighbour comes with two equal copies."""
    r = np.random.default_rng(7)
    base = r.standard_normal((300, 16)).astype(np.float32)
    x = np.concatenate([base, base, base])          # ids i, i+300, i+600
    q = r.standard_normal((16, 16)).astype(np.float32)
    od, oi = fused_topk(jnp.asarray(q), jnp.asarray(x), k, metric_kind=L2,
                        variant="exact", tile_n=512, interpret=True)
    np.testing.assert_array_equal(np.asarray(oi), _l2_oracle(q, x, k))


@pytest.mark.parametrize("k", [10, 200])
def test_fused_topk_fold_recall_band(rng, k):
    q, x = _bf_data(rng)
    want = _l2_oracle(q, x, k)
    od, oi = fused_topk(jnp.asarray(q), jnp.asarray(x), k, metric_kind=L2,
                        variant="fold", interpret=True)
    oi = np.asarray(oi)
    hits = np.mean([len(np.intersect1d(oi[i], want[i])) / k
                    for i in range(oi.shape[0])])
    # fold's per-tile loss bound is C(k, R+1)/128^R per tile — far
    # inside 1% at these shapes (the binned-path band tpu_parity uses)
    assert hits > 0.99, hits


@pytest.mark.parametrize("metric_kind", [IP, COSINE])
def test_fused_topk_ip_cosine_vs_oracle(rng, metric_kind):
    q, x = _bf_data(rng)
    k = 10
    qj, xj = jnp.asarray(q), jnp.asarray(x)
    dots = jax.lax.dot_general(
        qj, xj, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if metric_kind == IP:
        _, want = jax.lax.top_k(dots, k)
    else:
        qn = jnp.linalg.norm(qj, axis=1)[:, None]
        xn = jnp.linalg.norm(xj, axis=1)[None, :]
        cos = 1.0 - dots / jnp.maximum(qn * xn, 1e-30)
        _, want = jax.lax.top_k(-cos, k)
    od, oi = fused_topk(jnp.asarray(q), jnp.asarray(x), k,
                        metric_kind=metric_kind, variant="exact",
                        interpret=True)
    oi = np.asarray(oi)
    want = np.asarray(want)
    hits = np.mean([len(np.intersect1d(oi[i], want[i])) / k
                    for i in range(oi.shape[0])])
    # set-recall, not bitwise: the kernel's epilogue arithmetic
    # (fma order) may legitimately differ from the oracle's at ulp
    # scale for the division-based metrics; band leaves room for a
    # couple of boundary near-tie flips across the 640 ids
    assert hits > 0.99, hits


def test_fused_topk_pad_rows_never_selected(rng):
    """Row-tile padding (n not a multiple of tile_n) is masked to +inf
    in-kernel: pad ids must never reach the output, and rows short of k
    valid candidates return (-1, +inf)."""
    q, x = _bf_data(rng, m=16, n=700, d=16)
    od, oi = fused_topk(jnp.asarray(q), jnp.asarray(x), 10, metric_kind=L2,
                        variant="exact", tile_n=512, interpret=True)
    oi = np.asarray(oi)
    assert oi.max() < 700
    assert oi.min() >= 0          # 700 >= k: every slot fills

    # k > valid candidates per tile pool cannot happen (k <= n enforced
    # upstream), but short FINAL output is the n == k edge:
    od, oi = fused_topk(jnp.asarray(q), jnp.asarray(x[:10]), 10,
                        metric_kind=L2, variant="exact", tile_n=512,
                        interpret=True)
    assert (np.sort(np.asarray(oi), axis=1) == np.arange(10)).all()


def test_fused_topk_fold_rejects_off_lane_tile(rng):
    """Regression (r6, graft-kern dogfood): an explicit non-lane-
    multiple tile_n reached the fold arm unvalidated and
    fold_lane_stacks silently DROPPED the tail columns from the
    reduction — rows in the dropped tail could never be returned."""
    q, x = _bf_data(rng, m=8, n=700, d=16)
    with pytest.raises(ValueError, match="tile_n % 128"):
        fused_topk(jnp.asarray(q), jnp.asarray(x), 10, metric_kind=L2,
                   variant="fold", tile_n=300, interpret=True)
    # exact arm is tail-masked per column, not lane-folded: any tile ok
    od, oi = fused_topk(jnp.asarray(q), jnp.asarray(x), 10,
                        metric_kind=L2, variant="exact", tile_n=300,
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(oi), _l2_oracle(q, x, 10))


def test_tile_geometry_sublane_floor_is_dtype_aware():
    """Regression (r6, found by graft-kern's computed GL016 audit): the
    query-tile floor was a flat 8, putting the bf16 fast path's q-block
    off the (16, 128) tile at m <= 8."""
    from raft_tpu.ops.fused_topk import tile_geometry

    assert tile_geometry(4, 1000, 32, 10, "exact", itemsize=4)["tile_q"] == 8
    assert tile_geometry(4, 1000, 32, 10, "exact", itemsize=2)["tile_q"] == 16
    assert tile_geometry(8, 1000, 32, 10, "fold", itemsize=2)["tile_q"] == 16
    # 1-byte operands need the (32, 128) tile (review fix, r6)
    assert tile_geometry(4, 1000, 32, 10, "exact", itemsize=1)["tile_q"] == 32
    assert tile_geometry(200, 1000, 32, 10, "exact",
                         itemsize=2)["tile_q"] == 128


def test_fused_topk_brute_force_wiring(rng):
    """The brute_force.search impl plumbing end to end on CPU: the
    fused interpret path must return the scan path's answer (same
    distances, same ids) — the package-boundary parity check."""
    from raft_tpu.neighbors import brute_force

    q, x = _bf_data(rng, m=32, n=2000, d=16)
    ix = brute_force.build(x, "sqeuclidean")
    d_s, i_s = brute_force.search(ix, q, 10, impl="scan")
    d_f, i_f = brute_force.search(ix, q, 10,
                                  impl="fused_exact:512:interpret")
    np.testing.assert_array_equal(np.asarray(i_s), np.asarray(i_f))
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_f),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused_list_scan_topk extraction arms (IVF list scan)
# ---------------------------------------------------------------------------


def _scan_workload(rng, C=4, cap=256, d=32, G=8, nb=8):
    storage = rng.standard_normal((C, cap, d)).astype(np.float32)
    ids = (np.arange(C * cap, dtype=np.int32).reshape(C, cap))
    sizes = np.full((C,), cap, np.int32)
    buckets = (np.arange(nb, dtype=np.int32) % C)
    qv = rng.standard_normal((nb, G, d)).astype(np.float32)
    return storage, ids, sizes, buckets, qv


def _scan_oracle(storage, ids, buckets, qv, k):
    """Per-(bucket, query) exact top-k over the list block, computed
    with the kernel's own expanded-form f32 arithmetic through XLA ops
    (numpy/BLAS matmuls sum in a different order and flip near-ties)."""
    nb, G, d = qv.shape
    out = np.empty((nb, G, k), np.int64)
    for b in range(nb):
        blk = storage[buckets[b]]
        dist = np.asarray(_l2_dist_xla(qv[b], blk))
        order = np.argsort(dist, axis=1, kind="stable")[:, :k]
        out[b] = ids[buckets[b]][order]
    return out


def test_list_scan_exact_bitwise_ids(rng):
    storage, ids, sizes, buckets, qv = _scan_workload(rng)
    k = 10
    want = _scan_oracle(storage, ids, buckets, qv, k)
    qj = jnp.asarray(qv)
    qaux = jnp.sum(qj * qj, axis=2)
    norms = jnp.asarray((storage ** 2).sum(2))
    od, oi = ivf_scan.fused_list_scan_topk(
        jnp.asarray(storage), jnp.asarray(ids), jnp.asarray(sizes),
        jnp.asarray(buckets), qj, qaux, norms, None,
        k=k, metric_kind=ivf_scan.L2, approx=False, interpret=True,
        extract="exact")
    np.testing.assert_array_equal(np.asarray(oi), want)


@pytest.mark.parametrize("extract", ["binned", "binned_deep", "fold"])
def test_list_scan_binned_arms_recall_band(rng, extract):
    storage, ids, sizes, buckets, qv = _scan_workload(rng)
    k = 10 if extract == "binned" else 100
    want = _scan_oracle(storage, ids, buckets, qv, k)
    qj = jnp.asarray(qv)
    qaux = jnp.sum(qj * qj, axis=2)
    norms = jnp.asarray((storage ** 2).sum(2))
    od, oi = ivf_scan.fused_list_scan_topk(
        jnp.asarray(storage), jnp.asarray(ids), jnp.asarray(sizes),
        jnp.asarray(buckets), qj, qaux, norms, None,
        k=k, metric_kind=ivf_scan.L2, approx=True, interpret=True,
        extract=extract)
    oi = np.asarray(oi)
    if extract == "fold":
        # fold emits its R*128 buffer unextracted — select here, the
        # way the caller's cross-probe merge does
        from raft_tpu.neighbors.common import merge_topk

        nb, G, kc = oi.shape
        od2, oi2 = merge_topk(np.asarray(od).reshape(nb * G, kc),
                              oi.reshape(nb * G, kc), k, True)
        oi = np.asarray(oi2).reshape(nb, G, k)
    hits = np.mean([
        len(np.intersect1d(oi[b, g], want[b, g])) / k
        for b in range(oi.shape[0]) for g in range(oi.shape[1])
    ])
    assert hits > 0.93, (extract, hits)   # tpu_parity's binned band


def test_binned_loss_model_single_home():
    """Review fix (r6): the (k-1)/256 collision-loss model lives in ONE
    place — the entry point, the contract sweep filter, and the
    microbench candidate set all call it, so they cannot drift."""
    from raft_tpu.analysis import contracts
    from raft_tpu.ops.ivf_scan import (
        DEFAULT_RECALL_TARGET,
        binned_k_cap,
        binned_loss_fits,
    )

    assert binned_k_cap() == 13                     # 0.95 default
    assert binned_loss_fits(13) and not binned_loss_fits(14)
    assert binned_k_cap(0.8) > binned_k_cap()       # looser budget
    assert binned_loss_fits(64, recall_target=0.0)  # forcing mode
    assert DEFAULT_RECALL_TARGET == 0.95
    # the contract's binned arm tracks the model, not a constant
    c = contracts.load_all()["ivf_scan"]
    arm = next(a for a in c.arms if a.get("extract") == "binned")
    assert arm["k_max"] == binned_k_cap()


def test_list_scan_binned_eligibility_is_loss_aware(rng):
    """Regression (r6, found by the kernel-contract sweep's
    lane-boundary cases): single-slot binning loses ~(k-1)/256 of each
    list's top-k, so the old flat k <= 64 eligibility admitted ~25%
    loss at k=64 against a 0.95 per-list recall target. The entry point
    now rejects the arm when the loss model exceeds the caller's
    budget; a recall_target <= 0 (the microbench racing arms for time)
    keeps it forceable."""
    storage, ids, sizes, buckets, qv = _scan_workload(rng)
    qj = jnp.asarray(qv)
    qaux = jnp.sum(qj * qj, axis=2)
    norms = jnp.asarray((storage ** 2).sum(2))
    args = (jnp.asarray(storage), jnp.asarray(ids), jnp.asarray(sizes),
            jnp.asarray(buckets), qj, qaux, norms, None)
    with pytest.raises(ValueError, match="not eligible"):
        ivf_scan.fused_list_scan_topk(
            *args, k=64, metric_kind=ivf_scan.L2, approx=True,
            interpret=True, extract="binned")
    # at the boundary the model admits (k=13: loss ~4.7% <= 5%) the
    # arm still clears the documented band
    want = _scan_oracle(storage, ids, buckets, qv, 13)
    od, oi = ivf_scan.fused_list_scan_topk(
        *args, k=13, metric_kind=ivf_scan.L2, approx=True,
        interpret=True, extract="binned")
    oi = np.asarray(oi)
    hits = np.mean([
        len(np.intersect1d(oi[b, g], want[b, g])) / 13
        for b in range(oi.shape[0]) for g in range(oi.shape[1])
    ])
    assert hits > 0.93, hits


def test_list_scan_fold_width_and_invalids(rng):
    """fold's output contract: width R*128, invalid slots (+inf, -1)."""
    storage, ids, sizes, buckets, qv = _scan_workload(rng, cap=256)
    sizes = np.full_like(sizes, 100)      # short lists -> invalid tail
    qj = jnp.asarray(qv)
    qaux = jnp.sum(qj * qj, axis=2)
    norms = jnp.asarray((storage ** 2).sum(2))
    od, oi = ivf_scan.fused_list_scan_topk(
        jnp.asarray(storage), jnp.asarray(ids), jnp.asarray(sizes),
        jnp.asarray(buckets), qj, qaux, norms, None,
        k=10, metric_kind=ivf_scan.L2, approx=True, interpret=True,
        extract="fold")
    assert od.shape[2] == 256             # R=2 lane stacks
    od, oi = np.asarray(od), np.asarray(oi)
    assert ((oi == -1) == np.isinf(od)).all()
    # 100 valid rows -> exactly 2*100=200 finite? no: lanes hold at most
    # R entries each; just require every finite id to be a live row
    live = oi[oi >= 0]
    assert (live % 256 < 100).all()


# ---------------------------------------------------------------------------
# hierarchical select_k vs the hardware top_k oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [16, 256, 1000])
def test_hierarchical_select_bitwise_vs_topk(rng, k):
    """DISTINCT values (shuffled iota — exact in f32 below 2^24): with
    no ties the hierarchical rung must agree bitwise with the hardware
    top_k on both values and ids. (Under ties top_k breaks by global
    lowest index while the hierarchical merge is only per-tile stable —
    the all-equal stability contract is pinned in test_select_k.py.)"""
    from raft_tpu.matrix.select_k import _hierarchical_topk, _select_k

    x = np.stack([rng.permutation(9000) for _ in range(8)]).astype(
        np.float32)
    x = jnp.asarray(x)
    for select_min in (True, False):
        hv, hi = _hierarchical_topk(x, k, select_min)
        tv, ti = _select_k(x, k, select_min)
        np.testing.assert_array_equal(np.asarray(hi), np.asarray(ti))
        np.testing.assert_array_equal(np.asarray(hv), np.asarray(tv))
