"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode (tests/test_pallas_parity.py) checks what a kernel
computes; only the chip's compiler says whether Mosaic accepts its
block shapes, layouts and VMEM use. Each case lowers one kernel at the
widths the library really passes on SIFT-1M / DEEP-10M-scale data and
compiles it for one chip of a described ``v5e:2x2`` topology, without a
chip attached (the on-chip-measurement guide's §2 rehearsal). A kernel
that the compiler refuses fails here; nothing runs, so these say nothing
about results or speed.

The topology is described inside a module fixture and never at import:
only one process may load the TPU library, and the driver runs the
suite under several xdist workers. Keep every such case in this file.
"""

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e, with JAX's persistent cache off
    around the compiles: an entry compiled for a described chip cannot
    be read back here, and the tests must not write into the checkout."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("variant,k,dtype", [
    ("exact", 10, F32),     # brute_force.search, exact arm (f32 operands)
    ("fold", 10, BF16),     # brute_force.search(fast=True), bf16 operands
])
def test_fused_topk_compiles(chip, variant, k, dtype):
    ft = importlib.import_module("raft_tpu.ops.fused_topk")

    m, n, d = 10_000, 1_000_000, 128
    geo = ft.tile_geometry(m, n, d, k, variant, jnp.dtype(dtype).itemsize)

    def run(q, x, xn, qa):
        return ft._fused_topk_tiles(
            q, x, xn, qa, k=k, metric_kind=ft.L2, variant=variant,
            tile_q=geo["tile_q"], tile_n=geo["tile_n"], interpret=False)

    _compile(run, chip, ((m, d), dtype), ((n, d), dtype), ((n,), F32),
             ((m,), F32))


@pytest.mark.parametrize("extract", ["exact", "binned", "binned_deep",
                                     "fold"])
def test_ivf_scan_compiles(chip, extract):
    """IVF-Flat SIFT-1M list shapes: 1024 lists of capacity 1280, d=128,
    query groups of 64, k=10, bf16 queries against f32 storage."""
    ivf_scan = importlib.import_module("raft_tpu.ops.ivf_scan")

    C, cap, d, G, nb, k = 1024, 1280, 128, 64, 11_264, 10

    def run(storage, ids, sizes, bl, qv, qaux, norms):
        return ivf_scan._fused_list_scan_topk(
            storage, ids, sizes, bl, qv, qaux, norms, k=k,
            metric_kind=ivf_scan.L2, extract=extract)

    _compile(run, chip, ((C, cap, d), F32), ((C, cap), I32), ((C,), I32),
             ((nb,), I32), ((nb, G, d), BF16), ((nb, G), F32),
             ((C, cap), F32))


def test_ivf_search_keeps_scan_kernel_name_and_stage_scopes(chip):
    """The served IVF-Flat program at the serve cell's shapes (SIFT-1M
    lists of capacity 1408, a 256-query bucket, nprobe 64, k=10, the
    tombstone keep-mask): the scan is still an instruction named after
    ``_fused_list_scan_topk``, the name benchmark/metrics/kernels.json
    matches in a trace, and each stage carries its named scope."""
    import re

    ivf_flat = importlib.import_module("raft_tpu.neighbors.ivf_flat")
    from raft_tpu.distance.types import DistanceType

    m, C, cap, d, k, n_probes, n = 256, 1024, 1408, 128, 10, 64, 1_000_000
    group = ivf_flat.adaptive_query_group(m, n_probes, C, 256)

    def run(q, centers, storage, ids, sizes, norms, bits):
        return ivf_flat._ivf_search(
            q, centers, storage, ids, sizes, k, n_probes,
            int(DistanceType.L2Expanded), group, 32, n, "bf16", 0.95, 1.0,
            norms, bits, scan_impl="pallas")

    shapes = [((m, d), F32), ((C, d), F32), ((C, cap, d), F32),
              ((C, cap), I32), ((C,), I32), ((C, cap), F32),
              ((-(-n // 32),), jnp.uint32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    text = jax.jit(run).lower(*args).compile().as_text()
    assert re.search(r"^\s*%?[\w.]*_fused_list_scan_topk[\w.]* = ", text,
                     re.M)
    for scope in ("ivf.coarse", "ivf.bucketize", "ivf.scan", "ivf.merge",
                  "ivf.scan/filter.keep_mask"):
        assert f"jit(_ivf_search)/{scope}/" in text, scope


@pytest.mark.parametrize("cache", ["i8", "i4"])
def test_ivf_scan_pq_cache_compiles(chip, cache):
    """IVF-PQ DEEP-10M list shapes: 1024 lists, capacity 12288, rot 96,
    k=30 (refine_ratio 3), over the int8 decoded-residual cache and the
    packed int4 cache (8 components per u32 word, transposed)."""
    ivf_scan = importlib.import_module("raft_tpu.ops.ivf_scan")

    C, cap, d, G, nb, k = 1024, 12_288, 96, 64, 20_480, 30
    storage = (((C, cap, d), jnp.int8) if cache == "i8"
               else ((C, d // 8, cap), jnp.uint32))

    def run(st, ids, sizes, bl, qv, qaux, norms):
        return ivf_scan._fused_list_scan_topk(
            st, ids, sizes, bl, qv, qaux, norms, k=k,
            metric_kind=ivf_scan.L2, extract="binned",
            packed_i4=cache == "i4")

    _compile(run, chip, storage, ((C, cap), I32), ((C,), I32),
             ((nb,), I32), ((nb, G, d), BF16), ((nb, G), F32),
             ((C, cap), F32))


@pytest.mark.parametrize("mode", ["seed", "packed"])
def test_beam_step_compiles(chip, mode):
    """CAGRA SIFT-1M search: itopk 64, width 4, degree 32, d=128 over
    10,000 queries in lane tiles of 128 — the seeding step (pre-scored
    candidates) and the per-iteration packed-row step."""
    from raft_tpu.ops.beam_step import beam_merge_step, packed_row_layout

    L, m, deg, d, width, n_seeds = 64, 10_000, 32, 128, 4, 64
    state = [((L, m), F32), ((L, m), I32), ((L, m), I32)]
    if mode == "seed":
        def run(bd, bi, be, cd, ci):
            return beam_merge_step(bd, bi, be, cand_d=cd, cand_i=ci,
                                   width=width, g=128)

        _compile(run, chip, *state, ((n_seeds, m), F32),
                 ((n_seeds, m), I32))
    else:
        W = packed_row_layout(deg, d)[3]

        def run(bd, bi, be, qrep, pack, par):
            return beam_merge_step(bd, bi, be, qrep=qrep, pack=pack,
                                   parents=par, deg=deg, d=d, width=width,
                                   g=128)

        _compile(run, chip, *state, ((m, 4, deg * d // 4), BF16),
                 ((m, width, W), I32), ((width, m), I32))


def test_graph_local_join_compiles(chip):
    """nn-descent under cagra.build(NN_DESCENT, intermediate degree 64):
    K = 96 list slots, C = 128 sampled + 96 reverse candidates, blocks
    of 65,536 rows, d=128."""
    graph_join = importlib.import_module("raft_tpu.ops.graph_join")

    B, C, K, d = 65_536, 224, 96, 128
    tile_b = graph_join.tile_geometry(C, K, d)["tile_b"]

    def run(q, cid, cvec, cd, ci, qn, cn):
        return graph_join._graph_join_tiles(
            q, cid, cvec, cd, ci, qn, cn, ip=False, tile_b=tile_b,
            interpret=False)

    _compile(run, chip, ((B, d), F32), ((B, C), I32), ((B, C, d), F32),
             ((B, K), F32), ((B, K), I32), ((B,), F32), ((B, C), F32))


def test_plain_argmin_of_f32_compares_in_bf16(chip):
    """Pins the compiler behaviour that :func:`argmin_exact` works
    around: ``jnp.argmin``/``argmax`` of f32 compile for the TPU to a
    reduce whose value operand is bf16. When a libtpu update makes this
    fail, the workaround (utils/precision.py) can go."""
    args = [jax.ShapeDtypeStruct((4096, 256), F32, sharding=chip)]
    for op in (jnp.argmin, jnp.argmax):
        text = jax.jit(lambda x: op(x, axis=1)).lower(*args).compile(
        ).as_text()
        assert "bf16" in text


def _label_programs():
    """(name, fn, f32 arg shapes): every library program that picks an
    arg-min/max of f32 distances, at SIFT/DEEP-like widths."""
    ivf_pq = importlib.import_module("raft_tpu.neighbors.ivf_pq")
    kb = importlib.import_module("raft_tpu.cluster.kmeans_balanced")
    km = importlib.import_module("raft_tpu.cluster.kmeans")
    dist = importlib.import_module("raft_tpu.distance")
    matrix = importlib.import_module("raft_tpu.matrix")
    kbp = kb.KMeansBalancedParams(n_clusters=1024)
    return {
        "ivf_pq_encode": (lambda r, p: ivf_pq._encode_subspace(r, p, 256),
                          [(4096, 48, 2), (48, 256, 2)]),
        "kmeans_balanced_predict": (lambda c, x: kb.predict(kbp, c, x),
                                    [(1024, 96), (4096, 96)]),
        "fused_l2_nn_argmin": (lambda x, y: dist.fused_l2_nn_argmin(x, y),
                               [(4096, 128), (1024, 128)]),
        "fused_l2_nn_argmin_tiled": (
            lambda x, y: dist.fused_l2_nn_argmin(x, y, tile_n=4096),
            [(1024, 128), (16384, 128)]),
        "kmeans_predict": (lambda c, x: km.predict(1024, c, x),
                           [(1024, 128), (4096, 128)]),
        "kmeans_predict_cosine": (
            lambda c, x: km.predict(km.KMeansParams(
                n_clusters=1024, metric=km.DistanceType.CosineExpanded), c, x),
            [(1024, 128), (4096, 128)]),
        "matrix_argmin": (matrix.argmin, [(4096, 256)]),
        "matrix_argmax": (matrix.argmax, [(4096, 256)]),
    }


@pytest.mark.parametrize("name", sorted(
    ["ivf_pq_encode", "kmeans_balanced_predict", "fused_l2_nn_argmin",
     "fused_l2_nn_argmin_tiled", "kmeans_predict", "kmeans_predict_cosine",
     "matrix_argmin", "matrix_argmax"]))
def test_labels_compare_in_f32(chip, name):
    """The PQ encode, k-means labels and the public 1-NN / arg-min ops
    route through argmin_exact: their programs hold no bf16 value (a
    plain f32 argmin would, see the test above)."""
    fn, shapes = _label_programs()[name]
    args = [jax.ShapeDtypeStruct(s, F32, sharding=chip) for s in shapes]
    assert "bf16" not in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("axis", [0, 1, -1])
def test_argmin_exact_matches_numpy(axis):
    from raft_tpu.utils.precision import argmax_exact, argmin_exact

    x = jnp.asarray([[3.0, 1.0, 1.0, 2.0], [0.5, 0.5, 4.0, -1.0],
                     [1.0, 1.0 + 2e-7, 9.0, 1.0]], jnp.float32)
    xn = jax.device_get(x)
    import numpy as np

    np.testing.assert_array_equal(argmin_exact(x, axis), xn.argmin(axis))
    np.testing.assert_array_equal(argmax_exact(x, axis), xn.argmax(axis))
