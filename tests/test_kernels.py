"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (off a TPU
the kernels run in the Pallas interpreter without being asked to)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.csr import build_csr, expand_frontier
from repro.kernels.embedding_bag import (embedding_bag, embedding_bag_ref,
                                         fixed_hot_lookup)
from repro.kernels.frontier_expand import frontier_expand_fused
from repro.kernels.frontier_pull import (frontier_pull_fused,
                                         frontier_pull_ref)
from repro.kernels.late_gather import (late_gather_pallas, late_gather_ref,
                                       materialize)
from repro.kernels.spmm_segment import (gcn_norm_spmm, spmm_segment,
                                        spmm_segment_ref)

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("r,w,p", [(8, 1, 4), (64, 37, 25), (128, 128, 200),
                                   (33, 260, 7)])
def test_late_gather_sweep(dtype, r, w, p):
    tab = jnp.asarray(RNG.standard_normal((r, w)) * 10).astype(dtype)
    pos = jnp.asarray(RNG.integers(0, r + 5, p).astype(np.int32))
    a = late_gather_pallas(tab, pos)
    b = late_gather_ref(tab, pos)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_materialize_fused_multicolumn():
    cols = {"a": jnp.asarray(RNG.standard_normal(50).astype(np.float32)),
            "b": jnp.asarray(RNG.standard_normal((50, 3)).astype(np.float32)),
            "i": jnp.arange(50, dtype=jnp.int32)}
    pos = jnp.asarray([0, 7, 49, 50, 60], jnp.int32)
    out = materialize(cols, pos, ["a", "b", "i"], use_pallas=True)
    assert out["a"].shape == (5,)
    assert out["b"].shape == (5, 3)
    assert int(out["i"][2]) == 49 and int(out["i"][3]) == 0
    ref = materialize(cols, pos, ["a", "b", "i"], use_pallas=False)
    for k in out:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-6)


@pytest.mark.parametrize("d", [1, 7, 10, 128, 200])
@pytest.mark.parametrize("weighted", [False, True])
def test_embedding_bag_sweep(d, weighted):
    r, i, b = 40, 70, 9
    tab = jnp.asarray(RNG.standard_normal((r, d)).astype(np.float32))
    idx = jnp.asarray(RNG.integers(0, r + 3, i).astype(np.int32))
    seg = jnp.asarray(RNG.integers(0, b, i).astype(np.int32))  # unsorted,
    w = jnp.asarray(RNG.standard_normal(i).astype(np.float32)) \
        if weighted else None                                   # empty bags
    a = embedding_bag(tab, idx, seg, b, w, use_pallas=True)
    ref = embedding_bag_ref(tab, idx, seg, b, w)
    np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=1e-4)


def test_embedding_bag_mean_combiner():
    tab = jnp.eye(6, dtype=jnp.float32)
    idx = jnp.asarray([0, 1, 2, 3], jnp.int32)
    seg = jnp.asarray([0, 0, 1, 1], jnp.int32)
    out = embedding_bag(tab, idx, seg, 3, combiner="mean", use_pallas=True)
    assert np.allclose(np.asarray(out[0]), [0.5, 0.5, 0, 0, 0, 0])
    assert np.allclose(np.asarray(out[2]), 0.0)


def test_fixed_hot_lookup():
    tab = jnp.asarray(RNG.standard_normal((30, 8)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, 30, (4, 5)).astype(np.int32))
    a = fixed_hot_lookup(tab, ids, use_pallas=True)
    b = fixed_hot_lookup(tab, ids, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.parametrize("n,e,d", [(10, 30, 4), (50, 200, 17),
                                   (30, 100, 128)])
def test_spmm_sweep(n, e, d):
    x = jnp.asarray(RNG.standard_normal((n, d)).astype(np.float32))
    src = jnp.asarray(RNG.integers(0, n, e).astype(np.int32))
    dst = jnp.asarray(RNG.integers(0, n, e).astype(np.int32))
    w = jnp.asarray(RNG.standard_normal(e).astype(np.float32))
    a = spmm_segment(x, src, dst, w, n, use_pallas=True)
    b = spmm_segment_ref(x, src, dst, w, n)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_spmm_sweeps_edges_in_chunks(monkeypatch):
    """Edges beyond one SMEM-sized chunk: each chunk's call adds into the
    previous chunk's sums, including a destination tile split across a
    chunk boundary, and a destination tile no edge reaches stays zero."""
    import importlib
    kernel = importlib.import_module("repro.kernels.spmm_segment.spmm_segment")
    monkeypatch.setattr(kernel, "_EDGE_CHUNK", 16)
    n, e, d = 23, 101, 3
    x = jnp.asarray(RNG.standard_normal((n, d)).astype(np.float32))
    src = jnp.asarray(RNG.integers(0, n + 2, e).astype(np.int32))
    dst = jnp.asarray(RNG.integers(0, 12, e).astype(np.int32))
    w = jnp.asarray(RNG.standard_normal(e).astype(np.float32))
    a = spmm_segment(x + 5.0, src, dst, w, n, use_pallas=True)
    b = spmm_segment_ref(x + 5.0, src, dst, w, n)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_gcn_norm_parity():
    n, e, d = 20, 80, 9
    x = jnp.asarray(RNG.standard_normal((n, d)).astype(np.float32))
    src = jnp.asarray(RNG.integers(0, n, e).astype(np.int32))
    dst = jnp.asarray(RNG.integers(0, n, e).astype(np.int32))
    a = gcn_norm_spmm(x, src, dst, n, use_pallas=True)
    b = gcn_norm_spmm(x, src, dst, n, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_frontier_kernel_property(seed):
    rng = np.random.default_rng(seed)
    v = int(rng.integers(4, 60))
    e = int(rng.integers(2, 300))
    src = jnp.asarray(rng.integers(0, v, e).astype(np.int32))
    csr = build_csr(src, v)
    f = int(rng.integers(1, 30))
    targets = jnp.asarray(rng.integers(-1, v, f).astype(np.int32))
    valid = jnp.asarray(rng.random(f) < 0.8)
    cap = int(rng.integers(8, e + 16))
    ea, ta, oa = expand_frontier(csr, targets, valid, cap)
    eb, tb, ob = frontier_expand_fused(csr, targets, valid, cap)
    np.testing.assert_array_equal(np.asarray(ea), np.asarray(eb))
    assert int(ta) == int(tb) and bool(oa) == bool(ob)


@pytest.mark.parametrize("cap", [700, 4096])
def test_frontier_kernel_spans_chunks(cap):
    """A frontier of several 512-slot chunks, with many zero-degree slots:
    each output block walks only the chunks holding its producing slots,
    and the result still equals the XLA expansion (also when the capacity
    truncates the level)."""
    rng = np.random.default_rng(7)
    v, e, f = 3000, 4000, 1500
    csr = build_csr(jnp.asarray(rng.integers(0, v // 2, e), jnp.int32), v)
    targets = jnp.asarray(rng.integers(-1, v, f), jnp.int32)
    valid = jnp.asarray(rng.random(f) < 0.9)
    ea, ta, oa = expand_frontier(csr, targets, valid, cap)
    eb, tb, ob = frontier_expand_fused(csr, targets, valid, cap)
    np.testing.assert_array_equal(np.asarray(ea), np.asarray(eb))
    assert int(ta) == int(tb) and bool(oa) == bool(ob)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_frontier_pull_kernel_property(seed):
    """The Pallas bottom-up membership kernel == the XLA reverse-CSR pull
    on random graphs, frontiers and visited sets."""
    rng = np.random.default_rng(seed)
    v = int(rng.integers(4, 60))
    e = int(rng.integers(2, 300))
    src = jnp.asarray(rng.integers(0, v, e).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, v, e).astype(np.int32))
    rcsr = build_csr(dst, v)
    frontier = jnp.asarray(rng.random(v) < 0.3)
    visited = jnp.asarray(rng.random(v) < 0.4) | frontier
    a = frontier_pull_ref(rcsr, src, dst, frontier, visited)
    b = frontier_pull_fused(rcsr, src, dst, frontier, visited)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
