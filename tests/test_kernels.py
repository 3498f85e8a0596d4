"""Pallas kernels vs float64 oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import treemath
import _oracle as oracle
from repro.kernels import grad_dot, ops, round_stats, weighted_agg

SHAPES = [(7,), (128,), (65536,), (1000, 333), (3, 17, 129)]
DTYPES = [jnp.float32, jnp.bfloat16]
# padding edges around the 128*128 block: one short, exact, one over, ragged
NS = [100, 16383, 16384, 16385, 70001]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_grad_dot_stats(shape, dtype):
    a = jax.random.normal(jax.random.key(0), shape, dtype)
    b = jax.random.normal(jax.random.key(1), shape, dtype)
    got = grad_dot.grad_dot_stats(a, b)
    want = oracle.grad_dot_stats(a, b)
    rtol = 1e-3 if dtype == jnp.float32 else 2e-2
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol)


@pytest.mark.parametrize("k", [1, 4, 32])
@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_weighted_agg(k, n, dtype):
    x = jax.random.normal(jax.random.key(0), (k, n), dtype)
    w = jax.random.uniform(jax.random.key(1), (k,), jnp.float32)
    got = weighted_agg.weighted_agg(w, x, min_kernel_elems=0)
    want = oracle.weighted_agg(w, x)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=1e-2,
    )


@pytest.mark.parametrize("k", [1, 8, 32])
@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_round_stats(k, n, dtype):
    x = jax.random.normal(jax.random.key(0), (k, n), dtype)
    g = jax.random.normal(jax.random.key(1), (n,), dtype)
    got = round_stats.round_stats(x, g, min_kernel_elems=0)
    want = oracle.round_stats(x, g)
    rtol = 1e-3 if dtype == jnp.float32 else 2e-2
    for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=rtol,
                                   err_msg=name)


@pytest.mark.parametrize("n", [100, 16385])
def test_round_stats_masked(n):
    x = jax.random.normal(jax.random.key(0), (4, n), jnp.float32)
    g = jax.random.normal(jax.random.key(1), (n,), jnp.float32)
    mask = (jax.random.uniform(jax.random.key(2), (n,)) > 0.5).astype(
        jnp.float32)
    got = round_stats.round_stats(x, g, mask, min_kernel_elems=0)
    want = oracle.round_stats(x, g, mask)
    for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=1e-3,
                                   err_msg=name)
    # masked stats == stats over the masked subspace, not a rescale
    full = round_stats.round_stats(x, g, min_kernel_elems=0)
    assert not np.allclose(np.asarray(got[1]), np.asarray(full[1]))


# K values straddling the K_TILE=32 client-chunk boundary: degenerate
# single chunk, one full + one ragged chunk, exact multiples.
CHUNK_KS = [1, 33, 64]


@pytest.mark.parametrize("k", CHUNK_KS)
@pytest.mark.parametrize("n", [100, 16385])
@pytest.mark.parametrize("dtype", DTYPES)
def test_chunked_round_stats(k, n, dtype):
    """Client-axis chunking (the former MAX_K trace-time error is gone):
    ragged K + non-multiple-of-block N padding + bf16 inputs."""
    x = jax.random.normal(jax.random.key(0), (k, n), dtype)
    g = jax.random.normal(jax.random.key(1), (n,), dtype)
    got = round_stats.round_stats(x, g, min_kernel_elems=0)
    want = oracle.round_stats(x, g)
    rtol = 1e-3 if dtype == jnp.float32 else 2e-2
    for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=rtol,
                                   atol=1e-2, err_msg=name)


@pytest.mark.parametrize("k", CHUNK_KS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_chunked_weighted_agg(k, dtype):
    n = 16385  # one lane-block plus a ragged tail
    x = jax.random.normal(jax.random.key(0), (k, n), dtype)
    w = jax.random.uniform(jax.random.key(2), (k,), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(weighted_agg.weighted_agg(w, x, min_kernel_elems=0),
                   np.float32),
        np.asarray(oracle.weighted_agg(w, x), np.float32), rtol=2e-2, atol=1e-2)


@pytest.mark.parametrize("dtype", DTYPES)
def test_chunked_round_stats_masked_across_chunk_boundary(dtype):
    """A segment mask spanning both lane tiles and the K=33 ragged client
    chunk: masked stats must equal the oracle over the masked subspace."""
    k, n = 33, 33000  # > 2 lane blocks; 33 clients -> chunks of 32 + 1
    x = jax.random.normal(jax.random.key(0), (k, n), dtype)
    g = jax.random.normal(jax.random.key(1), (n,), dtype)
    # contiguous masked-out segment straddling the first block boundary,
    # as segment_mask produces for a dropped leaf
    mask = jnp.ones((n,), jnp.float32).at[16000:17000].set(0.0)
    got = round_stats.round_stats(x, g, mask, min_kernel_elems=0)
    want = oracle.round_stats(x, g, mask)
    rtol = 1e-3 if dtype == jnp.float32 else 2e-2
    for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=rtol,
                                   atol=1e-1, err_msg=name)
    # the mask must actually bite
    full = round_stats.round_stats(x, g, min_kernel_elems=0)
    assert not np.allclose(np.asarray(got[1]), np.asarray(full[1]))


# ---- int4 packed wire: fused unpack+grouped-dequant kernel parity ----
# (transport-level parity and boundary sweeps live in test_transport.py;
# these pin the KERNEL contract directly on hand-built wire buffers.)


def _int4_wire(key, k, n, gs):
    from repro import transport

    x = jax.random.normal(key, (k, n), jnp.float32)
    q = transport.quantize(x, "int4", group_size=gs)
    return q.values, q.scales


@pytest.mark.parametrize("k", CHUNK_KS)
@pytest.mark.parametrize("gs", [32, 512, 16384])
def test_round_stats_q4_kernel_chunk_and_group_boundaries(k, gs):
    """Ragged client chunks x scale groups that subdivide a kernel tile
    row (gs=32), straddle rows (gs=512), and match the whole chunk."""
    n = 16385  # one byte-tile plus a ragged logical tail (odd N)
    values, scales = _int4_wire(jax.random.key(0), k, n, gs)
    g = jax.random.normal(jax.random.key(1), (n,), jnp.float32)
    got = round_stats.round_stats_q4(values, scales, g, group_size=gs)
    want = oracle.round_stats_q4(values, scales, g, group_size=gs)
    for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=2e-3,
                                   atol=1e-2, err_msg=name)


@pytest.mark.parametrize("k", CHUNK_KS)
@pytest.mark.parametrize("gs", [32, 512, 16384])
def test_weighted_agg_q4_kernel_chunk_and_group_boundaries(k, gs):
    n = 16385
    values, scales = _int4_wire(jax.random.key(2), k, n, gs)
    w = jax.random.uniform(jax.random.key(3), (k,), jnp.float32)
    got = weighted_agg.weighted_agg_q4(w, values, scales, n=n, group_size=gs)
    want = oracle.weighted_agg_q4(w, values, scales, n=n, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=1e-3)


def test_q4_kernels_reject_packed_width_mismatch():
    """A packed buffer whose width is not ceil(n/2) is a layout bug, not
    a tolerable input — both kernels must refuse it."""
    values, scales = _int4_wire(jax.random.key(4), 2, 100, 32)
    g = jnp.ones((97,), jnp.float32)  # wrong logical width
    with pytest.raises(AssertionError):
        round_stats.round_stats_q4(values, scales, g, group_size=32)
    with pytest.raises(AssertionError):
        weighted_agg.weighted_agg_q4(jnp.ones((2,)), values, scales, n=97,
                                     group_size=32)


@pytest.mark.parametrize("mk", [0, None])
def test_round_stats_bf16_accumulates_in_f32(mk):
    # 2^14 bf16 ones: naive bf16 accumulation saturates at 256. Pinned on
    # BOTH paths — the Pallas kernel (mk=0) and the small-shape XLA
    # fallback (mk=None: 2*2^14 < SMALL_ELEMS) share the f32 contract.
    n = 1 << 14
    x = jnp.ones((2, n), jnp.bfloat16)
    g = jnp.ones((n,), jnp.bfloat16)
    dots, sqs, sqg = round_stats.round_stats(x, g, min_kernel_elems=mk)
    assert float(sqg) == float(n)
    np.testing.assert_allclose(np.asarray(dots), [n, n])
    np.testing.assert_allclose(np.asarray(sqs), [n, n])


# ---- small-shape XLA fallback (the K=8, d=1024 flat-engine cliff fix) ----


def _has_pallas_call(fn, *args, **kwargs) -> bool:
    text = str(jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args))
    return "pallas_call" in text


@pytest.mark.parametrize("k,n", [(8, 1024), (1, 70001), (4, 16384)])
def test_small_shape_fallback_matches_kernel(k, n):
    """Below SMALL_ELEMS the wrappers dispatch to XLA; both paths must
    agree to kernel-vs-oracle tolerance so the engine A/B cannot fork."""
    assert k * n < weighted_agg.SMALL_ELEMS
    x = jax.random.normal(jax.random.key(0), (k, n), jnp.float32)
    g = jax.random.normal(jax.random.key(1), (n,), jnp.float32)
    w = jax.random.uniform(jax.random.key(2), (k,), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(weighted_agg.weighted_agg(w, x)),
        np.asarray(weighted_agg.weighted_agg(w, x, min_kernel_elems=0)),
        rtol=1e-5, atol=1e-5)
    for a, b, name in zip(
            round_stats.round_stats(x, g),
            round_stats.round_stats(x, g, min_kernel_elems=0),
            ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-3, err_msg=name)


def test_small_shape_fallback_trace_time_dispatch():
    """The dispatch is trace-time: a small buffer lowers with NO
    pallas_call in the jaxpr (the cliff was the launch cost, so it must
    not merely be masked), while min_kernel_elems=0 forces the kernel."""
    x = jnp.ones((8, 1024), jnp.float32)
    g = jnp.ones((1024,), jnp.float32)
    w = jnp.ones((8,), jnp.float32)
    assert not _has_pallas_call(weighted_agg.weighted_agg, w, x)
    assert not _has_pallas_call(round_stats.round_stats, x, g)
    assert _has_pallas_call(weighted_agg.weighted_agg, w, x,
                            min_kernel_elems=0)
    assert _has_pallas_call(round_stats.round_stats, x, g,
                            min_kernel_elems=0)
    # above the threshold the kernel path is the default
    big = jnp.ones((32, 65536), jnp.float32)
    wb = jnp.ones((32,), jnp.float32)
    assert _has_pallas_call(weighted_agg.weighted_agg, wb, big)


def test_row_block_adapts_to_narrow_buffers():
    """_row_block keeps the f32 minimum sublane tile and never pads a
    narrow buffer to the full 128*128 chunk (16x waste at d=1024)."""
    for n, want in [(1, 8), (1024, 8), (1025, 16), (16384, 128),
                    (10**6, 128)]:
        assert weighted_agg._row_block(n) == want, n
    # padded width under the adaptive block stays within 2x of N
    for n in (1024, 5000, 20000, 70001):
        rows = weighted_agg._row_block(n)
        padded = -(-n // (rows * 128)) * rows * 128
        assert padded < 2 * max(n, 8 * 128)


def _tree(key, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "a": jax.random.normal(k1, (257, 33), dtype),
        "b": {"c": jax.random.normal(k2, (1000,), dtype),
              "d": jax.random.normal(k3, (4, 4, 4), dtype)},
    }


def test_ops_tree_dot_and_norms_matches_treemath():
    a, b = _tree(jax.random.key(0)), _tree(jax.random.key(1))
    got = ops.tree_dot_and_norms(a, b)
    want = treemath.tree_dot_and_norms(a, b)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-3)


def test_ops_tree_weighted_sum_matches_treemath():
    trees = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[_tree(jax.random.key(i)) for i in range(4)],
    )
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    got = ops.tree_weighted_sum(trees, w)
    want = treemath.tree_weighted_sum(trees, w)
    jax.tree.map(
        lambda g, x: np.testing.assert_allclose(
            np.asarray(g), np.asarray(x), rtol=1e-3, atol=1e-5
        ),
        got, want,
    )



@pytest.mark.parametrize("name", ["grad_dot_stats", "weighted_agg",
                                  "round_stats"])
def test_xla_reference_jits_and_matches_oracle(name):
    """`repro.kernels.ref` is the benchmarks' jitted XLA baseline: it must
    trace under jax.jit and compute what the float64 oracle computes."""
    from repro.kernels import ref

    x = jax.random.normal(jax.random.key(0), (4, 3000), jnp.float32)
    g = jax.random.normal(jax.random.key(1), (3000,), jnp.float32)
    w = jax.random.uniform(jax.random.key(2), (4,), jnp.float32)
    args = {"grad_dot_stats": (x[0], g), "weighted_agg": (w, x),
            "round_stats": (x, g)}[name]
    got = jax.jit(getattr(ref, name))(*args)
    want = getattr(oracle, name)(*args)
    for gg, ww in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww),
                                   rtol=1e-5, atol=1e-4)


def test_kernel_micro_bench_runs_tiny(capsys):
    """`python -m benchmarks.run --only kernels --tiny` end to end: every
    kernel and its jitted XLA reference emit a finite time."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import run

    run.kernel_micro(tiny=True)
    lines = capsys.readouterr().out.strip().splitlines()
    names = [ln.split(",")[0] for ln in lines]
    assert names == [f"kernel/{k}/{v}"
                     for k in ("grad_dot", "weighted_agg", "round_stats")
                     for v in ("pallas_interp", "xla_ref")], names
    assert all(np.isfinite(float(ln.split(",")[1])) for ln in lines)


@pytest.mark.parametrize("shape", [(3, 40, 136), (2, 20, 64), (300, 96),
                                   (4, 7), (50,)])
def test_leaf_stats(shape):
    """One leaf's delta statistics, (<p - q, g>, ||p - q||^2, ||g||^2),
    in row blocks of the leaf's own layout: a ragged last block, a last
    dim narrower than a lane row, and the XLA path of small leaves."""
    from repro.kernels import round_stats

    rng = np.random.default_rng(0)
    p, q, g = (rng.normal(size=shape).astype(np.float32) for _ in range(3))
    round_stats.LEAF_BLOCK_ELEMS, keep = 1024, round_stats.LEAF_BLOCK_ELEMS
    try:
        got = round_stats.leaf_stats(jnp.asarray(p), jnp.asarray(q),
                                     jnp.asarray(g), min_kernel_elems=0)
    finally:
        round_stats.LEAF_BLOCK_ELEMS = keep
    d = p.astype(np.float64) - q
    gf = g.astype(np.float64)
    want = [np.sum(d * gf), np.sum(d * d), np.sum(gf * gf)]
    np.testing.assert_allclose([float(v) for v in got], want, rtol=1e-5)
