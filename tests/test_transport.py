"""Bidirectional wire: wire-format round trips, fused-dequant kernel
parity, end-to-end engine equivalence per (uplink, downlink) pair, and
error-feedback carry in both directions.

The transport contract (ROADMAP): transport="f32" is the reference uplink
wire format and downlink="f32" the reference broadcast; the tree engine
never reads quantized buffers directly — it dequantizes back to the
stacked tree and runs the per-leaf reference reductions. The fused
kernels (`round_stats_q{,4}`, `weighted_agg_q{,4}`) must therefore match
the dequantize-then-f32 oracles bit-for-tolerance, which makes
tree == flat == flat_sharded hold under every transport pair.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import transport
from repro.core import fl, treemath
import _oracle as oracle
from repro.kernels import round_stats, weighted_agg
from repro.launch.mesh import make_mesh
from repro.transport.quantize import CHUNK

# K values straddling the K_TILE=32 client-chunk boundary (degenerate
# single chunk / one full + ragged chunk / exact multiples), N values
# straddling the CHUNK=ROWS*LANE=16384 scale-chunk boundary.
CHUNK_KS = [1, 33, 64]
NS = [100, CHUNK + 1, 2 * CHUNK + 600]
# int4 scale-group widths: sub-(kernel-tile-row) groups (32 < 256 bytes x
# 2 nibbles — many groups per tile row), row-straddling (512), and the
# degenerate one-group-per-chunk case (== CHUNK, scales 1:1 with tiles).
GROUP_SIZES = [32, 512, CHUNK]


def _chunky(key, k, n, block=CHUNK):
    """(k, n) normal data whose per-block magnitude varies by orders of
    magnitude, so a kernel reading the WRONG scale column fails loudly."""
    x = jax.random.normal(key, (k, n), jnp.float32)
    cols = jnp.arange(n) // block
    return x * (10.0 ** (cols % 5).astype(jnp.float32))[None, :]


# ---------------------------------------------------------------- quantize


@pytest.mark.parametrize("n", NS)
def test_int8_roundtrip_error_bound(n):
    """|x - deq(quant(x))| <= scale/2 elementwise — round-to-nearest with
    s = absmax/127 never clips, so half an int8 step bounds the error."""
    x = _chunky(jax.random.key(0), 5, n)
    q = transport.quantize(x, "int8")
    assert q.values.dtype == jnp.int8
    assert q.scales.shape == (5, transport.num_chunks(n))
    err = np.abs(np.asarray(x) - np.asarray(transport.dequantize(q)))
    bound = np.repeat(np.asarray(q.scales), CHUNK, axis=1)[:, :n]
    assert np.all(err <= 0.5 * bound * (1 + 1e-6) + 1e-8)


def test_int8_zero_chunk_is_exact():
    """All-zero chunks must not divide by zero and must reconstruct zero."""
    x = jnp.zeros((2, CHUNK + 7), jnp.float32).at[1, CHUNK + 3].set(3.0)
    q = transport.quantize(x, "int8")
    np.testing.assert_array_equal(np.asarray(q.scales)[:, 0], [1.0, 1.0])
    np.testing.assert_allclose(np.asarray(transport.dequantize(q)),
                               np.asarray(x), atol=3.0 / 254)


def test_bf16_roundtrip_error_bound():
    """bf16 keeps 8 significand bits: relative error <= 2^-8."""
    x = _chunky(jax.random.key(1), 3, 2000)
    rt = transport.roundtrip(x, "bf16")
    np.testing.assert_allclose(np.asarray(rt), np.asarray(x), rtol=2.0**-8)


def test_f32_roundtrip_is_identity():
    x = _chunky(jax.random.key(2), 2, 300)
    np.testing.assert_array_equal(np.asarray(transport.roundtrip(x, "f32")),
                                  np.asarray(x))


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("gs", GROUP_SIZES)
def test_int4_roundtrip_error_bound(n, gs):
    """|x - deq(quant(x))| <= scale/2 elementwise, per GROUP: round-to-
    nearest with s = absmax(group)/7 never clips, so half an int4 step
    bounds the error."""
    x = _chunky(jax.random.key(3), 5, n, block=gs)
    q = transport.quantize(x, "int4", group_size=gs)
    assert q.values.dtype == jnp.int8
    assert q.values.shape == (5, -(-n // 2))
    assert q.scales.shape == (5, transport.num_groups(n, gs))
    assert (q.transport, q.n, q.group_size) == ("int4", n, gs)
    err = np.abs(np.asarray(x) - np.asarray(transport.dequantize(q)))
    bound = np.repeat(np.asarray(q.scales), gs, axis=1)[:, :n]
    assert np.all(err <= 0.5 * bound * (1 + 1e-6) + 1e-8)


def test_int4_zero_group_is_exact():
    """All-zero groups must not divide by zero and must reconstruct zero
    exactly (zero bytes carry nibble pairs (0, 0) under any scale)."""
    gs = 32
    x = jnp.zeros((2, 3 * gs + 7), jnp.float32).at[1, gs + 3].set(3.0)
    q = transport.quantize(x, "int4", group_size=gs)
    s = np.asarray(q.scales)
    assert s[0, 1] == 1.0 and s[1, 0] == 1.0  # untouched groups
    np.testing.assert_allclose(np.asarray(transport.dequantize(q)),
                               np.asarray(x), atol=3.0 / 14)
    np.testing.assert_array_equal(
        np.asarray(transport.dequantize(q))[0], 0.0)


def test_int4_pack_unpack_roundtrip():
    """pack_int4/unpack_int4 are exact inverses over the full [-7, 7]
    nibble range, including the sign-extension edge values."""
    q = jnp.asarray(
        np.random.default_rng(0).integers(-7, 8, size=(3, 64)), jnp.int32)
    back = transport.unpack_int4(transport.pack_int4(q))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(q))


@pytest.mark.parametrize("gs", [0, 1, 3, 7, 100, CHUNK + 2, 2 * CHUNK])
def test_int4_rejects_bad_group_size(gs):
    """Odd sizes (a byte would straddle groups), non-divisors of CHUNK
    (tiles would straddle groups), and out-of-range sizes all raise."""
    with pytest.raises(ValueError, match="group_size"):
        transport.quantize(jnp.zeros((1, 64)), "int4", group_size=gs)


def test_quantize_rejects_unknown_transport():
    with pytest.raises(ValueError, match="transport"):
        transport.quantize(jnp.zeros((1, 8)), "fp8")


def test_transport_property_and_wire_bytes():
    x = jnp.ones((4, CHUNK + 1), jnp.float32)
    assert transport.quantize(x, "int8").transport == "int8"
    assert transport.quantize(x, "bf16").transport == "bf16"
    assert transport.quantize(x, "f32").transport == "f32"
    assert transport.quantize(x, "int4").transport == "int4"
    n = CHUNK + 1  # 2 scale chunks
    assert transport.wire_bytes(4, n, "f32") == 4 * n * 4
    assert transport.wire_bytes(4, n, "bf16") == 4 * n * 2
    assert transport.wire_bytes(4, n, "int8") == 4 * n + 4 * 2 * 4
    g = transport.num_groups(n, 512)
    assert transport.wire_bytes(4, n, "int4", group_size=512) == (
        4 * -(-n // 2) + 4 * g * 4)
    # the acceptance ratios: int8 moves ~4x and int4 ~8x fewer bytes
    assert transport.wire_bytes(4, n, "f32") > 3.9 * transport.wire_bytes(
        4, n, "int8")
    ratio4 = (transport.wire_bytes(4, n, "int4")
              / transport.wire_bytes(4, n, "f32"))
    assert abs(ratio4 - 0.125) < 0.01, ratio4


def test_round_bytes_reports_both_directions():
    """`transport.round_bytes` covers the downlink too: up is the K-client
    delta uplink, down the K model broadcasts, total their sum."""
    k, n = 8, CHUNK + 1
    rb = transport.round_bytes(k, n, "int4", "int8")
    assert rb["up"] == transport.wire_bytes(k, n, "int4")
    assert rb["down"] == k * transport.wire_bytes(1, n, "int8")
    assert rb["total"] == rb["up"] + rb["down"]
    # reference downlink: f32 broadcast dominates a quantized uplink
    ref_rb = transport.round_bytes(k, n, "int4", "f32")
    assert ref_rb["down"] == k * n * 4
    assert rb["total"] < 0.5 * ref_rb["total"]
    with pytest.raises(ValueError, match="downlink"):
        transport.round_bytes(k, n, "int8", "int4")


def test_tree_unravel_stacked_roundtrip():
    """transport's tree-engine fallback: ravel -> (K, N) -> back to the
    stacked tree, original shapes and dtypes restored."""
    stacked = {
        "a": jax.random.normal(jax.random.key(0), (3, 5, 2), jnp.float32),
        "b": {"c": jax.random.normal(jax.random.key(1), (3, 7), jnp.bfloat16)},
    }
    flat, _ = treemath.tree_ravel_stacked(stacked)
    back = treemath.tree_unravel_stacked(stacked, flat)
    jax.tree.map(
        lambda x, y: (np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32), atol=1e-6),
            None)[1] or None, stacked, back)
    assert back["b"]["c"].dtype == jnp.bfloat16


# ------------------------------------------------- fused-dequant kernels


@pytest.mark.parametrize("k", CHUNK_KS)
@pytest.mark.parametrize("n", NS)
def test_round_stats_q_matches_dequant_oracle(k, n):
    """Fused in-register dequant == dequantize-then-f32 reference, across
    ragged client chunks AND chunk-boundary scales."""
    q = transport.quantize(_chunky(jax.random.key(0), k, n), "int8")
    g = jax.random.normal(jax.random.key(1), (n,), jnp.float32)
    got = round_stats.round_stats_q(q.values, q.scales, g)
    want = oracle.round_stats_q(q.values, q.scales, g)
    for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=1e-3,
                                   atol=1e-2, err_msg=name)


@pytest.mark.parametrize("k", CHUNK_KS)
@pytest.mark.parametrize("n", NS)
def test_weighted_agg_q_matches_dequant_oracle(k, n):
    q = transport.quantize(_chunky(jax.random.key(2), k, n), "int8")
    w = jax.random.uniform(jax.random.key(3), (k,), jnp.float32)
    got = weighted_agg.weighted_agg_q(w, q.values, q.scales)
    want = oracle.weighted_agg_q(w, q.values, q.scales)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3,
                               atol=1e-4)


def test_round_stats_q_masked_across_chunk_boundary():
    """Segment mask spanning the scale-chunk boundary + the K=33 ragged
    client chunk: masked fused stats == masked dequant oracle, and the
    mask must actually bite."""
    k, n = 33, 2 * CHUNK + 600
    q = transport.quantize(_chunky(jax.random.key(4), k, n), "int8")
    g = jax.random.normal(jax.random.key(5), (n,), jnp.float32)
    mask = jnp.ones((n,), jnp.float32).at[CHUNK - 500:CHUNK + 500].set(0.0)
    got = round_stats.round_stats_q(q.values, q.scales, g, mask)
    want = oracle.round_stats_q(q.values, q.scales, g, mask)
    for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=1e-3,
                                   err_msg=name)
    full = round_stats.round_stats_q(q.values, q.scales, g)
    assert not np.allclose(np.asarray(got[1]), np.asarray(full[1]))


@pytest.mark.parametrize("k", CHUNK_KS)
@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("gs", GROUP_SIZES)
def test_round_stats_q4_matches_dequant_oracle(k, n, gs):
    """int4 fused in-register unpack+dequant == dequantize-then-f32
    reference, across ragged client chunks AND group boundaries that do
    not align with kernel tile rows (gs=32 packs 16 groups per 128-byte
    row; gs=512 spans two rows; gs=CHUNK covers two tiles per group...
    exercising every scale-expansion regime)."""
    q = transport.quantize(_chunky(jax.random.key(10), k, n, block=gs),
                           "int4", group_size=gs)
    g = jax.random.normal(jax.random.key(11), (n,), jnp.float32)
    got = round_stats.round_stats_q4(q.values, q.scales, g, group_size=gs)
    want = oracle.round_stats_q4(q.values, q.scales, g, group_size=gs)
    for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=2e-3,
                                   atol=1e-2, err_msg=name)


@pytest.mark.parametrize("k", CHUNK_KS)
@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("gs", GROUP_SIZES)
def test_weighted_agg_q4_matches_dequant_oracle(k, n, gs):
    q = transport.quantize(_chunky(jax.random.key(12), k, n, block=gs),
                           "int4", group_size=gs)
    w = jax.random.uniform(jax.random.key(13), (k,), jnp.float32)
    got = weighted_agg.weighted_agg_q4(w, q.values, q.scales, n=n,
                                       group_size=gs)
    want = oracle.weighted_agg_q4(w, q.values, q.scales, n=n, group_size=gs)
    assert got.dtype == jnp.float32 and got.shape == (n,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=1e-3)


def test_round_stats_q4_masked_across_boundaries():
    """Segment mask spanning a scale-GROUP boundary, the byte-chunk
    boundary, and the K=33 ragged client chunk all at once: masked fused
    stats == masked dequant oracle, and the mask must actually bite.
    The mask edges are ODD offsets, so the masked-out span starts on a
    high nibble and ends on a low one — the even/odd mask views diverge."""
    k, n, gs = 33, 2 * CHUNK + 600, 512
    q = transport.quantize(_chunky(jax.random.key(14), k, n, block=gs),
                           "int4", group_size=gs)
    g = jax.random.normal(jax.random.key(15), (n,), jnp.float32)
    mask = jnp.ones((n,), jnp.float32).at[gs - 101:CHUNK + 501].set(0.0)
    got = round_stats.round_stats_q4(q.values, q.scales, g, mask,
                                     group_size=gs)
    want = oracle.round_stats_q4(q.values, q.scales, g, mask, group_size=gs)
    for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=2e-3,
                                   atol=1e-2, err_msg=name)
    full = round_stats.round_stats_q4(q.values, q.scales, g, group_size=gs)
    assert not np.allclose(np.asarray(got[1]), np.asarray(full[1]))


def test_q4_kernels_odd_n_tail_nibble():
    """Odd logical N: the last byte's high nibble is padding and must
    contribute exactly nothing to stats or aggregation."""
    k, n, gs = 3, 2 * CHUNK + 1, 512
    x = _chunky(jax.random.key(16), k, n, block=gs)
    q = transport.quantize(x, "int4", group_size=gs)
    g = jax.random.normal(jax.random.key(17), (n,), jnp.float32)
    w = jax.random.uniform(jax.random.key(18), (k,), jnp.float32)
    got = round_stats.round_stats_q4(q.values, q.scales, g, group_size=gs)
    want = oracle.round_stats_q4(q.values, q.scales, g, group_size=gs)
    for gg, ww in zip(got, want):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(ww), rtol=2e-3,
                                   atol=1e-2)
    ya = weighted_agg.weighted_agg_q4(w, q.values, q.scales, n=n,
                                      group_size=gs)
    yw = oracle.weighted_agg_q4(w, q.values, q.scales, n=n, group_size=gs)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yw), rtol=2e-3,
                               atol=1e-3)


def test_q4_fuzz_parity_seeded():
    """Seeded fuzz sweep over random (K, N, group_size) tuples — the
    shapes deliberately NOT hand-picked, so layout assumptions that only
    hold at the curated boundary cases fail here."""
    rng = np.random.default_rng(1234)
    pow2 = [2, 4, 8, 16, 32, 64, 128, 256, 512, 1024]
    for _ in range(6):
        k = int(rng.integers(1, 70))
        n = int(rng.integers(1, 3 * CHUNK))
        gs = int(pow2[rng.integers(0, len(pow2))])
        x = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
        q = transport.quantize(x, "int4", group_size=gs)
        g = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
        got = round_stats.round_stats_q4(q.values, q.scales, g,
                                         group_size=gs)
        want = oracle.round_stats_q4(q.values, q.scales, g, group_size=gs)
        for gg, ww, name in zip(got, want, ("dots", "sqnorms", "sqg")):
            np.testing.assert_allclose(
                np.asarray(gg), np.asarray(ww), rtol=2e-3, atol=1e-2,
                err_msg=f"{name} K={k} n={n} gs={gs}")


@pytest.mark.parametrize("k", CHUNK_KS)
def test_bf16_wire_through_plain_kernels(k):
    """bf16 transport has no scales: the plain kernels' in-VMEM astype IS
    the dequant, and out_dtype=f32 must avoid a lossy bf16 round-trip."""
    n = CHUNK + 1
    x = jax.random.normal(jax.random.key(6), (k, n), jnp.float32)
    wire = transport.quantize(x, "bf16").values
    w = jax.random.uniform(jax.random.key(7), (k,), jnp.float32)
    got = weighted_agg.weighted_agg(w, wire, out_dtype=jnp.float32)
    assert got.dtype == jnp.float32
    want = oracle.weighted_agg(w, wire.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3,
                               atol=1e-4)


# ------------------------------------------------- end-to-end transports


K = 4


def _toy_problem(K=K, tau=3, B=8, d=12, seed=0):
    rng = np.random.default_rng(seed)
    params = {"w": jnp.zeros((d, 1), jnp.float32),
              "b": jnp.zeros((1,), jnp.float32)}
    X = rng.normal(size=(K, tau, B, d)).astype(np.float32)
    w_true = rng.normal(size=(K, d, 1)).astype(np.float32)
    Y = np.einsum("ktbd,kde->ktbe", X, w_true)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    return params, loss_fn, (jnp.asarray(X), jnp.asarray(Y))


def _run(engine, transport_name, method="fedadp", rounds=3, k=K, mesh=None,
         error_feedback=False, downlink="f32", group_size=512,
         downlink_error_feedback=False, params=None):
    params0, loss_fn, batches = _toy_problem(K=k)
    params = params0 if params is None else params
    cfg = fl.FLConfig(num_clients=k, clients_per_round=k, local_steps=3,
                      method=method, engine=engine, transport=transport_name,
                      error_feedback=error_feedback, downlink=downlink,
                      group_size=group_size,
                      downlink_error_feedback=downlink_error_feedback,
                      base_lr=0.05)
    rf = jax.jit(fl.make_round_fn(loss_fn, cfg, mesh=mesh))
    st = fl.init_round_state(cfg, params)
    sel = jnp.arange(k, dtype=jnp.int32)
    sizes = jnp.asarray(10.0 * (1.0 + np.arange(k, dtype=np.float32)))
    for r in range(rounds):
        st, m = rf(st, batches, sel, sizes)
    return st.params, st.angle, m, st.ef, st.dl_ef


def _assert_trees_close(a, b, atol=1e-5):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=atol), a, b)


@pytest.mark.parametrize("uplink", list(transport.TRANSPORTS))
@pytest.mark.parametrize("downlink", list(transport.DOWNLINKS))
def test_engines_agree_per_wire_pair(uplink, downlink):
    """The acceptance pin: tree (dequantize-then-reference) == flat
    (fused-dequant kernels) == flat_sharded (1-way mesh) to 1e-5 for
    EVERY (uplink, downlink) transport pair, multi-round. int4 runs a
    sub-row scale group (32) so the grouped-dequant path is exercised."""
    gs = 32 if uplink == "int4" else 512
    mesh = make_mesh((1,), ("data",))
    p_t, s_t, m_t, _, _ = _run("tree", uplink, downlink=downlink,
                               group_size=gs)
    p_f, s_f, m_f, _, _ = _run("flat", uplink, downlink=downlink,
                               group_size=gs)
    p_s, s_s, m_s, _, _ = _run("flat_sharded", uplink, downlink=downlink,
                               group_size=gs, mesh=mesh)
    _assert_trees_close(p_t, p_f)
    _assert_trees_close(p_t, p_s)
    np.testing.assert_allclose(s_t.smoothed, s_f.smoothed, atol=1e-5)
    np.testing.assert_allclose(s_t.smoothed, s_s.smoothed, atol=1e-5)
    for m_other in (m_f, m_s):
        np.testing.assert_allclose(np.asarray(m_t["weights"]),
                                   np.asarray(m_other["weights"]),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("transport_name", ["bf16", "int8", "int4"])
def test_quantized_engines_agree_fedavg(transport_name):
    """fedavg's psi-weighted aggregate reuses the stats aggregate in the
    single-region sharded round — pin it per quantized wire too."""
    mesh = make_mesh((1,), ("data",))
    p_t, s_t, m_t, _, _ = _run("tree", transport_name, "fedavg")
    p_f, s_f, m_f, _, _ = _run("flat", transport_name, "fedavg")
    p_s, s_s, m_s, _, _ = _run("flat_sharded", transport_name, "fedavg",
                               mesh=mesh)
    _assert_trees_close(p_t, p_f)
    _assert_trees_close(p_t, p_s)
    np.testing.assert_allclose(s_t.smoothed, s_f.smoothed, atol=1e-5)
    np.testing.assert_allclose(s_t.smoothed, s_s.smoothed, atol=1e-5)


@pytest.mark.parametrize("engine", ["tree", "flat"])
@pytest.mark.parametrize("transport_name", ["int8", "int4"])
def test_quantized_close_to_f32_reference(engine, transport_name):
    """Compression must perturb, not distort: int8/int4 trajectories stay
    near the f32 wire (the convergence-parity pin runs in
    benchmarks/run.py and tests/test_golden_convergence.py). int4's quant
    step is 16x coarser than int8's, so its drift bound scales with it."""
    atol = 5e-3 if transport_name == "int8" else 8e-2
    p_q, s_q, m_q, _, _ = _run(engine, transport_name)
    p_f, s_f, m_f, _, _ = _run(engine, "f32")
    _assert_trees_close(p_q, p_f, atol=atol)
    np.testing.assert_allclose(np.asarray(m_q["theta"]),
                               np.asarray(m_f["theta"]), atol=10 * atol)
    # ... but quantization is genuinely lossy (else this proves nothing)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_q), jax.tree.leaves(p_f)))


def test_quantized_downlink_close_to_f32_broadcast():
    """The compressed broadcast perturbs (clients train from a lossy
    model) but must not distort the trajectory."""
    params = {"w": jnp.full((12, 1), 0.05, jnp.float32),
              "b": jnp.full((1,), 0.01, jnp.float32)}
    p_q, _, m_q, _, _ = _run("flat", "f32", downlink="int8", params=params)
    p_f, _, m_f, _, _ = _run("flat", "f32", downlink="f32", params=params)
    _assert_trees_close(p_q, p_f, atol=2e-2)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_q), jax.tree.leaves(p_f)))


def test_int8_tree_matches_flat_with_bf16_leaves():
    """bf16-leaf model under the int8 wire: the tree engine's dequantized
    reconstruction must stay f32 (a second rounding through the bf16 leaf
    dtype would push the angle stats off the flat engine, which streams
    the wire directly), and the param dtype must survive the round."""
    rng = np.random.default_rng(0)
    d = 12
    X = jnp.asarray(rng.normal(size=(K, 3, 8, d)).astype(np.float32))
    w_true = rng.normal(size=(K, d, 1)).astype(np.float32)
    Y = jnp.asarray(np.einsum("ktbd,kde->ktbe", X, w_true))

    def loss_fn(p, batch):
        x, y = batch
        pred = x @ p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)
        return jnp.mean((pred - y) ** 2)

    outs = {}
    for engine in ("tree", "flat"):
        params = {"w": jnp.zeros((d, 1), jnp.bfloat16),
                  "b": jnp.zeros((1,), jnp.bfloat16)}
        cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                          method="fedadp", engine=engine, transport="int8",
                          base_lr=0.05)
        rf = jax.jit(fl.make_round_fn(loss_fn, cfg))
        st = fl.init_round_state(cfg, params)
        sel = jnp.arange(K, dtype=jnp.int32)
        sizes = jnp.asarray([10.0, 20.0, 30.0, 40.0])
        for r in range(3):
            st, m = rf(st, (X, Y), sel, sizes)
        outs[engine] = (st.params, m)
    for a, b in zip(jax.tree.leaves(outs["tree"][0]),
                    jax.tree.leaves(outs["flat"][0])):
        assert a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-2)
    # stats see identical f32 dequantized values in both engines
    np.testing.assert_allclose(np.asarray(outs["tree"][1]["theta"]),
                               np.asarray(outs["flat"][1]["theta"]),
                               atol=1e-5)


@pytest.mark.parametrize("transport_name", ["int8", "int4"])
@pytest.mark.parametrize("k", [1, 33])
def test_quantized_flat_ragged_k_end_to_end(transport_name, k):
    """Quantized wire + ragged client chunk (tail bounds mask) together.
    K=1 is the int4 packed-width == 1 degenerate case for N odd."""
    p_t, s_t, m_t, _, _ = _run("tree", transport_name, rounds=2, k=k)
    p_f, s_f, m_f, _, _ = _run("flat", transport_name, rounds=2, k=k)
    _assert_trees_close(p_t, p_f)
    np.testing.assert_allclose(np.asarray(m_t["theta"]),
                               np.asarray(m_f["theta"]), atol=1e-5)


# ---------------------------------------------------------- error feedback


@pytest.mark.parametrize("transport_name", ["int8", "int4"])
def test_error_feedback_round1_residual_is_quant_error(transport_name):
    """With zero-initialized EF state, round 1's carried residual must be
    exactly flat(deltas) - dequantize(quantize(flat(deltas)))."""
    params, loss_fn, batches = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      method="fedadp", engine="flat",
                      transport=transport_name, error_feedback=True,
                      base_lr=0.05)
    deltas, _ = jax.vmap(
        lambda b: fl.local_update(loss_fn, params, b, cfg.base_lr)
    )(batches)
    flat0, _ = treemath.tree_ravel_stacked(deltas)
    want = np.asarray(flat0 - transport.roundtrip(flat0, transport_name))
    _, _, _, ef, _ = _run("flat", transport_name, rounds=1,
                          error_feedback=True)
    np.testing.assert_allclose(np.asarray(ef), want, atol=1e-7)
    assert np.abs(want).sum() > 0  # quantization actually dropped signal


def test_error_feedback_carries_across_rounds():
    """Round 2 replays round 1's residual into the uplink: the EF
    trajectory must diverge from the uncompensated int8 one, and the
    carried residual stays within the per-chunk quantization bound."""
    p_ef, _, m_ef, ef, _ = _run("flat", "int8", rounds=3,
                                error_feedback=True)
    p_nc, _, m_nc, _, _ = _run("flat", "int8", rounds=3)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_ef), jax.tree.leaves(p_nc)))
    assert np.all(np.isfinite(np.asarray(ef)))
    # residual of a quantized signal is at most half a quant step of the
    # (residual-boosted) signal — loosely, it must not blow up round over
    # round: bound by the largest per-round delta magnitude seen.
    assert np.abs(np.asarray(ef)).max() < 1.0


def test_error_feedback_requires_quantized_transport():
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      transport="f32", error_feedback=True)
    with pytest.raises(ValueError, match="error_feedback"):
        fl.make_round_fn(loss_fn, cfg)


def test_error_feedback_requires_state_buffer():
    """A RoundState missing its EF buffer (e.g. built for a config without
    error_feedback) must be refused, not silently run uncompensated."""
    params, loss_fn, batches = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      engine="flat", transport="int8", error_feedback=True)
    rf = fl.make_round_fn(loss_fn, cfg)
    st = fl.init_round_state(cfg, params)._replace(ef=None)
    with pytest.raises(ValueError, match="state.ef"):
        rf(st, batches, jnp.arange(K, dtype=jnp.int32), jnp.ones((K,)))


# ------------------------------------------------ downlink error feedback


def _nonzero_params():
    """Downlink tests need non-zero params: an all-zero model compresses
    exactly, leaving nothing for the broadcast EF to carry."""
    return {"w": jnp.full((12, 1), 0.05, jnp.float32),
            "b": jnp.full((1,), 0.01, jnp.float32)}


def test_downlink_ef_round1_residual_is_broadcast_quant_error():
    """With zero-initialized downlink EF state, round 1's carried residual
    must be exactly p - decompress(compress(p)) of the INITIAL params."""
    params = _nonzero_params()
    pvec, _ = treemath.tree_ravel(params)
    want = np.asarray(
        pvec - transport.downlink.broadcast_roundtrip(pvec, "int8"))
    _, _, _, _, dl = _run("flat", "f32", rounds=1, downlink="int8",
                          downlink_error_feedback=True, params=params)
    np.testing.assert_allclose(np.asarray(dl), want, atol=1e-7)
    assert np.abs(want).sum() > 0


def test_downlink_ef_carries_across_rounds():
    """The EF broadcast trajectory diverges from the uncompensated one and
    the carried residual stays bounded."""
    params = _nonzero_params()
    p_ef, _, _, _, dl = _run("flat", "f32", rounds=3, downlink="int8",
                             downlink_error_feedback=True, params=params)
    p_nc, _, _, _, _ = _run("flat", "f32", rounds=3, downlink="int8",
                            params=params)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_ef), jax.tree.leaves(p_nc)))
    assert np.all(np.isfinite(np.asarray(dl)))
    assert np.abs(np.asarray(dl)).max() < 1.0


def test_downlink_ef_engines_agree():
    """The EF broadcast is computed upstream of the engine branch: tree ==
    flat == flat_sharded to 1e-5 under downlink EF + quantized uplink."""
    params = _nonzero_params()
    mesh = make_mesh((1,), ("data",))
    outs = {
        eng: _run(eng, "int4", rounds=3, downlink="int8",
                  downlink_error_feedback=True, params=params,
                  mesh=(mesh if eng == "flat_sharded" else None))
        for eng in ("tree", "flat", "flat_sharded")
    }
    for eng in ("flat", "flat_sharded"):
        _assert_trees_close(outs["tree"][0], outs[eng][0])
        np.testing.assert_allclose(np.asarray(outs["tree"][4]),
                                   np.asarray(outs[eng][4]), atol=1e-6)


def test_downlink_ef_requires_quantized_downlink():
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      downlink="f32", downlink_error_feedback=True)
    with pytest.raises(ValueError, match="downlink_error_feedback"):
        fl.make_round_fn(loss_fn, cfg)


def test_downlink_ef_requires_state_buffer():
    params, loss_fn, batches = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      engine="flat", downlink="int8",
                      downlink_error_feedback=True)
    rf = fl.make_round_fn(loss_fn, cfg)
    st = fl.init_round_state(cfg, params)._replace(dl_ef=None)
    with pytest.raises(ValueError, match="state.dl_ef"):
        rf(st, batches, jnp.arange(K, dtype=jnp.int32), jnp.ones((K,)))


# ------------------------------------------------------------- validation


def test_unknown_transport_rejected():
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      transport="fp8")
    with pytest.raises(ValueError, match="transport"):
        fl.make_round_fn(loss_fn, cfg)


def test_unknown_downlink_rejected():
    """int4 is an uplink-only format: the downlink whitelist must refuse
    it (and anything else outside f32/bf16/int8)."""
    params, loss_fn, _ = _toy_problem()
    for dl in ("int4", "fp8"):
        cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                          downlink=dl)
        with pytest.raises(ValueError, match="downlink"):
            fl.make_round_fn(loss_fn, cfg)


def test_bad_group_size_rejected_at_config():
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      transport="int4", group_size=100)
    with pytest.raises(ValueError, match="group_size"):
        fl.make_round_fn(loss_fn, cfg)


def test_sequential_mode_rejects_quantized_transport():
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      mode="sequential", transport="int8")
    with pytest.raises(ValueError, match="sequential"):
        fl.make_round_fn(loss_fn, cfg)


def test_sequential_mode_rejects_quantized_downlink():
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      mode="sequential", downlink="int8")
    with pytest.raises(ValueError, match="parallel"):
        fl.make_round_fn(loss_fn, cfg)


# ---------------------------------------------------------------------------
# 2D (client x model) wire: quantization chunks are SHARD-LOCAL.
# ---------------------------------------------------------------------------

from jax.sharding import PartitionSpec as P  # noqa: E402


def _blocked_wire_fixture(k=3, m=4, seed=0):
    rng = np.random.default_rng(seed)
    stacked = {
        "wq": jnp.asarray(rng.normal(size=(k, 6, 8)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(k, 7)).astype(np.float32)),
    }
    pspecs = {"wq": P(None, "model"), "b": P(None)}
    lay = treemath.blocked_layout(stacked, pspecs, m)
    leaves = jax.tree.leaves(stacked)

    def block(j):
        loc = []
        for x, sdim in zip(leaves, lay.sharded_dims):
            if sdim >= 0:
                step = x.shape[sdim + 1] // m
                sl = [slice(None)] * x.ndim
                sl[sdim + 1] = slice(j * step, (j + 1) * step)
                loc.append(x[tuple(sl)])
            else:
                loc.append(x)
        return treemath.blocked_ravel_local(loc, lay, j)

    return stacked, lay, block


@pytest.mark.parametrize("tr,gs", [("int8", 0), ("int4", 8)])
def test_shard_local_scales_are_locally_determined(tr, gs):
    """The 2D wire contract: each model shard quantizes its OWN (K, N_loc)
    block, so a shard's values and scales depend only on that shard's
    elements — perturbing shard i cannot move shard j's wire bytes (with
    per-shard chunking, a scale can never straddle a model-axis split)."""
    _, lay, block = _blocked_wire_fixture()
    kw = dict(group_size=gs) if gs else {}
    base = [transport.quantize(block(j), tr, **kw) for j in range(4)]
    # perturb shard 0's elements only: scale up wq's first column block
    stacked2, lay2, block2 = _blocked_wire_fixture()
    stacked2["wq"] = stacked2["wq"].at[:, :, :2].mul(100.0)
    leaves2 = jax.tree.leaves(stacked2)

    def blk2(j):
        loc = []
        for x, sdim in zip(leaves2, lay2.sharded_dims):
            if sdim >= 0:
                step = x.shape[sdim + 1] // 4
                sl = [slice(None)] * x.ndim
                sl[sdim + 1] = slice(j * step, (j + 1) * step)
                loc.append(x[tuple(sl)])
            else:
                loc.append(x)
        return treemath.blocked_ravel_local(loc, lay2, j)

    pert = [transport.quantize(blk2(j), tr, **kw) for j in range(4)]
    # shard 0 changed...
    assert not np.array_equal(np.asarray(base[0].values),
                              np.asarray(pert[0].values))
    # ...but every other shard's wire bytes AND scales are untouched
    for j in range(1, 4):
        np.testing.assert_array_equal(np.asarray(base[j].values),
                                      np.asarray(pert[j].values))
        np.testing.assert_array_equal(np.asarray(base[j].scales),
                                      np.asarray(pert[j].scales))


@pytest.mark.parametrize("tr,gs", [("int8", 0), ("int4", 4)])
def test_shard_local_roundtrip_matches_per_block_reference(tr, gs):
    """fl_shard_map's blocked roundtrip == quantize/dequantize each shard's
    block independently with the reference quantizer — pinned without a
    mesh by replaying the per-shard blocks by hand."""
    _, lay, block = _blocked_wire_fixture()
    kw = dict(group_size=gs) if gs else {}
    for j in range(4):
        blk = block(j)
        rt = transport.roundtrip(blk, tr, **kw)
        q = transport.quantize(blk, tr, **kw)
        np.testing.assert_array_equal(np.asarray(rt),
                                      np.asarray(transport.dequantize(q)))
        # per-shard scale columns cover ceil(width/chunk) chunks of THIS
        # block only — the scale count is derived from the LOCAL width
        if tr == "int8":
            assert q.scales.shape == (3, transport.num_chunks(lay.width))
        else:
            assert q.scales.shape == (3, transport.num_groups(lay.width, gs))


def test_shard_local_chunks_differ_from_global_wire():
    """Same logical deltas, different chunk boundaries: the 2D blocked wire
    is NOT byte-identical to the global (1D) wire — that is by design (the
    wire layout is mesh-derived), and exactly why the tree engine on a 2D
    mesh must consume the blocked reconstruction rather than the global
    one. Guards against silently 'simplifying' the tree path back to the
    global quantizer."""
    stacked, lay, block = _blocked_wire_fixture()
    flat, _ = treemath.tree_ravel_stacked(stacked)
    global_rt = np.asarray(transport.roundtrip(flat, "int8"))
    # blocked reconstruction, reassembled into ravel order
    k = flat.shape[0]
    leaves = jax.tree.leaves(stacked)
    recs = {i: [] for i in range(len(leaves))}
    for j in range(4):
        rt = transport.roundtrip(block(j), "int8")
        for i, seg in enumerate(treemath.blocked_split(rt, lay)):
            recs[i].append(seg)
    parts = []
    for i, (shape, sdim) in enumerate(zip(lay.shapes, lay.sharded_dims)):
        if sdim >= 0:
            step = shape[sdim] // 4
            local = list(shape)
            local[sdim] = step
            rec = jnp.concatenate(
                [s.reshape((k,) + tuple(local)) for s in recs[i]],
                axis=sdim + 1)
        else:
            size = int(np.prod(shape)) if shape else 1
            rec = jnp.concatenate(recs[i], axis=1)[:, :size].reshape(
                (k,) + shape)
        parts.append(np.asarray(rec).reshape(k, -1))
    blocked_rt = np.concatenate(parts, axis=1)
    # both are valid int8 reconstructions (same error envelope)...
    assert np.max(np.abs(blocked_rt - np.asarray(flat))) < 0.1
    assert np.max(np.abs(global_rt - np.asarray(flat))) < 0.1
    # ...but they are different wires (different chunk boundaries)
    assert not np.array_equal(blocked_rt, global_rt)
