"""Fused per-round angle-statistics Pallas kernel (TPU target).

FedAdp's contribution measurement (paper Eqs. 8-11) needs, per round:
  dots[k] = <x_k, g>    — K angle numerators
  sqs[k]  = ||x_k||^2   — K client squared norms
  sqg     = ||g||^2     — the global-gradient squared norm
over the flat (K, N) client-delta buffer x and the (N,) global delta g.
Computed separately (one matvec + K sqnorm reductions + one sqnorm)
that is three HBM passes over x; this kernel streams each (K_TILE, ROWS,
128) tile through VMEM once and emits all 2K+1 statistics — a single HBM
pass over x.

The client axis is chunked like `weighted_agg`: the grid is (client
chunks, lane tiles) with the lane dimension minor, so each chunk's
output blocks accumulate across consecutive lane steps, and sqg
accumulates only on the first chunk (g is re-streamed per chunk but
must be counted once). The accumulators are lane-dense — (K_TILE, LANE)
per-client partial sums and a (1, LANE) sqg row, reduced over the lanes
after the kernel — since Mosaic stores neither scalars nor (K, 1)
columns to VMEM efficiently. Any K is served; a ragged tail chunk (K % K_TILE
!= 0) is bounds-masked in-kernel, so the buffer is never copied to a
zero-padded staging array.

An optional (N,) 0/1 segment mask restricts the statistics to a leaf
subset (the `angle_filter="dense_only"` MoE filter) without materializing
masked copies of x or g: the mask tile rides along and is applied in-VMEM.

`round_stats_q` is the quantized-transport path (repro.transport): x
arrives as int8 wire values plus one f32 scale per (client, ROWS*LANE
chunk); dequantization happens in-register on the loaded tile, so the
statistics stay one HBM pass over ~4x fewer bytes. g stays f32 — it is
server-side state and never crosses the wire.

`round_stats_q4` is the int4 packed path: each physical byte tile holds
two logical chunks (low/high nibble planes of consecutive element
pairs), scales are grouped (2*CHUNK/group_size groups per tile, expanded
in-register), and the server-side g / mask vectors ride along as even/odd
(ROWS, LANE) views so every nibble pairs with its own g element without
ever interleaving the wire buffer — one HBM pass over ~8x fewer bytes.

`leaf_stats` serves the sequential round, which never builds a flat
buffer: the statistics of one client's delta p - q (its trained and its
starting params) against g, leaf by leaf, each leaf read in its own
layout as (rows, last dim) row blocks, a view that only merges major
dims, so no leaf is copied or padded and the delta is never written.

Off a TPU the identical kernel body runs in the Pallas interpreter
(`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# tile geometry and client-chunk size are shared with weighted_agg — the
# (K_TILE, ROWS, LANE) x-tile here fits the same VMEM envelope.
from repro.kernels import interpret_mode
from repro.kernels.weighted_agg import (
    K_TILE,  # noqa: F401  (re-exported: callers size shards against it)
    LANE,
    ROWS,
    _expand_group_scales,
    _group_scale_operand,
    _k_chunks,
    _mask_tail_rows,
    _pad_lanes,
    _row_block,
    _tile_major,
    _unpack_nibbles,
    _use_fallback,
)


def _accumulate(outs, kc, i, planes):
    """Fold one tile's (x (KT, R, LANE), g (R, LANE)) f32 plane pairs
    into the (dots, sqs, sqg) accumulator refs.

    dots/sqs are (KT, LANE) partial sums over the tile's rows; sqg a
    (1, LANE) row. dots/sqs reset at each client chunk's first lane
    tile; sqg resets once and sums g only on the first chunk (g repeats
    per chunk but must be counted once)."""
    dots_ref, sqs_ref, sqg_ref = outs

    @pl.when(i == 0)
    def _init():
        dots_ref[...] = jnp.zeros_like(dots_ref)
        sqs_ref[...] = jnp.zeros_like(sqs_ref)

    @pl.when((kc == 0) & (i == 0))
    def _init_g():
        sqg_ref[...] = jnp.zeros_like(sqg_ref)

    dots_ref[...] += sum(jnp.sum(x * g[None], axis=1) for x, g in planes)
    sqs_ref[...] += sum(jnp.sum(x * x, axis=1) for x, _ in planes)

    @pl.when(kc == 0)
    def _accum_g():
        sqg_ref[...] += sum(jnp.sum(g * g, axis=0, keepdims=True)
                            for _, g in planes)


def _stats_call(kernel, operands, in_specs, *, k, tile, kp, rows, m,
                interpret):
    """Run a stats kernel over the (client chunk, lane tile) grid and
    reduce its lane-dense accumulators to (dots (K,), sqs (K,), sqg ())."""
    kvec_spec = pl.BlockSpec((tile, LANE), lambda kc, i: (kc, 0))
    dots, sqs, sqg = pl.pallas_call(
        kernel,
        grid=(kp // tile, m // rows),
        in_specs=in_specs,
        out_specs=(kvec_spec, kvec_spec,
                   pl.BlockSpec((1, LANE), lambda kc, i: (0, 0))),
        out_shape=(
            jax.ShapeDtypeStruct((kp, LANE), jnp.float32),
            jax.ShapeDtypeStruct((kp, LANE), jnp.float32),
            jax.ShapeDtypeStruct((1, LANE), jnp.float32),
        ),
        interpret=interpret_mode(interpret),
    )(*operands)
    return (jnp.sum(dots[:k], axis=1), jnp.sum(sqs[:k], axis=1),
            jnp.sum(sqg))


def _stats_kernel(*refs, k, tile, masked):
    x_ref, g_ref = refs[:2]
    kc, i = pl.program_id(0), pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)  # (R, LANE)
    if masked:  # (R, LANE) in {0, 1}
        mk = refs[2][...].astype(jnp.float32)
        x, g = x * mk[None], g * mk
    x = _mask_tail_rows(x, kc, k=k, tile=tile)
    _accumulate(refs[-3:], kc, i, [(x, g)])


@functools.partial(jax.jit,
                   static_argnames=("interpret", "min_kernel_elems"))
def round_stats(x: jax.Array, g: jax.Array, mask: jax.Array | None = None,
                *, interpret=None, min_kernel_elems=None):
    """(dots (K,), sqnorms (K,), sqg ()) in one pass over x: (K, N), g: (N,).

    mask, if given, is an (N,) 0/1 vector; statistics are computed over the
    masked subspace (mask is idempotent, so only one multiply per operand).
    Accumulates in f32 regardless of input dtype. Any K: the client axis is
    gridded in chunks, the ragged tail chunk bounds-masked in-kernel.
    Buffers below `min_kernel_elems` elements (default SMALL_ELEMS; 0
    forces Pallas) compute as plain XLA reductions.
    """
    K, n = x.shape
    if _use_fallback(K, n, min_kernel_elems):
        xf = x.astype(jnp.float32)
        gf = g.astype(jnp.float32)
        if mask is not None:
            mf = mask.astype(jnp.float32)
            xf = xf * mf[None]
            gf = gf * mf
        hi = jax.lax.Precision.HIGHEST
        return (jnp.matmul(xf, gf, precision=hi), jnp.sum(xf * xf, axis=1),
                jnp.dot(gf, gf, precision=hi))
    tile, kp = _k_chunks(K)
    rows = _row_block(n)
    block = rows * LANE
    x = _pad_lanes(x, block)
    g = _pad_lanes(g, block)
    m = x.shape[1] // LANE
    tile_spec = pl.BlockSpec((rows, LANE), lambda kc, i: (i, 0))
    in_specs = [
        pl.BlockSpec((tile, rows, LANE), lambda kc, i: (kc, i, 0)),
        tile_spec,
    ]
    operands = [x.reshape(K, m, LANE), g.reshape(m, LANE)]
    if mask is not None:
        in_specs.append(tile_spec)
        operands.append(_pad_lanes(mask, block).reshape(m, LANE))
    kernel = functools.partial(_stats_kernel, k=K, tile=tile,
                               masked=mask is not None)
    return _stats_call(kernel, operands, in_specs, k=K, tile=tile, kp=kp,
                       rows=rows, m=m, interpret=interpret)


def _stats_q4_kernel(*refs, k, tile, gs2, masked):
    x_ref, s_ref, ge_ref, go_ref = refs[:4]
    kc, i = pl.program_id(0), pl.program_id(1)
    lo, hi = _unpack_nibbles(x_ref[...])
    sexp = _expand_group_scales(s_ref[...], gs2)  # (KT, ROWS, LANE)
    xlo = lo.astype(jnp.float32) * sexp
    xhi = hi.astype(jnp.float32) * sexp
    ge = ge_ref[...].astype(jnp.float32)  # (ROWS, LANE) — g[0::2]
    go = go_ref[...].astype(jnp.float32)  # (ROWS, LANE) — g[1::2]
    if masked:  # (ROWS, LANE) even/odd views in {0, 1}
        me = refs[4][...].astype(jnp.float32)
        mo = refs[5][...].astype(jnp.float32)
        xlo, xhi = xlo * me[None], xhi * mo[None]
        ge, go = ge * me, go * mo
    xlo = _mask_tail_rows(xlo, kc, k=k, tile=tile)
    xhi = _mask_tail_rows(xhi, kc, k=k, tile=tile)
    _accumulate(refs[-3:], kc, i, [(xlo, ge), (xhi, go)])


def _even_odd_views(vec: jax.Array, cols: int, m: int):
    """Pad an (n,) server-side vector to 2*cols logical elements and split
    into the (m, LANE) even/odd views the nibble planes pair with."""
    pad = 2 * cols - vec.shape[0]
    if pad:
        vec = jnp.pad(vec, (0, pad))
    return vec[0::2].reshape(m, LANE), vec[1::2].reshape(m, LANE)


@functools.partial(jax.jit, static_argnames=("group_size", "interpret"))
def round_stats_q4(values: jax.Array, scales: jax.Array, g: jax.Array,
                   mask: jax.Array | None = None, *, group_size: int,
                   interpret=None):
    """`round_stats` over the int4 packed wire buffer, dequant in-register.

    values: (K, ceil(n/2)) int8 packed (two int4 params per byte, low
    nibble first); scales: (K, ceil(n/group_size)) f32 grouped dequant
    multipliers (repro.transport int4 layout). g: (n,) f32 (server-side,
    never quantized); mask likewise. Matches
    round_stats(dequantize(int4 wire), g, mask) to f32 accumulation
    order. group_size must be even and divide CHUNK = ROWS*LANE
    (transport.validate_group_size): tiles cover whole groups and both
    nibbles of a byte share one scale. Zero padding bytes dequantize to
    exactly zero; the ragged tail client chunk is bounds-masked, so
    out-of-range scale reads are select-zeroed with the rows they scale.
    """
    K, nb = values.shape
    n = g.shape[0]
    assert nb == -(-n // 2), (nb, n)
    gs2 = group_size // 2
    tile, kp = _k_chunks(K)
    x = _pad_lanes(values, ROWS * LANE)
    cols = x.shape[1]
    m = cols // LANE
    gp = cols // gs2
    assert scales.shape[0] == K and scales.shape[1] <= gp, (scales.shape, gp)
    sp = jnp.pad(scales.astype(jnp.float32),
                 ((0, 0), (0, gp - scales.shape[1])), constant_values=1.0)
    ge2, go2 = _even_odd_views(g.astype(jnp.float32), cols, m)

    s_op = _group_scale_operand(sp, kp, gs2)
    tile_spec = pl.BlockSpec((ROWS, LANE), lambda kc, i: (i, 0))
    in_specs = [
        pl.BlockSpec((tile, ROWS, LANE), lambda kc, i: (kc, i, 0)),
        pl.BlockSpec((None, tile) + s_op.shape[2:],
                     lambda kc, i: (i, kc) + (0,) * (s_op.ndim - 2)),
        tile_spec,
        tile_spec,
    ]
    operands = [x.reshape(K, m, LANE), s_op, ge2, go2]
    if mask is not None:
        in_specs += [tile_spec, tile_spec]
        operands += list(_even_odd_views(mask.astype(jnp.float32), cols, m))
    kernel = functools.partial(_stats_q4_kernel, k=K, tile=tile, gs2=gs2,
                               masked=mask is not None)
    return _stats_call(kernel, operands, in_specs, k=K, tile=tile, kp=kp,
                       rows=ROWS, m=m, interpret=interpret)


def _stats_q_kernel(*refs, k, tile, masked):
    x_ref, s_ref, g_ref = refs[:3]
    kc, i = pl.program_id(0), pl.program_id(1)
    # in-register dequant: one f32 scale per (client, tile)
    x = x_ref[...].astype(jnp.float32) * s_ref[...][:, :, None]
    g = g_ref[...].astype(jnp.float32)
    if masked:  # (ROWS, LANE) in {0, 1}
        mk = refs[3][...].astype(jnp.float32)
        x, g = x * mk[None], g * mk
    x = _mask_tail_rows(x, kc, k=k, tile=tile)
    _accumulate(refs[-3:], kc, i, [(x, g)])


@functools.partial(jax.jit, static_argnames=("interpret",))
def round_stats_q(values: jax.Array, scales: jax.Array, g: jax.Array,
                  mask: jax.Array | None = None, *, interpret=None):
    """`round_stats` over the int8 wire buffer, dequant fused in-register.

    values: (K, N) int8; scales: (K, ceil(N / (ROWS*LANE))) f32 — the
    repro.transport per-(client, chunk) layout, one scale per grid tile.
    g: (N,) f32 (server-side, never quantized). Matches
    round_stats(dequantize(values, scales), g, mask) to f32 accumulation
    order. Lane-tail zero padding needs no scale handling (int8 zeros
    dequantize to zero); the ragged tail client chunk is bounds-masked, so
    the zero-padded scale rows are select-zeroed with the rows they scale.
    """
    K, n = values.shape
    tile, kp = _k_chunks(K)
    block = ROWS * LANE
    x = _pad_lanes(values, block)
    g = _pad_lanes(g, block)
    m = x.shape[1] // LANE
    c = m // ROWS
    assert scales.shape == (K, c), (scales.shape, (K, c))

    tile_spec = pl.BlockSpec((ROWS, LANE), lambda kc, i: (i, 0))
    in_specs = [
        pl.BlockSpec((tile, ROWS, LANE), lambda kc, i: (kc, i, 0)),
        pl.BlockSpec((None, tile, 1), lambda kc, i: (i, kc, 0)),
        tile_spec,
    ]
    operands = [x.reshape(K, m, LANE),
                _tile_major(scales.astype(jnp.float32), kp, 1),
                g.reshape(m, LANE)]
    if mask is not None:
        in_specs.append(tile_spec)
        operands.append(_pad_lanes(mask, block).reshape(m, LANE))
    kernel = functools.partial(_stats_q_kernel, k=K, tile=tile,
                               masked=mask is not None)
    return _stats_call(kernel, operands, in_specs, k=K, tile=tile, kp=kp,
                       rows=ROWS, m=m, interpret=interpret)


# values of one operand a leaf_stats grid step reads (1 MiB of f32)
LEAF_BLOCK_ELEMS = 256 * 1024


def _leaf_kernel(p_ref, q_ref, g_ref, dot_ref, sq_ref, sqg_ref, *, rows,
                 blk):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for ref in (dot_ref, sq_ref, sqg_ref):
            ref[...] = jnp.zeros_like(ref)

    d = p_ref[...].astype(jnp.float32) - q_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if rows % blk:  # the ragged last block reads past the leaf's rows
        row = i * blk + jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
        d = jnp.where(row < rows, d, 0.0)
        g = jnp.where(row < rows, g, 0.0)
    dot_ref[...] += jnp.sum(d * g, axis=0, keepdims=True)
    sq_ref[...] += jnp.sum(d * d, axis=0, keepdims=True)
    sqg_ref[...] += jnp.sum(g * g, axis=0, keepdims=True)


def leaf_stats(p: jax.Array, q: jax.Array, g: jax.Array, *, interpret=None,
               min_kernel_elems=None):
    """(<p - q, g>, ||p - q||^2, ||g||^2) of one leaf, f32, in one pass
    over the three. The leaf is read as (rows, last dim) row blocks with
    lane-dense (1, last dim) accumulators. Leaves below
    `min_kernel_elems` elements (default SMALL_ELEMS; 0 forces Pallas),
    and vectors, compute as plain XLA reductions."""
    if p.ndim < 2 or _use_fallback(1, p.size, min_kernel_elems):
        d = p.astype(jnp.float32) - q.astype(jnp.float32)
        gf = g.astype(jnp.float32)
        return jnp.sum(d * gf), jnp.sum(d * d), jnp.sum(gf * gf)
    cols = p.shape[-1]
    rows = p.size // cols
    blk = max(8, LEAF_BLOCK_ELEMS // cols // 8 * 8)
    if blk >= rows:
        blk = rows
    in_spec = pl.BlockSpec((blk, cols), lambda i: (i, 0))
    out_spec = pl.BlockSpec((1, cols), lambda i: (0, 0))
    acc = jax.ShapeDtypeStruct((1, cols), jnp.float32)
    dot, sq, sqg = pl.pallas_call(
        functools.partial(_leaf_kernel, rows=rows, blk=blk),
        grid=(pl.cdiv(rows, blk),),
        in_specs=[in_spec] * 3,
        out_specs=(out_spec,) * 3,
        out_shape=(acc,) * 3,
        interpret=interpret_mode(interpret),
    )(*(a.reshape(rows, cols) for a in (p, q, g)))
    return jnp.sum(dot), jnp.sum(sq), jnp.sum(sqg)
