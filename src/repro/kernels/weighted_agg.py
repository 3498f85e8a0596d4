"""Fused K-way weighted delta aggregation Pallas kernel (TPU target).

FedAdp's global update is y = sum_k w_k * x_k over K client deltas
(Eq. 4/11). A naive implementation is K scaled-add passes (K reads of y);
this kernel streams (K_TILE, ROWS, 128) tiles through VMEM and writes
each y tile once — a single HBM pass over the stacked deltas.

The client axis is CHUNKED, not whole-K tiled: the grid walks
ceil(K / K_TILE) client chunks per output tile and accumulates partial
sums into the revisited f32 output block (sequential grid steps run in
order on one TPU core, so revisited output blocks act as accumulators —
same pattern as `grad_dot.py`). Any K is served with a bounded VMEM
envelope. Ragged K (K % K_TILE != 0) is handled by an IN-KERNEL bounds
mask on the tail chunk — the (K, N) buffer is never copied to a padded
staging buffer (the former `jnp.concatenate` zero-pad is gone; only the
O(K) weight vector is still padded, which costs nothing).

`weighted_agg_q` is the quantized-transport variant: it reads int8 wire
values plus one f32 scale per (client, CHUNK)-tile and dequantizes
in-register, so aggregation over a compressed uplink stays a single HBM
pass that moves ~4x fewer bytes (see repro.transport).

`weighted_agg_q4` extends that to the int4 packed wire: each physical
(ROWS, LANE) byte tile holds TWO logical value chunks (low/high nibbles
of consecutive element pairs), and the per-(client, group) scales are no
longer 1:1 with tiles — a tile covers 2*CHUNK/group_size groups, expanded
in-register (`_expand_group_scales`). Both nibbles unpack in-register (shift /
mask / sign-extend on the int32 upcast), so aggregation over the int4
uplink is a single HBM pass over ~8x fewer bytes than f32. The kernel
emits separate even/odd accumulators (one per nibble plane) that the
wrapper interleaves back to logical order — an O(N) f32 shuffle on the
OUTPUT, never a second pass over the wire buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

LANE = 128
ROWS = 128  # per-client block: 128*128*4 B = 64 KiB
# Client-axis chunk: 32*128*128*4 B = 2 MiB per x tile — small enough to
# leave VMEM room for double buffering on a ~16 MiB core. K <= K_TILE runs
# as one chunk of size K; larger K is gridded, with the ragged tail chunk
# bounds-masked inside the kernel (no buffer copy).
K_TILE = 32

# Below this many buffer elements (K * N) the f32 wrappers dispatch to the
# equivalent jnp/XLA expression instead of pallas_call: the kernel's fixed
# launch cost (~1.3 ms in interpret mode, and still a full grid setup
# compiled) dwarfs the arithmetic of a tiny round — the measured source of
# the K=8, d=1024 flat-vs-tree cliff. The fallback computes the same f32
# reduction (different accumulation order, same 1e-5 contract as the
# kernels vs their oracles). Quantized wrappers are exempt: the wire's
# per-CHUNK scale layout is a transport contract, and quantized buffers
# only arise at sizes where the kernels already win.
SMALL_ELEMS = 1 << 17


def _k_chunks(k: int) -> tuple[int, int]:
    """(chunk size, padded K) for gridding the client axis."""
    tile = min(k, K_TILE)
    return tile, ((k + tile - 1) // tile) * tile


def _row_block(n: int) -> int:
    """Sublane block for the unquantized kernels: shrink ROWS for narrow
    buffers so N pads to rows*LANE instead of ROWS*LANE (a d=1024 row
    would otherwise pad 16x). Power of two in [8, ROWS]; 8 sublanes is
    the f32 minimum tile. The quantized kernels keep ROWS — their scale
    chunk CHUNK = ROWS*LANE is the transport wire layout."""
    lanes = -(-n // LANE)
    r = 8
    while r < ROWS and r < lanes:
        r *= 2
    return r


def _use_fallback(k: int, n: int, min_kernel_elems) -> bool:
    """True when (k, n) is below the Pallas break-even point.

    `min_kernel_elems=None` uses SMALL_ELEMS; 0 forces the kernel path
    (tests pin Pallas coverage with it); a custom threshold tunes the
    break-even per deployment."""
    lim = SMALL_ELEMS if min_kernel_elems is None else min_kernel_elems
    return k * n < lim


def _pad_axis0(x: jax.Array, kp: int) -> jax.Array:
    """Zero-pad axis 0 to kp rows — used only for O(K) weight/scale
    vectors; the (K, N) buffers stay unpadded (in-kernel tail mask)."""
    k = x.shape[0]
    if kp == k:
        return x
    return jnp.concatenate([x, jnp.zeros((kp - k,) + x.shape[1:], x.dtype)])


def _tile_major(s: jax.Array, kp: int, per_tile: int) -> jax.Array:
    """(K, C*per_tile) per-(client, chunk) scales -> (C, kp, per_tile).

    Mosaic needs the last two block dims to be (8, 128)-divisible or
    whole: a (K_TILE, 1) block of a (K, C) array is neither. With the
    chunk axis leading and squeezed out of the block, each grid step
    reads a (tile, per_tile) block whose trailing dims are a client
    chunk (whole kp, or K_TILE = 32) by the whole per-tile width."""
    s = _pad_axis0(s, kp)
    return s.reshape(kp, -1, per_tile).transpose(1, 0, 2)


def _pad_lanes(x: jax.Array, block: int) -> jax.Array:
    """Zero-pad the last axis to a multiple of `block`."""
    pad = (-x.shape[-1]) % block
    if not pad:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def _unpack_nibbles(b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """int8 byte block -> (low, high) int32 nibble planes in [-8, 7].

    The int32 upcast sign-extends the byte; `& 0xF` then isolates each
    nibble and the `^ 8 - 8` trick re-extends the nibble's own sign bit.
    Works identically on any block shape (elementwise)."""
    bi = b.astype(jnp.int32)
    lo = ((bi & 0xF) ^ 8) - 8
    hi = (((bi >> 4) & 0xF) ^ 8) - 8
    return lo, hi


def _group_scale_operand(sp: jax.Array, kp: int, gs2: int) -> jax.Array:
    """(K, C*Gt) int4 group scales -> the kernels' tile-major operand.

    gs2 = group_size // 2 bytes per group and Gt = ROWS*LANE // gs2
    groups per tile. A group that is one byte or covers whole 128-lane
    rows ships as (C, kp, Gt); with q = LANE // gs2 > 1 groups per row it
    ships as (C, kp, ROWS, q), one row of scales per byte row. The block
    is the operand's shape with the chunk axis squeezed and kp cut to the
    client tile."""
    s = _tile_major(sp, kp, (ROWS * LANE) // gs2)
    if 1 < gs2 < LANE:
        s = s.reshape(s.shape[0], kp, ROWS, LANE // gs2)
    return s


def _expand_group_scales(s: jax.Array, gs2: int) -> jax.Array:
    """A client tile's group scales -> (KT, ROWS, LANE) per-byte multipliers.

    (KT, Gt) blocks expand by a static repeat+reshape (Gt * gs2 ==
    ROWS * LANE). Mosaic cannot lower that repeat below a whole lane row,
    so (KT, ROWS, q) blocks expand along the lanes by a 0/1 matmul at
    HIGHEST precision — exact, since each output is one scale times 1.0."""
    if s.ndim == 2:
        return jnp.repeat(s, gs2, axis=1).reshape(s.shape[0], ROWS, LANE)
    kt, rows, q = s.shape
    grp = jax.lax.broadcasted_iota(jnp.int32, (q, LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, LANE), 1)
    expand = (lane // gs2 == grp).astype(jnp.float32)
    return jnp.dot(s.reshape(kt * rows, q), expand,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).reshape(kt, rows, LANE)


def _mask_tail_rows(x: jax.Array, kc, *, k: int, tile: int) -> jax.Array:
    """Select-zero rows past K in the ragged tail client chunk.

    Blocks past the array edge read unspecified values (Pallas pads the
    partial block); a select (not a multiply) guarantees even NaN garbage
    cannot poison the f32 accumulators. Trace-time no-op when K divides
    into whole chunks.
    """
    if k % tile == 0:
        return x
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) + kc * tile
    valid = rows < k  # (tile, 1)
    return jnp.where(valid[:, :, None], x, jnp.zeros_like(x))


def _agg_kernel(w_ref, x_ref, y_ref, *, k, tile):
    kc = pl.program_id(1)

    @pl.when(kc == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    w = w_ref[...].astype(jnp.float32)  # (KT, 1)
    x = _mask_tail_rows(x_ref[...].astype(jnp.float32), kc, k=k, tile=tile)
    y_ref[...] += jnp.sum(w[:, :, None] * x, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "out_dtype",
                                    "min_kernel_elems"))
def weighted_agg(w: jax.Array, x: jax.Array, *, interpret=None,
                 out_dtype=None, min_kernel_elems=None):
    """y[n] = sum_k w[k] x[k, n]. x: (K, N) any float dtype; f32 accumulate.

    `out_dtype` overrides the result dtype (default: x.dtype) — pass
    jnp.float32 when a bf16 wire buffer must aggregate into the server's
    f32 reference delta without a lossy round-trip through bf16.
    Buffers below `min_kernel_elems` elements (default SMALL_ELEMS; 0
    forces Pallas) compute as one XLA tensordot — the kernel's launch
    cost dominates tiny rounds.
    """
    K, n = x.shape
    if _use_fallback(K, n, min_kernel_elems):
        y = jnp.tensordot(w.reshape(K).astype(jnp.float32),
                          x.astype(jnp.float32), axes=1,
                          precision=jax.lax.Precision.HIGHEST)
        return y.astype(out_dtype or x.dtype)
    tile, kp = _k_chunks(K)
    rows = _row_block(n)
    x = _pad_lanes(x, rows * LANE)
    m = x.shape[1] // LANE
    x3 = x.reshape(K, m, LANE)
    w2 = _pad_axis0(w.reshape(K).astype(jnp.float32), kp).reshape(kp, 1)

    # grid order: client chunks are the MINOR dimension, so each output
    # tile is revisited across consecutive steps while kc accumulates.
    y = pl.pallas_call(
        functools.partial(_agg_kernel, k=K, tile=tile),
        grid=(m // rows, kp // tile),
        in_specs=[
            pl.BlockSpec((tile, 1), lambda i, kc: (kc, 0)),
            pl.BlockSpec((tile, rows, LANE), lambda i, kc: (kc, i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANE), lambda i, kc: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, LANE), jnp.float32),
        interpret=interpret_mode(interpret),
    )(w2, x3)
    return y.reshape(-1)[:n].astype(out_dtype or x.dtype)


def _agg_q_kernel(ws_ref, x_ref, y_ref, *, k, tile):
    kc = pl.program_id(1)

    @pl.when(kc == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    ws = ws_ref[...]  # (KT, 1) f32 — weight x per-chunk dequant scale
    x = _mask_tail_rows(
        x_ref[...].astype(jnp.float32) * ws[:, :, None], kc, k=k, tile=tile)
    y_ref[...] += jnp.sum(x, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_agg_q(w: jax.Array, values: jax.Array, scales: jax.Array, *,
                   interpret=None):
    """y[n] = sum_k w[k] * scale[k, n // CHUNK] * values[k, n], f32 out.

    values: (K, N) int8 wire buffer; scales: (K, ceil(N / (ROWS*LANE)))
    f32 per-(client, chunk) dequant multipliers (repro.transport layout).
    The weight and the scale fold into ONE multiplier per input tile, so
    fused dequant costs a single extra (K_TILE, 1) VMEM operand per step.
    Lane-tail zero padding needs no scale handling: int8 zeros dequantize
    to zero under any scale.
    """
    K, n = values.shape
    tile, kp = _k_chunks(K)
    x = _pad_lanes(values, ROWS * LANE)
    m = x.shape[1] // LANE
    c = m // ROWS
    assert scales.shape == (K, c), (scales.shape, (K, c))
    x3 = x.reshape(K, m, LANE)
    ws = _tile_major(
        w.reshape(K, 1).astype(jnp.float32) * scales.astype(jnp.float32),
        kp, 1)

    y = pl.pallas_call(
        functools.partial(_agg_q_kernel, k=K, tile=tile),
        grid=(m // ROWS, kp // tile),
        in_specs=[
            pl.BlockSpec((None, tile, 1), lambda i, kc: (i, kc, 0)),
            pl.BlockSpec((tile, ROWS, LANE), lambda i, kc: (kc, i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, LANE), lambda i, kc: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, LANE), jnp.float32),
        interpret=interpret_mode(interpret),
    )(ws, x3)
    return y.reshape(-1)[:n]


def _agg_q4_kernel(ws_ref, x_ref, ye_ref, yo_ref, *, k, tile, gs2):
    kc = pl.program_id(1)

    @pl.when(kc == 0)
    def _init():
        ye_ref[...] = jnp.zeros_like(ye_ref)
        yo_ref[...] = jnp.zeros_like(yo_ref)

    lo, hi = _unpack_nibbles(x_ref[...])
    # weight x per-group dequant scales -> per-byte multipliers
    sexp = _expand_group_scales(ws_ref[...], gs2)
    xlo = _mask_tail_rows(lo.astype(jnp.float32) * sexp, kc, k=k, tile=tile)
    xhi = _mask_tail_rows(hi.astype(jnp.float32) * sexp, kc, k=k, tile=tile)
    ye_ref[...] += jnp.sum(xlo, axis=0)
    yo_ref[...] += jnp.sum(xhi, axis=0)


@functools.partial(jax.jit, static_argnames=("n", "group_size", "interpret"))
def weighted_agg_q4(w: jax.Array, values: jax.Array, scales: jax.Array, *,
                    n: int, group_size: int, interpret=None):
    """y[m] = sum_k w[k] * scale[k, m // group_size] * x4[k, m], f32 out.

    values: (K, ceil(n/2)) int8 PACKED wire buffer (two int4 params per
    byte, low nibble first); scales: (K, ceil(n/group_size)) f32 grouped
    dequant multipliers (repro.transport int4 layout); `n` the logical
    element count. The weight folds into the per-group scale on the host
    (one small VMEM operand per step, `_group_scale_operand`); nibbles unpack in-register
    and accumulate into separate even/odd f32 planes, interleaved back to
    logical order after the kernel. group_size must be even and divide
    CHUNK = ROWS*LANE (transport.validate_group_size), so a tile covers
    whole groups and a byte never straddles two scales. Zero padding
    bytes dequantize to (0, 0) under any scale.
    """
    K, nb = values.shape
    assert nb == -(-n // 2), (nb, n)
    gs2 = group_size // 2
    tile, kp = _k_chunks(K)
    x = _pad_lanes(values, ROWS * LANE)
    m = x.shape[1] // LANE
    gp = x.shape[1] // gs2  # padded group columns (gs2 | ROWS*LANE | cols)
    assert scales.shape[0] == K and scales.shape[1] <= gp, (scales.shape, gp)
    x3 = x.reshape(K, m, LANE)
    # padding scales with 1.0 keeps padded zero bytes at exactly zero
    sp = jnp.pad(scales.astype(jnp.float32),
                 ((0, 0), (0, gp - scales.shape[1])), constant_values=1.0)
    ws = _group_scale_operand(w.reshape(K, 1).astype(jnp.float32) * sp, kp,
                              gs2)

    ye, yo = pl.pallas_call(
        functools.partial(_agg_q4_kernel, k=K, tile=tile, gs2=gs2),
        grid=(m // ROWS, kp // tile),
        in_specs=[
            pl.BlockSpec((None, tile) + ws.shape[2:],
                         lambda i, kc: (i, kc) + (0,) * (ws.ndim - 2)),
            pl.BlockSpec((tile, ROWS, LANE), lambda i, kc: (kc, i, 0)),
        ],
        out_specs=(pl.BlockSpec((ROWS, LANE), lambda i, kc: (i, 0)),
                   pl.BlockSpec((ROWS, LANE), lambda i, kc: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((m, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((m, LANE), jnp.float32)),
        interpret=interpret_mode(interpret),
    )(ws, x3)
    # interleave the nibble planes back to logical order: y[2j] = ye[j],
    # y[2j+1] = yo[j] — an O(N) shuffle of the f32 OUTPUT, not the wire.
    y = jnp.stack([ye.reshape(-1), yo.reshape(-1)], axis=-1).reshape(-1)
    return y[:n]

