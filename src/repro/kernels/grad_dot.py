"""Fused gradient-statistics Pallas kernel (TPU target).

FedAdp's angle needs (<g, g_i>, ||g||^2, ||g_i||^2) over the flattened
parameter vector — O(P) elements streamed once. XLA emits three separate
reduce fusions (three HBM passes); this kernel computes all three in a
single HBM->VMEM pass (memory-bound: arithmetic intensity ~3 FLOP/8 B).

Tiling: inputs are viewed as (M, 128) f32 and the grid walks row-blocks of
ROWS x 128 (ROWS*128*4 B = 256 KiB per operand in VMEM). TPU executes grid
steps of a sequential dimension in order on the same core, so the (1, 1)
output blocks act as accumulators across steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

LANE = 128
ROWS = 512  # 512*128*4 B = 256 KiB per input block


def _kernel(a_ref, b_ref, dot_ref, na_ref, nb_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dot_ref[0, 0] = 0.0
        na_ref[0, 0] = 0.0
        nb_ref[0, 0] = 0.0

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    dot_ref[0, 0] += jnp.sum(a * b)
    na_ref[0, 0] += jnp.sum(a * a)
    nb_ref[0, 0] += jnp.sum(b * b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grad_dot_stats(a: jax.Array, b: jax.Array, *, interpret=None):
    """(<a,b>, ||a||^2, ||b||^2) for equally-shaped arrays, f32 accumulate.

    `interpret` resolves through `repro.kernels.interpret_mode`.
    """
    assert a.shape == b.shape
    af = a.reshape(-1)
    bf = b.reshape(-1)
    n = af.shape[0]
    block = ROWS * LANE
    pad = (-n) % block
    if pad:
        af = jnp.concatenate([af, jnp.zeros((pad,), af.dtype)])
        bf = jnp.concatenate([bf, jnp.zeros((pad,), bf.dtype)])
    m = af.shape[0] // LANE
    a2 = af.reshape(m, LANE)
    b2 = bf.reshape(m, LANE)

    out_shape = tuple(jax.ShapeDtypeStruct((1, 1), jnp.float32) for _ in range(3))
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    dot, na, nb = pl.pallas_call(
        _kernel,
        grid=(m // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, LANE), lambda i: (i, 0)),
        ],
        out_specs=(scalar_spec, scalar_spec, scalar_spec),
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(a2, b2)
    return dot[0, 0], na[0, 0], nb[0, 0]
