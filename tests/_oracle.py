"""Float64 oracles for the kernel parity tests.

The reductions run in float64 NumPy over the kernels' f32 inputs, so an
oracle's own rounding stays far below the kernels' f32 accumulation
error and does not depend on the host's reduction order. (An f32 jnp
oracle can be the less accurate side: on a (64, 16385) int4 buffer its
dots were 1.13 off the float64 value where the kernel's were 0.18.)
`repro.kernels.ref` keeps the jit-able jnp versions that the benchmarks
time as the XLA baseline.
"""
import jax.numpy as jnp
import numpy as np

from repro.transport.quantize import QuantizedDelta, dequantize


def _f64(a) -> np.ndarray:
    return np.asarray(jnp.asarray(a, jnp.float32), np.float64)


def _f32(a) -> np.ndarray:
    return np.asarray(a, np.float32)


def grad_dot_stats(a, b):
    af, bf = _f64(a).reshape(-1), _f64(b).reshape(-1)
    return _f32(af @ bf), _f32(af @ af), _f32(bf @ bf)


def weighted_agg(w, x):
    return _f32(_f64(w) @ _f64(x))


def round_stats(x, g, mask=None):
    """(dots (K,), sqnorms (K,), sqg ()) over x (K, N), g (N,)."""
    xf, gf = _f64(x), _f64(g)
    if mask is not None:
        mf = _f64(mask)
        xf = xf * mf[None]
        gf = gf * mf
    return _f32(xf @ gf), _f32(np.sum(xf * xf, axis=1)), _f32(gf @ gf)


# The quantized oracles dequantize through the transport layer's own
# `dequantize`, so they check the fused kernels against the wire's actual
# semantics (a local re-derivation could drift from the chunk layout).


def weighted_agg_q(w, values, scales):
    return weighted_agg(w, dequantize(QuantizedDelta(values, scales)))


def round_stats_q(values, scales, g, mask=None):
    return round_stats(dequantize(QuantizedDelta(values, scales)), g, mask)


def _dequant4(values, scales, n, group_size):
    return dequantize(QuantizedDelta(values, scales, "int4", n, group_size))


def weighted_agg_q4(w, values, scales, *, n, group_size):
    return weighted_agg(w, _dequant4(values, scales, n, group_size))


def round_stats_q4(values, scales, g, mask=None, *, group_size):
    return round_stats(_dequant4(values, scales, g.shape[0], group_size),
                       g, mask)
