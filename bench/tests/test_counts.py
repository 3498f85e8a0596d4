"""Operation and byte counts against what the compiler and the wire say."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import counts
from repro import transport
from repro.models import small

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return cost["flops"]


@pytest.mark.parametrize("model", ["cnn", "mlr"])
def test_model_flops_against_cost_analysis(model):
    """The counts leave out elementwise work and padded taps, which XLA's
    cost analysis includes: they agree to 1.5%."""
    cfg = _config(model)
    init, apply = small.MODELS[model]
    params = init(jax.random.key(0))
    b = 4
    x = jnp.zeros((b, 28, 28, 1), jnp.float32)
    y = jnp.zeros((b,), jnp.int32)
    fwd = _xla_flops(apply, params, x) / b
    train = _xla_flops(jax.grad(
        lambda p: small.classification_loss(apply, p, x, y)), params) / b
    assert counts.forward_flops(cfg) == pytest.approx(fwd, rel=0.015)
    assert counts.train_flops(cfg) == pytest.approx(train, rel=0.015)
    assert cfg["flops_forward_per_sample"] == counts.forward_flops(cfg)
    assert cfg["flops_train_per_sample"] == counts.train_flops(cfg)
    assert cfg["params"] == sum(a.size for a in jax.tree.leaves(params))


@pytest.mark.parametrize("wire", ["f32", "int8", "int4"])
@pytest.mark.parametrize("k,n", [(3, 40000), (10, 1663370)])
def test_wire_bytes_against_quantized_buffers(wire, k, n):
    def wire_arrays(x):
        q = transport.quantize(x, wire, group_size=512)
        return q.values, q.scales

    values, scales = jax.eval_shape(
        wire_arrays, jax.ShapeDtypeStruct((k, n), jnp.float32))
    have = values.size * values.dtype.itemsize
    if scales is not None:
        have += scales.size * scales.dtype.itemsize
    assert counts.wire_bytes(k, n, wire, 512) == have


def test_kernel_call_bytes_add_server_vectors_and_outputs():
    k, n = 10, 1663370
    wire = counts.wire_bytes(k, n, "int4", 512)
    assert counts.kernel_call_bytes("round_stats_q4", k, n, 512) == (
        wire + 4 * n + 4 * (2 * k + 1))
    assert counts.kernel_call_bytes("weighted_agg", k, n, 512) == (
        4 * k + 4 * k * n + 4 * n)


def test_kernel_names_are_the_program_wrappers():
    """The trace names a Pallas call after the jitted wrapper that makes
    it; every wrapper the flat engines call is counted."""
    from repro.kernels import round_stats, weighted_agg

    wrappers = {n for mod in (round_stats, weighted_agg)
                for n in dir(mod) if n.startswith(("round_stats",
                                                   "weighted_agg"))}
    assert set(counts.KERNELS) == wrappers
