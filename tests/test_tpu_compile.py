"""The main-path Pallas kernels compile with Mosaic for a v5e chip, and
the CNN's client-batched local update keeps the clients on the lanes.

Interpret mode accepts block shapes, scalar stores and reshapes that the
TPU compiler refuses, so the CPU parity tests cannot see them. These
tests lower each kernel of the flat engine for a described (not
attached) v5e chip at the paper CNN's width, N = 1,663,370, for one
client chunk (K=10) and a ragged second chunk (K=33), and check that the
compiled program holds the Mosaic kernel (`tpu_custom_call`). They also
compile the paper cell's local update (K=10 CNN clients, B=32, 18 steps)
and read the layouts XLA chose for its 28x28 stage, and the sequential
round's `leaf_stats` at DeepSeek-V2-Lite's leaf widths, where it must read
each leaf in place (no copy of it in the compiled program), and MLA's
fused causal attention, forward and backward, at that cell's shape, and
in a prefill step laid on one chip and on a (2, 2) mesh.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro import transport
from repro.configs import registry, shapes
from repro.core import fl
from repro.kernels import flash_attn, round_stats, weighted_agg
from repro.launch import steps
from repro.models import small

N = 1_663_370  # models/small.py CNN parameter count


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _case(name, k, group_size):
    """(fn, arg shapes) for one kernel; interpret=False forces Mosaic."""
    c = transport.num_chunks(N)
    g4 = transport.num_groups(N, group_size)
    f32, i8 = jnp.float32, jnp.int8
    w, vec = ((k,), f32), ((N,), f32)
    x8, s8 = ((k, N), i8), ((k, c), f32)
    x4, s4 = ((k, -(-N // 2)), i8), ((k, g4), f32)
    return {
        "weighted_agg": (lambda w, x: weighted_agg.weighted_agg(
            w, x, interpret=False, out_dtype=f32), [w, ((k, N), f32)]),
        "weighted_agg_q": (lambda w, x, s: weighted_agg.weighted_agg_q(
            w, x, s, interpret=False), [w, x8, s8]),
        "weighted_agg_q4": (lambda w, x, s: weighted_agg.weighted_agg_q4(
            w, x, s, n=N, group_size=group_size, interpret=False),
            [w, x4, s4]),
        # the stats kernels run masked: flat_sharded always passes a mask
        "round_stats": (lambda x, g, m: round_stats.round_stats(
            x, g, m, interpret=False), [((k, N), f32), vec, vec]),
        "round_stats_q": (lambda x, s, g, m: round_stats.round_stats_q(
            x, s, g, m, interpret=False), [x8, s8, vec, vec]),
        "round_stats_q4": (lambda x, s, g: round_stats.round_stats_q4(
            x, s, g, group_size=group_size, interpret=False),
            [x4, s4, vec]),
    }[name]


KERNELS = ["round_stats", "round_stats_q", "round_stats_q4",
           "weighted_agg", "weighted_agg_q", "weighted_agg_q4"]
CASES = ([(name, k, transport.GROUP_SIZE) for name in KERNELS
          for k in (10, 33)]
         # int4 groups narrower than a 128-lane row expand on the MXU
         + [(name, 10, 32) for name in ("round_stats_q4",
                                        "weighted_agg_q4")])


@pytest.mark.parametrize("name,k,group_size", CASES,
                         ids=[f"{n}-K{k}-gs{g}" for n, k, g in CASES])
def test_kernel_compiles_for_v5e(one_chip, name, k, group_size):
    fn, shapes = _case(name, k, group_size)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _f32_layouts(text):
    """name -> (dims, minor dimension) of every f32 array instruction in a
    compiled module's text, from its layout's minor-to-major list."""
    found = {}
    for name, dims, layout in re.findall(
            r"%([\w.-]+) = f32\[([\d,]+)\]\{([\d,]+)[:}]", text):
        dims = [int(d) for d in dims.split(",")]
        found[name] = (dims, dims[int(layout.split(",")[0])])
    return found


def test_cnn_clients_local_update_keeps_clients_on_lanes(one_chip):
    """The 28x28 stage keeps the K clients' 32 channels merged on the
    minor axis: no f32 activation [.., 28, 28, K, 32] or [K, .., 28, 28,
    32] is left, and the first pool's `reduce` reads its operand with
    K*32 minor."""
    k, b, tau = 10, 32, 18

    def shape(s, dt=jnp.float32):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    params = jax.tree.map(lambda a: shape((k, *a.shape)),
                          jax.eval_shape(small.cnn_init, jax.random.key(0)))
    batches = (shape((k, tau, b, 28, 28, 1)), shape((k, tau, b), jnp.int32))

    def loss(p, bt):
        return small.classification_loss(small.cnn_apply_clients, p, *bt)

    text = jax.jit(lambda p, bt: fl.local_update_clients(
        loss, p, bt, 0.02)).lower(params, batches).compile().as_text()
    assert not re.search(r"f32\[(\d+,)*28,28,10,32\]", text)
    assert not re.search(r"f32\[10,(\d+,)*28,28,32\]", text)
    layouts = _f32_layouts(text)
    pools = [layouts[operand] for operand in re.findall(
        rf"= f32\[{b},14,14,{k * 32}\]\S* reduce\(%([\w.-]+),", text)]
    assert pools and all(m == k * 32 for _, m in pools), pools


# leaves of the dsv2lite.silo chip share, layer-stacked: routed experts,
# MLA's q projection, the vocabulary slice. (Where the last dim is not a
# multiple of 128, as the dense MLP's 10,944 and the kv projection's 576,
# the compiler may give the argument another layout and copy it.)
LEAVES = [(4, 8, 2048, 1408), (4, 8, 1408, 2048), (5, 2048, 3072),
          (12800, 2048), (2048, 12800)]


@pytest.mark.parametrize("shape", LEAVES, ids=["x".join(map(str, s))
                                               for s in LEAVES])
def test_leaf_stats_compiles_in_place_for_v5e(one_chip, shape):
    arg = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = jax.jit(lambda p, q, g: round_stats.leaf_stats(
        p, q, g, interpret=False)).lower(arg, arg, arg).compile().as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= f32\[[\d,]+\][^ ]* (copy|pad|transpose)\(", text)


def test_mla_causal_attention_compiles_for_v5e(one_chip):
    """The fused core at dsv2lite.silo's shape (B=2, 16 heads, T=2,048,
    q/k heads 192, v heads 128, bf16 operands): the forward and the
    backward's dkv and dq kernels, with no (T, T) array left."""
    B, H, T = 2, 16, 2048

    def shape(d, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct((B, H, T, d), dt, sharding=one_chip)

    def loss(q, k, v, w):
        out = flash_attn.causal_attention(q, k, v, 0.1147, interpret=False)
        return jnp.sum(out.astype(jnp.float32) * w)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(192), shape(192), shape(128),
        shape(128, jnp.float32)).compile().as_text()
    for kernel in ("fwd", "dkv", "dq"):
        assert re.search(rf"%splash_mha_{kernel}\w*\.?\d* = .*"
                         r'custom_call_target="tpu_custom_call"', text), kernel
    assert not re.search(rf"\[[\d,]*{T},{T}\]", text)


@pytest.mark.parametrize("dims", [(1, 1), (2, 2)])
def test_mla_prefill_compiles_for_v5e_meshes(topo, dims, monkeypatch):
    """DeepSeek-V2-Lite's smoke prefill at T = 256, its kernels in
    Mosaic: on one chip attention runs the fused core's kernels; on a
    (2, 2) data x model mesh it runs as einsums, since the partitioner
    refuses to split a Mosaic call."""
    monkeypatch.setattr(flash_attn, "interpret_mode", lambda interpret: False)
    mesh = Mesh(np.array(topo.devices[:dims[0] * dims[1]]).reshape(dims),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    fn, args, ins, outs, _ = steps.build_prefill_step(
        registry.get("deepseek-v2-lite-16b-smoke"), mesh,
        shapes.InputShape("prefill", 256, 4, "prefill"))
    text = jax.jit(fn, in_shardings=ins, out_shardings=outs).lower(
        *args).compile().as_text()
    fused = re.search(r"%splash_mha_fwd\w*\.?\d* = .*"
                      r'custom_call_target="tpu_custom_call"', text)
    assert bool(fused) == (dims == (1, 1))
