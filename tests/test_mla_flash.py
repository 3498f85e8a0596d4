"""MLA's fused causal attention core (`mla._fused_core` over
`flash_attn.causal_attention`) against its einsum core, on the CPU
through the Pallas interpreter, at T = 256 with DeepSeek-V2's head
split (q/k 16 + 8 RoPE, v 16) and YaRN's softmax scale: outputs and
gradients of every MLA param, and a client model's loss and gradient
through the transformer. The fused core engages by shape and sharding
alone: a T the kernel cannot tile, decode, `act_constrain` and the
launch steps laid on a mesh of several devices keep the einsums, which
the lowered text shows by the absence of its scope."""
import dataclasses
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs import deepseek_v2_lite_16b as ds
from repro.kernels import flash_attn
from repro.models import layers, mla, sharding

MLA_WIDTHS = dict(kv_lora_rank=32, rope_head_dim=8, nope_head_dim=16,
                  v_head_dim=16)


def small_config(**kw):
    """DeepSeek-V2-Lite's chip share, a dense and an MoE layer, at small
    widths; `kw` replaces fields after."""
    cfg = ds.chip_share(chips=2, moe_layers=1)
    cfg = dataclasses.replace(
        cfg, d_model=64, num_heads=2, num_kv_heads=2, d_ff=96,
        vocab_size=256, mla=dataclasses.replace(cfg.mla, **MLA_WIDTHS),
        moe=dataclasses.replace(cfg.moe, num_experts=4, top_k=2,
                                d_ff_expert=32, held=(0, 1, 2, 3)))
    return dataclasses.replace(cfg, **kw)


def _inputs(cfg, T, B=2):
    x = jax.random.normal(jax.random.key(1), (B, T, cfg.d_model))
    pos = jnp.arange(T)[None].repeat(B, 0)
    cos, sin = layers.rope_cos_sin(pos, cfg.mla.rope_head_dim,
                                   cfg.rope_theta, cfg.yarn)
    return x, cos, sin


def _mla_loss(p, cfg, x, cos, sin):
    y, _ = mla.mla_forward(p, cfg, x, cos, sin)
    w = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
    return jnp.sum(y * w), y


def _close(got, want, rel):
    """Every leaf within `rel` of the largest magnitude of its reference."""
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g), w, rtol=0,
                                   atol=rel * np.max(np.abs(w)))


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_fused_mla_matches_einsum_output_and_param_grads(monkeypatch):
    cfg = small_config()
    assert mla.softmax_scale(cfg) == pytest.approx(  # YaRN's mscale²
        0.1147214 * (192 / 24) ** 0.5, rel=1e-6)
    p = mla.mla_init(jax.random.key(0), cfg)
    x, cos, sin = _inputs(cfg, 256)
    grad = jax.jit(jax.grad(_mla_loss, has_aux=True), static_argnums=1)
    got_g, got_y = grad(p, cfg, x, cos, sin)
    assert mla.ATTN_SCOPE in _lowered(
        lambda p: _mla_loss(p, cfg, x, cos, sin)[0], p)
    monkeypatch.setattr(flash_attn, "causal_blocks", lambda T: None)
    want_g, want_y = jax.jit(jax.grad(_mla_loss, has_aux=True),
                             static_argnums=1)(p, cfg, x, cos, sin)
    assert set(got_g) == set(p)
    _close(got_y, want_y, 1e-5)
    _close(got_g, want_g, 1e-5)


def test_fused_client_loss_and_grad_match_einsum(monkeypatch):
    """A client model's next-token loss and its gradient, through the
    scanned, rematerialized layers of `transformer.forward` (dense FFNs
    in place of the experts, which the two cores share)."""
    cfg = small_config(moe=None)
    client = repro.lm_client(cfg)
    params = client.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(2), (1, 257), 0, cfg.vocab_size)
    batch = (toks[:, :-1], toks[:, 1:])
    vg = jax.jit(jax.value_and_grad(client.loss, has_aux=True))
    (got, _), got_g = vg(params, batch)
    monkeypatch.setattr(flash_attn, "causal_blocks", lambda T: None)
    vg = jax.jit(jax.value_and_grad(client.loss, has_aux=True))
    (want, _), want_g = vg(params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    _close(got_g, want_g, 1e-5)


@pytest.mark.parametrize("T", [128, 384, 640])
def test_causal_attention_tiles_every_multiple_of_128(T):
    """Against materialized causal softmax scores, with separate q/k and
    v head dims."""
    B, H, dqk, dv, scale = 1, 2, 24, 16, 0.3
    ks = jax.random.split(jax.random.key(T), 3)
    q, k = (jax.random.normal(kk, (B, H, T, dqk)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (B, H, T, dv))
    got = flash_attn.causal_attention(q, k, v, scale)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, precision="highest") * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    want = jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1), v,
                      precision="highest")
    _close(got, want, 1e-5)


def test_blocks_follow_the_sequence():
    assert flash_attn.causal_blocks(16) is None
    assert flash_attn.causal_blocks(200) is None
    b = flash_attn.causal_blocks(256)
    assert (b.block_q, b.block_kv) == (256, 256)
    b = flash_attn.causal_blocks(2048)
    tiles = flash_attn.CAUSAL_TILES
    assert (b.block_q, b.block_kv) == tiles["fwd"]
    assert (b.block_q_dkv, b.block_kv_dkv) == tiles["dkv"]
    assert (b.block_q_dq, b.block_kv_dq) == tiles["dq"]


@pytest.mark.parametrize("case",
                         ["short", "decode", "act_constrain", "mesh"])
def test_einsum_path_where_the_kernel_does_not_engage(case):
    cfg = small_config(act_constrain=case == "act_constrain")
    p = mla.mla_init(jax.random.key(0), cfg)
    if case == "mesh":  # what a step laid on two devices shows the model
        x, cos, sin = _inputs(cfg, 256)
        text = _lowered(sharding.traced_on(
            SimpleNamespace(size=2), lambda p: jax.grad(
                lambda p: _mla_loss(p, cfg, x, cos, sin)[0])(p)), p)
    elif case == "decode":
        x, cos, sin = _inputs(cfg, 1)
        cache = {"ckv": jnp.zeros((2, 256, cfg.mla.kv_lora_rank)),
                 "krope": jnp.zeros((2, 256, cfg.mla.rope_head_dim))}
        text = _lowered(lambda p: mla.mla_decode(p, cfg, x, cache, 5, cos,
                                                 sin)[0], p)
    else:
        x, cos, sin = _inputs(cfg, 16 if case == "short" else 256)
        text = _lowered(lambda p: jax.grad(
            lambda p: _mla_loss(p, cfg, x, cos, sin)[0])(p), p)
    assert mla.ATTN_SCOPE not in text


def test_launch_steps_keep_the_einsums_on_a_mesh():
    """DeepSeek-V2-Lite's smoke config at T = 256: the train and prefill
    steps laid on a (2, 2) data x model mesh leave attention to the
    partitioner; the same steps on one device take the fused core. Four
    CPU devices, so in a process of its own."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.configs import registry, shapes
        from repro.launch import steps
        from repro.launch.mesh import make_mesh
        from repro.models import mla
        cfg = registry.get("deepseek-v2-lite-16b-smoke")
        for dims in [(2, 2), (1, 1)]:
            mesh = make_mesh(dims, ("data", "model"))
            for kind in ("train", "prefill"):
                build = getattr(steps, f"build_{kind}_step")
                fn, args, ins, outs, _ = build(
                    cfg, mesh, shapes.InputShape(kind, 256, 4, kind))
                text = jax.jit(fn, in_shardings=ins, out_shardings=outs
                               ).lower(*args).as_text(debug_info=True)
                print(dims, kind, mla.ATTN_SCOPE in text)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.stdout.split("\n")[:-1] == [
        "(2, 2) train False", "(2, 2) prefill False",
        "(1, 1) train True", "(1, 1) prefill True"], out.stderr[-2000:]
