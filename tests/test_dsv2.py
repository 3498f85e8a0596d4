"""DeepSeek-V2-Lite as a cross-silo FedAdp client, on the CPU at a small
size with seeded random weights, against the plain float32 reference in
`tests/_dsv2_reference.py`: the published and the cut parameter counts,
YaRN's frequencies and softmax scale, the expert shares adding up to the
uncut layer, dropless dispatch under fully skewed routing, and two
sequential FedAdp rounds through `FedServer`."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs import deepseek_v2_lite_16b as ds
from repro.data.synthetic import Dataset
from repro.models import layers, mla, moe

import _dsv2_reference as ref

# the small model: every published mechanism, small widths
SMALL = dict(d_model=64, num_heads=2, num_kv_heads=2, d_ff=96,
             vocab_size=256)
SMALL_MLA = dict(kv_lora_rank=32, rope_head_dim=8, nope_head_dim=16,
                 v_head_dim=16)


def small_config(held=(0, 1, 2, 3), experts=8, moe_layers=2):
    cfg = ds.chip_share(chips=2, moe_layers=moe_layers)
    return dataclasses.replace(
        cfg, **SMALL, mla=dataclasses.replace(cfg.mla, **SMALL_MLA),
        moe=dataclasses.replace(cfg.moe, num_experts=experts, top_k=3,
                                d_ff_expert=32, held=tuple(held)))


def small_setup(cfg, **kw) -> ref.Setup:
    m, a, y = cfg.moe, cfg.mla, cfg.yarn
    return ref.Setup(
        d=cfg.d_model, heads=cfg.num_heads, kv_rank=a.kv_lora_rank,
        nope=a.nope_head_dim, rope=a.rope_head_dim, v_dim=a.v_head_dim,
        dense_ff=cfg.d_ff, expert_ff=m.d_ff_expert, router=m.num_experts,
        held=tuple(m.held) or tuple(range(m.num_experts)), top_k=m.top_k,
        shared=m.num_shared, vocab=cfg.vocab_size,
        dense_layers=cfg.first_dense_layers,
        moe_layers=cfg.num_layers - cfg.first_dense_layers,
        theta=cfg.rope_theta,
        yarn=(y.factor, y.original_max_position_embeddings, y.beta_fast,
              y.beta_slow, y.mscale, y.mscale_all_dim),
        eps=1e-6, aux_alpha=m.aux_loss_weight,
        **dict(dict(clients=4, tau=2, batch=2, base_lr=0.05, lr_decay=0.995,
                    alpha=5.0, eval_chunk=2, numerics=ref.REFERENCE), **kw))


def test_param_counts():
    """The published model counts 15.7B params; one chip's share of
    8-way expert parallelism, the dense layer and 4 MoE layers deep,
    535,060,992."""
    assert ds.CONFIG.param_count() == 15_706_484_224
    share = ds.chip_share()
    assert share.param_count() == 535_060_992
    assert share.moe.held == tuple(range(8))
    assert share.vocab_size == 12_800 and share.num_layers == 5
    assert ds.chip_share(chip=7).moe.held == tuple(range(56, 64))


def test_yarn_matches_published_equations():
    """`DeepseekV2YarnRotaryEmbedding` at the published values (dim 64,
    base 10000, factor 40, 4096 original positions, beta 32 / 1,
    mscale 0.707), transcribed independently in numpy."""
    dim, base, factor, orig = 64, 10000.0, 40.0, 4096

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    assert (low, high) == (10, 23)
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = freq_inter * (1 - mask) + freq_extra * mask
    np.testing.assert_allclose(
        layers.yarn_inv_freq(dim, base, ds.CONFIG.yarn), want, rtol=1e-12)
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert mla.softmax_scale(ds.CONFIG) == pytest.approx(
        192 ** -0.5 * mscale * mscale, rel=1e-12)
    assert mla.softmax_scale(ds.CONFIG) == pytest.approx(0.1147214, abs=1e-7)
    # cos/sin carry mscale(40, 0.707) / mscale(40, 0.707) = 1
    cos, sin = layers.rope_cos_sin(jnp.arange(5)[None], dim, base,
                                   ds.CONFIG.yarn)
    np.testing.assert_allclose(np.asarray(cos[0]),
                               np.cos(np.arange(5)[:, None] * want),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(sin[0]),
                               np.sin(np.arange(5)[:, None] * want),
                               atol=2e-6)


def _layer_params(cfg, seed=0):
    """One MoE layer's params of the uncut model (all experts held)."""
    uncut = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=()))
    return moe.moe_init(jax.random.key(seed), uncut)


def _share(p, ids):
    idx = jnp.asarray(ids)
    return dict(p, **{k: p[k][idx] for k in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("chips", [2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(chips):
    """Each chip's part (its held experts' outputs plus the shared
    experts every chip computes alike) summed over the chips, the shared
    experts counted once, is the uncut reference layer's output; the
    balance loss is the same on every chip."""
    cfg = small_config()
    p = _layer_params(cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
    per = cfg.moe.num_experts // chips
    outs, auxs = [], []
    for c in range(chips):
        ids = tuple(range(c * per, (c + 1) * per))
        share = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                                 held=ids))
        y, aux, _ = moe.moe_apply(_share(p, ids), share, x)
        outs.append(y)
        auxs.append(aux)
    shared = layers.mlp_apply(p["shared"], x.reshape(-1, cfg.d_model),
                              "swiglu").reshape(x.shape)
    total = sum(outs) - (chips - 1) * shared
    s = small_setup(cfg)._replace(held=tuple(range(cfg.moe.num_experts)))
    with jax.default_matmul_precision("highest"):
        want, want_aux = ref._moe(s, p, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    for a in auxs:
        assert float(a) == pytest.approx(float(want_aux), rel=1e-5)


@pytest.mark.parametrize("held,matches", [
    ((0, 1, 2, 3), True), (tuple(range(8)), True), ((), False)],
    ids=["dropless-share", "dropless-all", "capacity-all"])
def test_no_token_expert_pair_is_dropped(held, matches):
    """Every token routed to expert 0 (a router that all but forces it):
    a layer told which experts it holds, a share or all of them, runs
    dropless, still equals the dense reference and counts every
    token-expert pair bound for its held experts; a layer that holds all
    experts unnamed runs at a fixed capacity and drops the overflow."""
    cfg = small_config(held=held)
    ids = held or tuple(range(cfg.moe.num_experts))
    p = _share(_layer_params(cfg), ids)
    x = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (2, 16, cfg.d_model))
    p["router"] = p["router"].at[:, 0].set(1.0)
    y, _, rows = moe.moe_apply(p, cfg, x)
    s = small_setup(cfg)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._moe(s, p, x)
        probs = jax.nn.softmax(x.reshape(-1, cfg.d_model) @ p["router"])
    _, idx = jax.lax.top_k(probs, cfg.moe.top_k)
    assert bool(jnp.all(idx[:, 0] == 0))
    close = np.allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-5)
    assert close == matches
    if matches:
        assert int(rows) == int(np.isin(np.asarray(idx), ids).sum())


def _silos(vocab, n=8, t=16, silos=4, seed=0):
    rng = np.random.default_rng(seed)

    def ds_(k):
        seq = rng.integers(0, vocab, size=(k, t + 1)).astype(np.int32)
        return Dataset(seq[:, :-1], seq[:, 1:])

    return [ds_(n) for _ in range(silos)], ds_(4)


def test_two_fedadp_rounds_match_the_reference():
    """Two sequential FedAdp rounds of the client model through
    `FedServer` against the streaming reference, from one seed. Both sides
    compute in float32 on the CPU and differ by summation order, which
    the Gompertz weights at alpha 5 magnify: the per-round losses within
    1e-5 relative (seen: at most 1.3e-6 over three seeds), the FedAdp
    weights within 2e-4 (seen: 3e-5) and the params within 2e-3 of the
    largest change of any param (seen: 2.3e-4), each about ten times what
    was seen. The expert-row counter counts every pair bound for a held
    expert, in both passes."""
    cfg = small_config()
    nodes, test = _silos(cfg.vocab_size)
    fl = repro.FLConfig(num_clients=4, clients_per_round=4, local_steps=2,
                        method="fedadp", alpha=5.0, base_lr=0.05,
                        lr_decay=0.995, mode="sequential", engine="tree")
    seed = 3_000_000_019
    server = repro.FedServer(repro.lm_client(cfg, eval_chunk=2), fl, nodes,
                             test, batch_size=2, seed=seed)
    server.reset(seed)
    hist = server.run(2, eval_every=1, mode="scanned", block=2)
    prog = jax.device_get(server.state.params)

    s = small_setup(cfg)
    data = ref.reference_data(nodes, test)
    with jax.default_matmul_precision("highest"):
        want = ref.follow(s, data, seed, 2, 1)
    np.testing.assert_allclose(hist.loss, want["loss"], rtol=1e-5)
    np.testing.assert_allclose(np.stack(hist.weights),
                               np.stack(want["weights"]), atol=2e-4)
    np.testing.assert_allclose(hist.accuracy, list(want["acc"].values()),
                               atol=1e-6)
    np.testing.assert_allclose(hist.eval_loss,
                               list(want["eval_loss"].values()), rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path
    start = dict(flat(want["params0"])[0])
    theirs = dict(flat(want["params"])[0])
    scale = max(float(np.max(np.abs(np.asarray(theirs[k]) - start[k])))
                for k in theirs)
    for path, leaf in flat(prog)[0]:
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(theirs[path]),
                                   atol=2e-3 * scale, err_msg=str(path))
    # 4 silos x 2 steps x 2 passes x 2 MoE layers x 2*16 tokens x top-3,
    # of which the pairs bound for the 4 held experts of 8
    assert len(hist.expert_rows) == 2
    assert all(0 < r <= 4 * 2 * 2 * 2 * 32 * 3 for r in hist.expert_rows)
    assert len(hist.eval_loss) == 2 and len(hist.eval_expert_rows) == 2
