"""Step builders shared by dryrun / train / serve launchers.

Each builder returns (fn, args, in_shardings) where `args` are
jax.ShapeDtypeStruct trees — `jax.jit(fn, in_shardings=...).lower(*args)`
never allocates device memory.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import shapes as shapes_mod
from repro.configs.registry import get as get_arch
from repro.core import fl as fl_mod
from repro.models import sharding, transformer
from repro.models.config import ModelConfig

SEQUENTIAL_THRESHOLD = 40e9  # params; larger models use the sequential engine


def fl_mode_for(cfg: ModelConfig) -> str:
    return "sequential" if cfg.param_count() > SEQUENTIAL_THRESHOLD else "parallel"


def _replicate_extra(cfg: ModelConfig, mesh: Mesh, mqa_replicate_kv: bool):
    """KV projections to replicate when heads can't fill the model axis."""
    if mqa_replicate_kv and cfg.num_kv_heads < mesh.shape.get("model", 1):
        return frozenset({"wk", "wv"})
    return frozenset()


def _sds(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _batch_total(mesh: Mesh) -> int:
    t = 1
    for a in sharding.batch_axes(mesh):
        t *= mesh.shape[a]
    return t


def params_sds(cfg: ModelConfig):
    return jax.eval_shape(functools.partial(transformer.init_params, cfg=cfg),
                          jax.random.key(0))


# ------------------------------------------------------------- train


def build_train_step(cfg: ModelConfig, mesh: Mesh, shape: shapes_mod.InputShape,
                     *, fl_mode: str | None = None, method: str = "fedadp",
                     stale: bool = False, local_steps: int = 1,
                     q_chunk: int = 0, angle_filter: str = "all",
                     mqa_replicate_kv: bool = False,
                     ssm_unroll: int = 0, loss_chunk: int = 0,
                     rs_grads: bool = False, ssm_stream_bf16: bool = False,
                     act_constrain: bool = False, moe_combine_bf16: bool = False,
                     telemetry: str | None = None):
    import dataclasses

    if q_chunk:
        cfg = dataclasses.replace(cfg, q_chunk=q_chunk)
    if loss_chunk:
        cfg = dataclasses.replace(cfg, loss_chunk=loss_chunk)
    if ssm_unroll and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, scan_unroll=ssm_unroll))
    if ssm_stream_bf16 and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, stream_dtype="bfloat16"))
    if act_constrain:
        cfg = dataclasses.replace(cfg, act_constrain=True)
        sharding.set_constraint_mesh(mesh)
    if moe_combine_bf16 and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, combine_dtype="bfloat16"))
    rep_extra = _replicate_extra(cfg, mesh, mqa_replicate_kv)
    fl_mode = fl_mode or fl_mode_for(cfg)
    dtot = _batch_total(mesh)
    K = dtot if fl_mode == "parallel" else 16
    B = max(shape.global_batch // K, 1)
    tau = local_steps

    loss_fn = functools.partial(transformer.loss_fn, cfg=cfg)

    def loss(params, batch):
        return loss_fn(params, batch=batch)

    # telemetry=None keeps the lowered step's jaxpr telemetry-free;
    # "node" adds the per-node tel/* metrics (repro.telemetry) and flows
    # through meta["flcfg"] so runtime state rebuilds see it too.
    flcfg = fl_mod.FLConfig(
        num_clients=K, clients_per_round=K, local_steps=tau, method=method,
        mode=fl_mode, stale_angles=stale, telemetry=telemetry,
    )

    p_sds = params_sds(cfg)
    prev_sds = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_sds)
    # one RoundState pytree carries the whole server-side round state
    # (params, Eq. 9 angles, prev delta, RNG key, round counter) — its
    # SDS comes from the same init the runtime uses, so the lowered
    # signature can never drift from init_round_state's layout.
    state_sds = jax.eval_shape(
        functools.partial(fl_mod.init_round_state, flcfg), p_sds)
    batch_one = shapes_mod.token_batch_specs(cfg, B, shape.seq_len)
    batch_sds = {
        k: jax.ShapeDtypeStruct((K, tau) + v.shape, v.dtype)
        for k, v in batch_one.items()
    }
    args = (
        state_sds, batch_sds,
        jax.ShapeDtypeStruct((K,), jnp.int32),
        jax.ShapeDtypeStruct((K,), jnp.float32),
    )

    fsdp = fl_mode == "sequential"
    p_shard = sharding.param_shardings(p_sds, mesh, fsdp=fsdp,
                                       replicate_extra=rep_extra)
    prev_shard = sharding.param_shardings(prev_sds, mesh, fsdp=fsdp,
                                          replicate_extra=rep_extra)

    delta_constraint = None
    if fl_mode == "parallel":
        # stacked per-client deltas: client axis on (pod, data), tensor dims
        # on the param's own model-axis spec.
        baxes = sharding.batch_axes(mesh)
        kspec = baxes if len(baxes) > 1 else baxes[0]
        spec_leaves = jax.tree.leaves(
            sharding.param_pspecs(p_sds, mesh, fsdp=False,
                                  replicate_extra=rep_extra),
            is_leaf=lambda x: isinstance(x, P),
        )

        def delta_constraint(deltas):
            leaves, treedef = jax.tree.flatten(deltas)
            out = [
                jax.lax.with_sharding_constraint(
                    d, NamedSharding(mesh, P(kspec, *s))
                )
                for d, s in zip(leaves, spec_leaves)
            ]
            return jax.tree.unflatten(treedef, out)

    angle_pred = (
        fl_mod.moe_dense_only_pred
        if (angle_filter == "dense_only" and cfg.moe is not None)
        else None
    )

    grad_constraint = None
    if fl_mode == "sequential" and rs_grads:
        # pin per-step grads to the FSDP param spec: batch-partial grads are
        # reduce-scattered onto the shard instead of all-reduced in full.
        gspec_leaves = jax.tree.leaves(
            sharding.param_pspecs(p_sds, mesh, fsdp=True,
                                  replicate_extra=rep_extra),
            is_leaf=lambda x: isinstance(x, P),
        )

        def grad_constraint(grads):
            leaves, treedef = jax.tree.flatten(grads)
            out = [
                jax.lax.with_sharding_constraint(g, NamedSharding(mesh, s))
                for g, s in zip(leaves, gspec_leaves)
            ]
            return jax.tree.unflatten(treedef, out)

    round_fn = sharding.traced_on(mesh, fl_mod.make_round_fn(
        loss, flcfg, delta_constraint, angle_pred, grad_constraint))
    if fl_mode == "parallel":
        b_shard = sharding.shard_batch_dim(mesh, batch_sds, default_dim=0)
    else:
        # K is the scan axis; shard the within-client batch dim instead
        def seq_leaf(name, x):
            if name == "positions":  # (K, tau, 3, B, T) — B at dim 3
                dim = 3
            else:
                dim = 2
            axes = sharding.batch_axes(mesh)
            total = _batch_total(mesh)
            spec = [None] * len(x.shape)
            if x.shape[dim] % total == 0 and x.shape[dim] >= total:
                spec[dim] = axes if len(axes) > 1 else axes[0]
            return NamedSharding(mesh, P(*spec))

        b_shard = {k: seq_leaf(k, v) for k, v in batch_sds.items()}
    rep = lambda t: sharding.replicated(mesh, t)
    state_shard = fl_mod.RoundState(
        params=p_shard, angle=rep(state_sds.angle), prev_delta=prev_shard,
        ef=None, dl_ef=None, bcast=None,
        rng=rep(state_sds.rng), round=rep(state_sds.round))
    in_shard = (state_shard, b_shard, rep(args[2]), rep(args[3]))
    out_sds = jax.eval_shape(round_fn, *args)
    out_shard = (state_shard, rep(out_sds[1]))
    # flcfg determines the RoundState pytree structure, so callers that
    # build a runtime state (launch/train.py) must use THIS config, not a
    # hand-rebuilt copy — ship it in meta (as a JSON-safe dict; dryrun
    # serializes meta into results/)
    meta = {"K": K, "B": B, "tau": tau, "fl_mode": fl_mode,
            "flcfg": dataclasses.asdict(flcfg)}
    return round_fn, args, in_shard, out_shard, meta


# ------------------------------------------------------------ prefill


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, shape: shapes_mod.InputShape,
                       *, fsdp: bool | None = None, q_chunk: int = 0):
    if q_chunk:
        import dataclasses

        cfg = dataclasses.replace(cfg, q_chunk=q_chunk)
    B, T = shape.global_batch, shape.seq_len
    if fsdp is None:
        fsdp = cfg.param_count() > SEQUENTIAL_THRESHOLD

    @functools.partial(sharding.traced_on, mesh)
    def prefill_step(params, batch):
        logits, aux, cache = transformer.forward(
            params, cfg, batch, mode="prefill", max_len=T
        )
        return logits[:, -1:], cache

    p_sds = params_sds(cfg)
    batch_sds = shapes_mod.token_batch_specs(cfg, B, T)
    p_shard = sharding.param_shardings(p_sds, mesh, fsdp=fsdp)
    b_shard = sharding.shard_batch_dim(mesh, batch_sds, default_dim=0)
    if "positions" in batch_sds:
        b_shard["positions"] = _pos_shard(mesh, batch_sds["positions"], dim=1)
    out_sds = jax.eval_shape(prefill_step, p_sds, batch_sds)
    out_shard = (
        sharding.shard_batch_dim(mesh, out_sds[0], default_dim=0),
        _cache_shardings(cfg, mesh, out_sds[1]),
    )
    return (prefill_step, (p_sds, batch_sds), (p_shard, b_shard), out_shard,
            {"B": B, "T": T})


def _pos_shard(mesh, x, dim):
    axes = sharding.batch_axes(mesh)
    total = _batch_total(mesh)
    spec = [None] * len(x.shape)
    if x.shape[dim] % total == 0 and x.shape[dim] >= total:
        spec[dim] = axes if len(axes) > 1 else axes[0]
    return NamedSharding(mesh, P(*spec))


# ------------------------------------------------------------- decode


def _cache_shardings(cfg, mesh, cache_sds):
    """Decode-cache rules: batch dim over (pod,data); if B is unshardable
    (long_500k B=1) the sequence dim of attention caches goes on "data";
    SSM inner dims follow their params onto "model"."""
    axes = sharding.batch_axes(mesh)
    total = _batch_total(mesh)
    msize = mesh.shape.get("model", 1)
    baxes = axes if len(axes) > 1 else axes[0]

    def leaf_with_path(path, x):
        keys = tuple(getattr(k, "key", getattr(k, "name", "")) for k in path)
        name = keys[-1]
        nd = len(x.shape)
        spec = [None] * nd
        # dim0 = scan group axis (never sharded); dim1 = batch
        if nd >= 2 and x.shape[1] % total == 0 and x.shape[1] >= total:
            spec[1] = baxes
        elif name in ("k", "v", "ckv", "krope", "cross_k", "cross_v") and nd >= 3:
            if x.shape[2] % mesh.shape.get("data", 1) == 0:
                spec[2] = "data"
        if name in ("k", "v", "cross_k", "cross_v") and nd >= 4:
            if x.shape[3] % msize == 0 and x.shape[3] >= msize:
                spec[3] = "model"
        if name == "h" and nd >= 3 and x.shape[2] % msize == 0:
            spec[2] = "model"
        if name == "conv" and nd >= 4 and x.shape[3] % msize == 0:
            spec[3] = "model"
        if name == "S" and nd >= 3 and x.shape[2] % msize == 0:
            spec[2] = "model"  # rwkv heads
        return NamedSharding(mesh, P(*spec))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_sds)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf_with_path(p, x) for p, x in flat]
    )


def build_decode_step(cfg: ModelConfig, mesh: Mesh, shape: shapes_mod.InputShape,
                      *, fsdp: bool | None = None):
    cfg = shapes_mod.config_for_shape(cfg, shape)
    B, S = shape.global_batch, shape.seq_len
    if fsdp is None:
        fsdp = cfg.param_count() > SEQUENTIAL_THRESHOLD

    def serve_step(params, token, cache, pos):
        return transformer.decode_step(params, cfg, token, cache, pos)

    p_sds = params_sds(cfg)
    d = shapes_mod.decode_specs(cfg, B, S)
    p_shard = sharding.param_shardings(p_sds, mesh, fsdp=fsdp)
    tok_shard = sharding.shard_batch_dim(mesh, d["token"], default_dim=0)
    cache_shard = _cache_shardings(cfg, mesh, d["cache"])
    pos_shard = NamedSharding(mesh, P())
    args = (p_sds, d["token"], d["cache"], d["pos"])
    in_shard = (p_shard, tok_shard, cache_shard, pos_shard)
    out_sds = jax.eval_shape(serve_step, *args)
    out_shard = (
        sharding.shard_batch_dim(mesh, out_sds[0], default_dim=0),
        _cache_shardings(cfg, mesh, out_sds[1]),
    )
    return serve_step, args, in_shard, out_shard, {"B": B, "S": S,
                                                   "window": cfg.sliding_window}


def build_step(arch: str, shape_name: str, mesh: Mesh, **kw):
    cfg = get_arch(arch)
    shape = shapes_mod.SHAPES[shape_name]
    if shape.kind == "train":
        return build_train_step(cfg, mesh, shape, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, mesh, shape, **kw)
    return build_decode_step(cfg, mesh, shape, **kw)
