"""FedAdp aggregation as an explicit shard_map collective schedule.

The pjit engine (core/fl.py) leaves collective placement to GSPMD. This
module expresses the SAME aggregation — the paper's actual contribution —
with hand-placed collectives under `jax.shard_map`, which makes the
communication pattern auditable and lets §Perf reason about it directly:

  per model-shard:   g_avg = psum_{clients}(psi_i * delta_i)        (1)
  per client:        dot_i = psum_{model}(<delta_i, g_avg>_shard)   (2)
                     |d_i|^2, |g|^2 likewise
  replicated:        theta -> Gompertz -> softmax weights           (3)
  per model-shard:   delta = psum_{clients}(w_i * delta_i)          (4)

Exactly two client-axis tree reductions (1)(4) plus O(K) scalar psums (2)
per round — the minimum the algorithm admits with exact same-round angles.

Two engines share this schedule:

* ``engine="tree"`` (reference) — per-leaf reductions; tensor dims may be
  sharded over the model axes, so big-model leaves stay sharded.
* ``engine="flat"`` — the stacked deltas are raveled once into a (K, N)
  f32 buffer row-sharded over the client axis ("pod","data"); steps
  (1)(2)(4) run as the fused Pallas kernels (`kernels.weighted_agg`,
  `kernels.round_stats`) on each shard's rows, followed by the same psums.
  This is the scalable large-cohort path: per-device work is one HBM pass
  over K/num_shards rows regardless of K. On a client-only mesh each
  client's delta row is contiguous on its shard; on a 2D (client x
  model) mesh the buffer becomes a grid of (K_loc, N_loc) tiles instead
  (`make_round_ops_2d`) — each model shard ravels its LOCAL leaf blocks
  (treemath.blocked_ravel_local, no all-gather), quantization chunks are
  shard-local (the 2D wire layout), dots/sqnorms psum over both axes and
  the aggregates over the client axis only, so model-sharded leaves stay
  sharded end-to-end, for flat exactly as for tree.

`make_round_ops` packages the whole flat round — stats psums, the
replicated O(K) weighting, and the aggregate psum — as ONE shard_map
region; core/fl.py's `engine="flat_sharded"` round path reuses it so the
pjit and shard_map stacks aggregate through literally the same kernels.
The RoundState contract lives one level up: core/fl.py gathers the
selected clients' Eq. 9 slots out of `RoundState.angle` before entering
this region and scatters the results back after it, so the shard_map
schedule stays a pure (K,)-shaped aggregation op and the region composes
unchanged with the scanned driver (`core.driver` puts the whole round —
this region included — inside `lax.scan`).

Works on any mesh whose client axis is "data" (+"pod") and whose tensor
axes follow models/sharding.param_pspecs; on a 1x1 host mesh it reduces to
plain math (used by the CPU equivalence test).

Telemetry contract: the per-client quantities this schedule produces
(theta, smoothed theta, softmax weights) leave the shard_map region
replicated, so the `FLConfig(telemetry="node")` tel/* metrics built from
them in core/fl.py are exact per-node rows — identical across shards and
matching the unsharded engines to 1e-5 (pinned by tests/test_telemetry.py's
8-device subprocess leg).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import treemath, weighting
from repro.kernels import round_stats as round_stats_mod
from repro.kernels import weighted_agg as weighted_agg_mod

PyTree = Any


MODEL_AXIS = "model"


def _client_axes(mesh: Mesh):
    caxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not caxes:
        raise ValueError(
            f"mesh axes {mesh.axis_names} contain no client axis — the "
            "FedAdp client dimension shards over ('pod', 'data')")
    return caxes


def model_axis_size(mesh: Mesh) -> int:
    """Size of the mesh's "model" axis (1 when absent): > 1 selects the
    2D (client x model) layout for the flat engine."""
    if mesh is None or MODEL_AXIS not in mesh.axis_names:
        return 1
    return int(mesh.shape[MODEL_AXIS])


def client_axis_size(mesh: Mesh) -> int:
    size = 1
    for a in _client_axes(mesh):
        size *= mesh.shape[a]
    return size


def flat_client_sharding(mesh: Mesh) -> NamedSharding:
    """Row sharding for the (K, N) flat delta buffer: K over ("pod","data")."""
    caxes = _client_axes(mesh)
    return NamedSharding(mesh, P(caxes if len(caxes) > 1 else caxes[0]))


def _client_axis(mesh: Mesh):
    caxes = _client_axes(mesh)
    return caxes if len(caxes) > 1 else caxes[0]


def _shard_slots(values, caxis):
    """Global client slots owned by this shard (rows are client-sharded)."""
    k_loc = values.shape[0]
    return jax.lax.axis_index(caxis) * k_loc + jnp.arange(k_loc)


def _shard_agg(w_loc, values, scales, *, transport, n,
               group_size):
    """Per-shard weighted aggregation over the local rows, f32 out.

    scales is None for f32/bf16 wire buffers (the kernels' in-VMEM
    astype(f32) IS the bf16 dequant); int8 routes through the fused
    in-register dequant kernel with the per-(client, chunk) scales, int4
    through the grouped-scale packed-nibble kernel (`n` is the logical
    width the packed buffer unpacks to).
    """
    if scales is None:
        return weighted_agg_mod.weighted_agg(
            w_loc, values, out_dtype=jnp.float32)
    if transport == "int4":
        return weighted_agg_mod.weighted_agg_q4(
            w_loc, values, scales, n=n, group_size=group_size)
    return weighted_agg_mod.weighted_agg_q(
        w_loc, values, scales)


def _shard_stats(values, scales, g_flat, mask, *, transport,
                 group_size):
    """Per-shard fused angle statistics over the local rows."""
    if scales is None:
        return round_stats_mod.round_stats(
            values, g_flat, mask)
    if transport == "int4":
        return round_stats_mod.round_stats_q4(
            values, scales, g_flat, mask, group_size=group_size)
    return round_stats_mod.round_stats_q(
        values, scales, g_flat, mask)


def make_round_ops(mesh: Mesh, *, alpha: float, method: str = "fedadp",
                   transport: str = "f32",
                   group_size: int = 0):
    """The whole aggregation round as ONE shard_map call.

    PR 2's `make_flat_ops` exposed stats and aggregate as two separate
    shard_map regions, which re-entered the collective schedule (and
    re-staged the row shards) between them. The weighting in between is
    O(K) replicated scalar math — Eq. 9 smoothing + Gompertz softmax — so
    it folds into the same region: stats psums -> replicated weighting ->
    aggregate psum, one schedule, the buffer staying put on its shard
    (the two-region form is gone; this is the only flat schedule). For
    fedavg/fedprox the weighting IS psi, so the aggregate reuses the
    stats' g_flat and the round is a single client-axis reduction.

    transport selects the buffer's wire dtype (repro.transport):
    "f32"/"bf16" stream it through the plain kernels (bf16 dequant is the
    kernels' in-VMEM astype); "int8" adds a row-sharded
    (K, num_chunks(N)) f32 scales operand and routes through the fused
    in-register dequant kernels; "int4" row-shards the PACKED
    (K, ceil(N/2)) byte buffer plus its (K, num_groups) grouped scales
    (`group_size` required) through the packed-nibble kernels — in every
    case the per-shard partial dots/sqnorms and aggregates are psum'd
    exactly as in the f32 path, so scales never cross shards. mask is a
    REQUIRED (N,) f32 vector in LOGICAL element space (pass ones for
    unfiltered stats — multiplying by 1.0 is exact in f32, so the result
    is bit-identical to the unmasked kernel); for int4 it doubles as the
    carrier of the logical width N the packed rows unpack to.

    Returns round_op(values[, scales], psi, mask, smoothed_sel, count_sel,
    data_sizes) -> (g_flat, dots, sqs, sqg, delta_flat, theta, theta_sm,
    w), where smoothed_sel/count_sel are the selected clients' angle-state
    slots and theta_sm applies Eq. 9 with the same float ops as core.fl's
    scatter-then-gather, so trajectories match the unsharded engines.
    """
    caxis = _client_axis(mesh)
    row_spec = P(caxis)
    if transport == "int4":
        from repro import transport as transport_mod

        group_size = group_size or transport_mod.GROUP_SIZE
        transport_mod.validate_group_size(group_size)

    def _body(values, scales, psi, mask, smoothed_sel, count_sel,
              data_sizes):
        my = _shard_slots(values, caxis)
        n = mask.shape[0]  # logical width (!= packed width for int4)
        kw = dict(transport=transport, group_size=group_size)
        with jax.named_scope("fl.stats"):
            g_flat = jax.lax.psum(
                _shard_agg(psi[my], values, scales, n=n, **kw),
                caxis)
            d_loc, s_loc, sqg = _shard_stats(values, scales, g_flat, mask,
                                             **kw)
            k = psi.shape[0]
            dots = jax.lax.psum(
                jnp.zeros((k,), jnp.float32).at[my].set(d_loc), caxis)
            sqs = jax.lax.psum(
                jnp.zeros((k,), jnp.float32).at[my].set(s_loc), caxis)
            theta = weighting.instantaneous_angle(dots, sqs, sqg)
            cnt = count_sel.astype(jnp.float32) + 1.0
            theta_sm = ((cnt - 1.0) * smoothed_sel + theta) / cnt  # Eq. 9
        if method == "fedadp":
            with jax.named_scope("fl.weights"):
                w = weighting.fedadp_weights(theta_sm, data_sizes, alpha)
            with jax.named_scope("fl.aggregate"):
                delta_flat = jax.lax.psum(
                    _shard_agg(w[my], values, scales, n=n, **kw),
                    caxis)
        else:  # w == psi: the stats' aggregate IS the round delta
            w = psi
            delta_flat = g_flat
        return g_flat, dots, sqs, sqg, delta_flat, theta, theta_sm, w

    outs = (P(),) * 8
    if transport in ("int8", "int4"):
        return jax.shard_map(_body, mesh=mesh,
                             in_specs=(row_spec, row_spec) + (P(),) * 5,
                             out_specs=outs, check_vma=False)
    return jax.shard_map(
        lambda values, *rest: _body(values, None, *rest), mesh=mesh,
        in_specs=(row_spec,) + (P(),) * 5, out_specs=outs, check_vma=False)


def _spec_tree(pspecs):
    """(leaves, unflatten) over a PartitionSpec tree."""
    is_p = lambda x: isinstance(x, P)
    leaves = jax.tree.leaves(pspecs, is_leaf=is_p)
    structure = jax.tree.structure(pspecs, is_leaf=is_p)
    return leaves, lambda ls: jax.tree.unflatten(structure, ls)


def _stacked_specs(pspecs, caxis):
    """Stacked-delta specs: client axis leading, param dims per pspec."""
    leaves, tree_of = _spec_tree(pspecs)
    return tree_of([P(caxis, *tuple(s)) for s in leaves])


def _blocked_unstack_local(vec, layout, *, dtypes=None, gather_rows=False):
    """Per-leaf outputs from a blocked (…, width) array, inside the region.

    A model-sharded leaf's segment IS its local block (reshape only — the
    leaf stays sharded); a replicated leaf's column slices are re-joined
    with a small all_gather over the model axis (O(leaf size), never the
    full buffer). `gather_rows=True` handles (k_loc, width) row blocks
    (gather on axis 1), else (width,) vectors.
    """
    import math as _math

    m = layout.n_shards
    segs = treemath.blocked_split(vec, layout)
    out = []
    for i, (seg, shape, sdim) in enumerate(
            zip(segs, layout.shapes, layout.sharded_dims)):
        dt = layout.dtypes[i] if dtypes is None else dtypes
        lead = vec.shape[:-1]
        if sdim >= 0:
            local = list(shape)
            local[sdim] //= m
            out.append(seg.reshape(lead + tuple(local)).astype(dt))
        else:
            axis = 1 if gather_rows else 0
            full = jax.lax.all_gather(seg, MODEL_AXIS, axis=axis,
                                      tiled=True)
            size = _math.prod(shape) if shape else 1
            full = jax.lax.slice_in_dim(full, 0, size, axis=axis)
            out.append(full.reshape(lead + shape).astype(dt))
    return out


def make_round_ops_2d(mesh: Mesh, template_stacked: PyTree, pspecs: PyTree,
                      *, alpha: float, method: str = "fedadp",
                      transport: str = "f32",
                      group_size: int = 0, keep=None):
    """`make_round_ops` on a 2D (client x model) mesh — tree in, tree out.

    The flat buffer becomes a P(caxis, "model") grid of (K_loc, N_loc)
    tiles: each device RAVELS its local stacked leaf blocks in-region
    (treemath.blocked_ravel_local — model-sharded leaves reshape locally,
    replicated leaves ceil-split column-wise, so no leaf is ever gathered
    to full width), quantizes them shard-locally (transport != "f32":
    int8/int4 scale chunks are per-shard, never straddling a model-axis
    split — THE wire layout on 2D meshes), and runs the fused kernels on
    its tile. Partial dots/sqnorms psum over BOTH axes; sqg over the
    model axis only (g is already client-reduced); the replicated Eq. 9 +
    Gompertz softmax stays scalar; and the two aggregates psum over the
    client axis ONLY — aggregated columns stay model-sharded, so the tree
    contract's "keeps sharded leaves sharded" now holds for flat too
    (replicated leaves re-join via an O(leaf) all_gather of their column
    slices).

    `template_stacked`/`pspecs`: the K-stacked delta tree (leading axis
    padded to the client-axis size) and the UNSTACKED param
    PartitionSpecs (models/sharding.param_pspecs — buffer sharding is
    config-derived). `keep`: per-leaf bool angle-filter flags (None =
    all; replaces the 1D form's mask operand, baked as a shard-identical
    (N_loc,) constant).

    Returns round_op(deltas_stacked, psi, smoothed_sel, count_sel,
    data_sizes) -> (g_tree, dots, sqs, sqg, delta_tree, theta, theta_sm,
    w): the 1D op's 8-tuple with the two flat vectors replaced by
    UNSTACKED f32 trees, sharded per `pspecs`.
    """
    caxes = _client_axes(mesh)
    caxis = caxes if len(caxes) > 1 else caxes[0]
    msize = model_axis_size(mesh)
    if msize <= 1:
        raise ValueError(
            "make_round_ops_2d needs a mesh with a 'model' axis of size "
            "> 1; use make_round_ops for client-only sharding")
    layout = treemath.blocked_layout(template_stacked, pspecs, msize,
                                     MODEL_AXIS)
    if transport == "int4":
        from repro import transport as transport_mod

        group_size = group_size or transport_mod.GROUP_SIZE
        transport_mod.validate_group_size(group_size)
    mask_const = treemath.blocked_segment_mask(layout, keep)
    n_loc = layout.width
    kw = dict(transport=transport, group_size=group_size)

    def _body(deltas, psi, smoothed_sel, count_sel, data_sizes):
        j = jax.lax.axis_index(MODEL_AXIS)
        with jax.named_scope("fl.uplink"):
            x = treemath.blocked_ravel_local(jax.tree.leaves(deltas),
                                             layout, j)
            if transport == "f32":
                values, scales = x, None
            else:
                from repro import transport as transport_mod

                q = transport_mod.quantize(
                    x, transport,
                    group_size=group_size or transport_mod.GROUP_SIZE)
                values, scales = q.values, q.scales
        my = _shard_slots(x, caxis)
        with jax.named_scope("fl.stats"):
            g_loc = jax.lax.psum(
                _shard_agg(psi[my], values, scales, n=n_loc, **kw),
                caxis)
            d_loc, s_loc, sqg_loc = _shard_stats(values, scales, g_loc,
                                                 mask_const, **kw)
            kp = psi.shape[0]
            both = caxes + (MODEL_AXIS,)
            dots = jax.lax.psum(
                jnp.zeros((kp,), jnp.float32).at[my].set(d_loc), both)
            sqs = jax.lax.psum(
                jnp.zeros((kp,), jnp.float32).at[my].set(s_loc), both)
            sqg = jax.lax.psum(sqg_loc, MODEL_AXIS)
            theta = weighting.instantaneous_angle(dots, sqs, sqg)
            cnt = count_sel.astype(jnp.float32) + 1.0
            theta_sm = ((cnt - 1.0) * smoothed_sel + theta) / cnt  # Eq. 9
        if method == "fedadp":
            with jax.named_scope("fl.weights"):
                w = weighting.fedadp_weights(theta_sm, data_sizes, alpha)
            with jax.named_scope("fl.aggregate"):
                delta_loc = jax.lax.psum(
                    _shard_agg(w[my], values, scales, n=n_loc, **kw),
                    caxis)
        else:  # w == psi: the stats' aggregate IS the round delta
            w = psi
            delta_loc = g_loc
        with jax.named_scope("fl.stats"):
            g_tree = jax.tree.unflatten(
                jax.tree.structure(deltas),
                _blocked_unstack_local(g_loc, layout, dtypes=jnp.float32))
        with jax.named_scope("fl.aggregate"):
            delta_tree = jax.tree.unflatten(
                jax.tree.structure(deltas),
                _blocked_unstack_local(delta_loc, layout,
                                       dtypes=jnp.float32))
        return g_tree, dots, sqs, sqg, delta_tree, theta, theta_sm, w

    spec_leaves, tree_of = _spec_tree(pspecs)
    unstacked = tree_of(spec_leaves)
    in_specs = (_stacked_specs(pspecs, caxis), P(), P(), P(), P())
    out_specs = (unstacked, P(), P(), P(), unstacked, P(), P(), P())
    return jax.shard_map(_body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_blocked_roundtrip(mesh: Mesh, template_stacked: PyTree,
                           pspecs: PyTree, *, transport: str,
                           group_size: int = 0):
    """Shard-local wire roundtrip for the TREE engine on a 2D mesh.

    On a (client x model) mesh the uplink wire is quantized per
    (client, model-shard) block — scale chunks are shard-local (see
    `make_round_ops_2d`). The tree reference must consume the SAME
    reconstruction without ever raveling a model-sharded leaf to full
    width (the global `tree_ravel_stacked` + quantize path would
    all-gather it): this region ravels each shard's local blocks, runs
    quantize -> dequantize on the (K_loc, N_loc) tile, and returns the
    STACKED f32 reconstruction — zero collectives for model-sharded
    leaves, an O(leaf) all_gather to re-join each replicated leaf's
    column slices. The tree engine then runs its per-leaf reference
    reductions on the result, preserving the "tree never reads the wire
    buffer" contract (it reads the dequantized tree).

    Returns roundtrip(deltas_stacked) -> stacked f32 tree, sharded like
    the input (client axis leading, tensor dims per `pspecs`).
    """
    caxes = _client_axes(mesh)
    caxis = caxes if len(caxes) > 1 else caxes[0]
    msize = model_axis_size(mesh)
    layout = treemath.blocked_layout(template_stacked, pspecs, msize,
                                     MODEL_AXIS)
    from repro import transport as transport_mod

    if transport == "int4":
        group_size = group_size or transport_mod.GROUP_SIZE
        transport_mod.validate_group_size(group_size)

    def _body(deltas):
        j = jax.lax.axis_index(MODEL_AXIS)
        x = treemath.blocked_ravel_local(jax.tree.leaves(deltas), layout, j)
        q = transport_mod.quantize(
            x, transport, group_size=group_size or transport_mod.GROUP_SIZE)
        recon = transport_mod.dequantize(q)  # (k_loc, N_loc) f32
        return jax.tree.unflatten(
            jax.tree.structure(deltas),
            _blocked_unstack_local(recon, layout, dtypes=jnp.float32,
                                   gather_rows=True))

    stacked = _stacked_specs(pspecs, caxis)
    return jax.shard_map(_body, mesh=mesh, in_specs=(stacked,),
                         out_specs=stacked, check_vma=False)


def make_buffered_flush_ops(mesh: Mesh, *, alpha: float,
                            method: str = "fedadp", beta: float = 0.0):
    """The buffered-async flush as ONE shard_map call (core/fl.py's
    aggregation="buffered" under engine="flat_sharded").

    Exactly `make_round_ops`' schedule — (1) psi-weighted psum, (2) stat
    psums, (3) replicated weighting, (4) weighted psum — but over the
    report buffer's rows instead of this round's uplink. Two differences:

    * No scales operands: wire compression happened at ADMISSION, so the
      buffer always holds dequantized f32 rows and the region streams
      them through the plain kernels regardless of the config transport.
    * Step (3) is the staleness-aware weighting
      (`weighting.buffered_*_weights`): sizes/age/landed ride in as
      replicated (K,) operands and non-landed rows — including client-
      axis padding rows, which must be padded landed=False — get exactly
      zero weight, so they contribute nothing to the aggregate psum.

    flush_op(values, psi, mask, smoothed_sel, count_sel, sizes, age,
    landed) -> (g_flat, dots, sqs, sqg, delta_flat, theta, theta_sm, w),
    mirroring `make_round_ops`' output row so core/fl.py's buffered path
    consumes both identically.

    On a 2D (client x model) mesh the buffer's COLUMNS also shard: the
    report buffer stays a global f32 (K, Np) array (admission is
    unchanged — it dequantizes at landing), but Np must be padded to a
    multiple of the model-axis size (core/fl.py pads with zero columns
    and slices the outputs back) and values/mask ride in as
    P(caxis, "model") / P("model") tiles. Each device flushes its
    (K_loc, Np/msize) tile; dots/sqs psum over both axes, sqg over the
    model axis, and the two aggregates psum over the client axis only —
    g_flat/delta_flat come back as model-sharded (Np,) vectors.
    """
    caxis = _client_axis(mesh)
    caxes = _client_axes(mesh)
    msize = model_axis_size(mesh)
    stat_axes = caxes + (MODEL_AXIS,) if msize > 1 else caxes

    def _body(values, psi, mask, smoothed_sel, count_sel, sizes, age,
              landed):
        my = _shard_slots(values, caxis)
        with jax.named_scope("fl.stats"):
            g_flat = jax.lax.psum(
                weighted_agg_mod.weighted_agg(
                    psi[my], values, out_dtype=jnp.float32),
                caxis)
            d_loc, s_loc, sqg = round_stats_mod.round_stats(
                values, g_flat, mask)
            k = psi.shape[0]
            dots = jax.lax.psum(
                jnp.zeros((k,), jnp.float32).at[my].set(d_loc), stat_axes)
            sqs = jax.lax.psum(
                jnp.zeros((k,), jnp.float32).at[my].set(s_loc), stat_axes)
            if msize > 1:
                sqg = jax.lax.psum(sqg, MODEL_AXIS)
            theta = weighting.instantaneous_angle(dots, sqs, sqg)
            cnt = count_sel.astype(jnp.float32) + 1.0
            theta_sm = ((cnt - 1.0) * smoothed_sel + theta) / cnt  # Eq. 9
        with jax.named_scope("fl.weights"):
            if method == "fedadp":
                w = weighting.buffered_fedadp_weights(
                    theta_sm, sizes, age, landed, alpha, beta)
            else:
                w = weighting.buffered_fedavg_weights(sizes, age, landed,
                                                      beta)
        with jax.named_scope("fl.aggregate"):
            delta_flat = jax.lax.psum(
                weighted_agg_mod.weighted_agg(
                    w[my], values, out_dtype=jnp.float32),
                caxis)
        return g_flat, dots, sqs, sqg, delta_flat, theta, theta_sm, w

    if msize > 1:
        col = P(MODEL_AXIS)
        return jax.shard_map(
            _body, mesh=mesh,
            in_specs=(P(caxis, MODEL_AXIS), P(), col) + (P(),) * 5,
            out_specs=(col, P(), P(), P(), col, P(), P(), P()),
            check_vma=False)
    return jax.shard_map(_body, mesh=mesh,
                         in_specs=(P(caxis),) + (P(),) * 7,
                         out_specs=(P(),) * 8, check_vma=False)


def fedadp_aggregate(mesh: Mesh, delta_pspecs: PyTree, *, alpha: float,
                     method: str = "fedadp", engine: str = "tree",
                     transport: str = "f32",
                     group_size: int = 0):
    """Build an aggregation fn over K-stacked deltas.

    delta_pspecs: PartitionSpec tree for the STACKED deltas — leading axis
    = client axis over ("pod","data"), remaining dims per param sharding.

    engine="tree" (reference) runs per-leaf reductions and supports
    model-axis-sharded leaves; engine="flat" ravels the stacked tree into a
    client-row-sharded (K, N) buffer and runs the fused Pallas kernels per
    shard in ONE shard_map region (`make_round_ops`) — it requires
    client-only sharding and is the large-cohort fast path. `transport`
    (flat engine only) compresses the buffer to the wire dtype
    before aggregation (repro.transport; f32 is the reference wire).

    Returns agg(deltas, data_sizes, smoothed_prev, count_prev) ->
      (weighted_delta, theta, theta_smoothed, weights); weighted_delta is
      sharded like one param tree (tree engine) or replicated f32 (flat
      engine). smoothed/count are the selected clients' angle-state slots
      (Eq. 9 is applied inside, matching core.fl).
    """
    if engine == "flat":
        return _fedadp_aggregate_flat(mesh, delta_pspecs, alpha=alpha,
                                      method=method, transport=transport,
                                      group_size=group_size)
    if engine != "tree":
        raise ValueError(f"unknown engine {engine!r}")
    if transport != "f32":
        raise ValueError(
            "the tree engine never reads quantized buffers (ROADMAP "
            "transport contract); use engine='flat' for transport="
            f"{transport!r}")
    caxes = _client_axes(mesh)
    caxis = caxes if len(caxes) > 1 else caxes[0]

    spec_leaves = jax.tree.leaves(delta_pspecs, is_leaf=lambda x: isinstance(x, P))
    out_specs_leaves = [P(*s[1:]) for s in spec_leaves]  # drop client axis

    def body(deltas, data_sizes, smoothed_prev, count_prev):
        # deltas: local shard — leaves (K_loc, ...); replicated args full (K,)
        leaves = jax.tree.leaves(deltas)
        k_loc = leaves[0].shape[0]
        idx = jax.lax.axis_index(caxis)  # flattened over (pod, data)
        my_slots = idx * k_loc + jnp.arange(k_loc)

        psi_avg = weighting.fedavg_weights(data_sizes)

        def wsum(w_full):
            """psum over clients of w[k] * delta[k] (model shard local)."""
            w_loc = w_full[my_slots]

            def leaf(x):
                xf = x.astype(jnp.float32)
                part = jnp.tensordot(w_loc, xf, axes=1)
                return jax.lax.psum(part, caxis)

            return jax.tree.map(leaf, deltas)

        g_avg = wsum(psi_avg)  # (1)

        # (2) per-local-client stats, then psum over the non-client axes.
        # A leaf NOT sharded over some tensor axis is replicated there and
        # would be counted size(axis) times by that psum — divide each
        # leaf's contribution by its replication factor first.
        other_axes = tuple(a for a in mesh.axis_names if a not in caxes)

        def repl_factor(spec):
            used = set()
            for entry in tuple(spec)[1:]:
                if entry is None:
                    continue
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    used.add(a)
            f = 1
            for a in other_axes:
                if a not in used:
                    f *= mesh.shape[a]
            return float(f)

        def stats(x, g, spec):
            xf = x.astype(jnp.float32)
            gf = g.astype(jnp.float32)[None]
            axes_ = tuple(range(1, xf.ndim))
            inv = 1.0 / repl_factor(spec)
            return (jnp.sum(xf * gf, axis=axes_) * inv,
                    jnp.sum(xf * xf, axis=axes_) * inv,
                    jnp.sum(gf[0] * gf[0]) * inv)

        parts = [stats(x, g, s) for x, g, s in
                 zip(leaves, jax.tree.leaves(g_avg), spec_leaves)]
        dot_loc = sum(p[0] for p in parts)
        sq_loc = sum(p[1] for p in parts)
        sqg = sum(p[2] for p in parts)
        if other_axes:
            dot_loc = jax.lax.psum(dot_loc, other_axes)
            sq_loc = jax.lax.psum(sq_loc, other_axes)
            sqg = jax.lax.psum(sqg, other_axes)

        # gather per-client stats to all shards (K,) — O(K) scalars
        k_total = data_sizes.shape[0]
        dot_full = jnp.zeros((k_total,), jnp.float32).at[my_slots].set(dot_loc)
        sq_full = jnp.zeros((k_total,), jnp.float32).at[my_slots].set(sq_loc)
        dot_full = jax.lax.psum(dot_full, caxis)
        sq_full = jax.lax.psum(sq_full, caxis)

        theta = weighting.instantaneous_angle(dot_full, sq_full, sqg)  # (3)
        cnt = count_prev.astype(jnp.float32) + 1.0
        theta_sm = ((cnt - 1.0) * smoothed_prev + theta) / cnt  # Eq. 9
        if method == "fedadp":
            w = weighting.fedadp_weights(theta_sm, data_sizes, alpha)
        else:
            w = psi_avg
        return wsum(w), theta, theta_sm, w  # (4)

    tree_of = lambda leaves: jax.tree.unflatten(
        jax.tree.structure(delta_pspecs, is_leaf=lambda x: isinstance(x, P)),
        leaves,
    )
    in_specs = (tree_of(spec_leaves), P(), P(), P())
    out_specs = (tree_of(out_specs_leaves), P(), P(), P())
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _fedadp_aggregate_flat(mesh: Mesh, delta_pspecs: PyTree, *, alpha: float,
                           method: str,
                           transport: str = "f32", group_size: int = 0):
    """The flat engine behind `fedadp_aggregate(engine="flat")`.

    Same collective schedule as the tree engine — (1) psi-weighted psum,
    (2) per-client stat psums, (3) replicated weighting, (4) weighted psum
    — but each shard's contribution streams through the fused kernels over
    its contiguous (K_loc, N) rows, and the whole round is ONE shard_map
    region (`make_round_ops`). transport != "f32" compresses the raveled
    buffer to the wire dtype first; the kernels dequantize in-register.

    On a 2D (client x model) mesh — `mesh.axis_names` containing "model"
    with size > 1 — the flat buffer becomes a (client x model) grid of
    tiles instead (`make_round_ops_2d`): model-sharded leaves ravel
    shard-locally (no all-gather), quantization chunks are shard-local
    (the 2D wire layout), and the aggregated delta keeps its model
    sharding, so the old "client-only sharding" restriction is gone.
    """
    from repro import transport as transport_mod

    if transport == "int4" and not group_size:
        group_size = transport_mod.GROUP_SIZE
    spec_leaves = jax.tree.leaves(delta_pspecs,
                                  is_leaf=lambda x: isinstance(x, P))
    if model_axis_size(mesh) > 1:
        return _fedadp_aggregate_flat_2d(
            mesh, delta_pspecs, alpha=alpha, method=method,
            transport=transport, group_size=group_size)
    for s in spec_leaves:
        if any(e is not None for e in tuple(s)[1:]):
            raise ValueError(
                "engine='flat' ravels each client's delta into one "
                f"contiguous row and requires client-only sharding; got {s} "
                "(add a 'model' mesh axis for the 2D flat engine, or use "
                "engine='tree' for model-axis-sharded leaves)")
    round_op = make_round_ops(mesh, alpha=alpha, method=method,
                              transport=transport,
                              group_size=group_size)
    row_sharding = flat_client_sharding(mesh)

    def body(deltas, data_sizes, smoothed_prev, count_prev):
        k = data_sizes.shape[0]
        csize = client_axis_size(mesh)
        if k % csize:
            raise ValueError(
                f"engine='flat' needs K divisible by the client-axis size "
                f"(K={k}, client axis {csize}); pad the cohort or use "
                "engine='tree'")
        flat, unravel = treemath.tree_ravel_stacked(deltas, row_sharding)
        psi_avg = weighting.fedavg_weights(data_sizes)
        ones = jnp.ones((flat.shape[1],), jnp.float32)
        if transport == "f32":
            wire = (flat,)
        else:
            q = transport_mod.quantize(
                flat, transport,
                group_size=group_size or transport_mod.GROUP_SIZE)
            values = jax.lax.with_sharding_constraint(q.values, row_sharding)
            wire = (values,) if q.scales is None else (
                values,
                jax.lax.with_sharding_constraint(q.scales, row_sharding))
        _, _, _, _, delta_flat, theta, theta_sm, w = round_op(
            *wire, psi_avg, ones, smoothed_prev, count_prev, data_sizes)
        return unravel(delta_flat, jnp.float32), theta, theta_sm, w

    return body


def _fedadp_aggregate_flat_2d(mesh: Mesh, delta_pspecs: PyTree, *,
                              alpha: float, method: str,
                              transport: str, group_size: int):
    """`fedadp_aggregate(engine="flat")` on a (client x model) mesh."""
    spec_leaves = jax.tree.leaves(delta_pspecs,
                                  is_leaf=lambda x: isinstance(x, P))
    tree_of = lambda ls: jax.tree.unflatten(
        jax.tree.structure(delta_pspecs,
                           is_leaf=lambda x: isinstance(x, P)), ls)
    pspecs = tree_of([P(*tuple(s)[1:]) for s in spec_leaves])

    def body(deltas, data_sizes, smoothed_prev, count_prev):
        k = data_sizes.shape[0]
        csize = client_axis_size(mesh)
        if k % csize:
            raise ValueError(
                f"engine='flat' needs K divisible by the client-axis size "
                f"(K={k}, client axis {csize}); pad the cohort or use "
                "engine='tree'")
        round_op = make_round_ops_2d(
            mesh, deltas, pspecs, alpha=alpha, method=method,
            transport=transport,
            group_size=group_size)
        psi_avg = weighting.fedavg_weights(data_sizes)
        _, _, _, _, delta_tree, theta, theta_sm, w = round_op(
            deltas, psi_avg, smoothed_prev, count_prev, data_sizes)
        return delta_tree, theta, theta_sm, w

    return body
