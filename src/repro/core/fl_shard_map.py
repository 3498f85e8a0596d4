"""FedAdp's aggregation step as one shard_map region with hand-placed
collectives.

On a mesh, `engine="flat_sharded"` runs the round's aggregation step —
the paper's contribution — under `jax.shard_map`, so the communication
pattern is explicit:

  per model-shard:   g_avg = psum_{clients}(psi_i * delta_i)        (1)
  per client:        dot_i = psum_{model}(<delta_i, g_avg>_shard)   (2)
                     |d_i|^2, |g|^2 likewise
  replicated:        theta -> Eq. 9 -> weights                      (3)
  per model-shard:   delta = psum_{clients}(w_i * delta_i)          (4)

Exactly two client-axis reductions (1)(4) plus O(K) scalar psums (2) per
round — the minimum the algorithm admits with exact same-round angles.
For FedAvg and FedProx the weights are psi, so (4) is (1) and the round
is a single client-axis reduction.

`make_round_ops` builds that region. The reports are a (K, N) wire
buffer whose rows are sharded over the client axis ("pod", "data");
steps (1)(2)(4) run as the fused Pallas kernels on each shard's rows
(`wire_kernels`, the one place a kernel is chosen by wire), followed by
the psums. Step (3) is the caller's: the sync round passes the Gompertz
weights of Eq. 11, the buffered flush the staleness-aware ones. Three
layouts share the region's stats, psums, Eq. 9, weights and aggregate:

* a client-only mesh: rows sharded, columns whole, flat vectors out;
* a 2D (client x model) mesh with the stacked delta tree as input (the
  sync round): each device ravels its LOCAL leaf blocks in-region
  (`treemath.blocked_ravel_local`, no all-gather) and quantizes them
  shard-locally (scale chunks never straddle a model-axis split — the 2D
  wire layout); the outputs are trees whose model-sharded leaves stay
  sharded;
* a 2D mesh with a flat f32 buffer as input (the buffered flush): the
  buffer's columns tile over the model axis and the outputs are
  model-sharded flat vectors.

On a 2D mesh dots and sqnorms psum over both axes, |g|^2 over the model
axis, and the aggregates over the client axis only. `make_blocked_roundtrip`
gives the tree engine the same shard-local wire.

The RoundState contract lives one level up: core/fl.py gathers the
selected clients' Eq. 9 slots before the region and scatters the results
back after it, so the region stays a pure (K,)-shaped aggregation op and
composes unchanged with the scanned driver (`core.driver` puts the whole
round, this region included, inside `lax.scan`). The per-client
quantities it produces (theta, smoothed theta, weights) leave the region
replicated, so the `FLConfig(telemetry="node")` tel/* metrics built from
them are exact per-node rows (pinned by tests/test_telemetry.py's
8-device subprocess leg).
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import transport as transport_mod
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


def _client_axis(mesh: Mesh):
    caxes = _client_axes(mesh)
    return caxes if len(caxes) > 1 else caxes[0]


def flat_client_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of the (K, N) flat buffer: rows over ("pod","data"), and
    columns over "model" on a 2D mesh."""
    if model_axis_size(mesh) > 1:
        return NamedSharding(mesh, P(_client_axis(mesh), MODEL_AXIS))
    return NamedSharding(mesh, P(_client_axis(mesh)))


def _shard_slots(values, caxis):
    """Global client slots owned by this shard (rows are client-sharded)."""
    k_loc = values.shape[0]
    return jax.lax.axis_index(caxis) * k_loc + jnp.arange(k_loc)


def wire_kernels(q: transport_mod.QuantizedDelta):
    """The fused kernels that read the wire buffer `q`: the one place a
    kernel is chosen by wire.

    f32 and bf16 buffers carry no scales and take the plain kernels
    (their in-VMEM astype is bf16's dequant); int8 takes the in-register
    dequant kernels with its per-(client, chunk) scales; int4 the
    grouped-scale packed-nibble kernels (`q.n` is the logical width the
    packed rows unpack to). Returns (agg, stats): agg(w) is the (N,) f32
    weighted sum of the rows, stats(g, mask) their (dots, sqnorms, |g|^2)
    against g over the masked columns (mask None: all)."""
    x, s = q.values, q.scales
    if s is None:
        return (lambda w: weighted_agg_mod.weighted_agg(
                    w, x, out_dtype=jnp.float32),
                lambda g, mask: round_stats_mod.round_stats(x, g, mask))
    if q.transport == "int4":
        return (lambda w: weighted_agg_mod.weighted_agg_q4(
                    w, x, s, n=q.n, group_size=q.group_size),
                lambda g, mask: round_stats_mod.round_stats_q4(
                    x, s, g, mask, group_size=q.group_size))
    return (lambda w: weighted_agg_mod.weighted_agg_q(w, x, s),
            lambda g, mask: round_stats_mod.round_stats_q(x, s, g, mask))


def weigh_and_sum(weigh: Optional[Callable], wargs: tuple, theta_sm, psi,
                  g, weighted_sum: Callable):
    """Step (3) and (4): the weights `weigh(theta_sm, *wargs)` and the
    reports' `weighted_sum` under them. `weigh` None is FedAvg and
    FedProx, whose weights are psi: the psi-weighted sum g is reused
    rather than streaming the reports again."""
    if weigh is None:
        return psi, g
    with jax.named_scope("fl.weights"):
        w = weigh(theta_sm, *wargs)
    with jax.named_scope("fl.aggregate"):
        return w, weighted_sum(w)


def make_round_ops(mesh: Mesh, *, weigh: Optional[Callable] = None,
                   transport: str = "f32",
                   group_size: int = transport_mod.GROUP_SIZE,
                   template: PyTree = None, pspecs: PyTree = None,
                   keep: Optional[list] = None):
    """The aggregation step as ONE shard_map region: stats psums ->
    replicated Eq. 9 and weights -> aggregate psum, the reports staying
    on their shards throughout.

    `weigh(theta_sm, *wargs) -> w` is step (3), run replicated on the
    (K,) smoothed angles; `wargs` ride in as replicated operands. None
    means w = psi (FedAvg, FedProx). `transport` names the wire: f32 and
    bf16 buffers stream through the plain kernels, int8 adds a
    row-sharded (K, num_chunks(N)) scales operand, int4 row-shards the
    PACKED (K, ceil(N/2)) buffer with its (K, num_groups) scales; the
    per-shard partial dots, sqnorms and aggregates are psum'd alike, so
    scales never cross shards.

    Without `template` (a client-only mesh, or the buffered flush on a 2D
    mesh):
      op(values, scales, mask, psi, smoothed_sel, count_sel, *wargs)
    with `scales` None on scale-free wires and `mask` an (N,) f32 column
    mask in logical element space (ones for unfiltered stats: multiplying
    by 1.0 is exact). On a 2D mesh values and mask are (K, Np) and (Np,)
    with Np a multiple of the model-axis size, tiled over "model", and
    the two flat outputs come back model-sharded.

    With `template` and `pspecs` (the sync round on a 2D mesh): the
    K-stacked delta tree (leading axis padded to the client-axis size)
    and the UNSTACKED param PartitionSpecs (models/sharding.param_pspecs);
    `keep` holds per-leaf angle-filter flags (None = all), baked into a
    shard-identical (N_loc,) mask.
      op(deltas_stacked, psi, smoothed_sel, count_sel, *wargs)
    ravels and quantizes each device's tile in-region and returns the two
    aggregates as UNSTACKED f32 trees sharded per `pspecs` (replicated
    leaves re-join via an O(leaf) all_gather of their column slices).

    Either op returns (g, dots, sqs, sqg, delta, theta, theta_sm, w), with
    smoothed_sel/count_sel the selected clients' Eq. 9 slots and theta_sm
    Eq. 9 applied to them.
    """
    caxes = _client_axes(mesh)
    caxis = _client_axis(mesh)
    msize = model_axis_size(mesh)
    stat_axes = caxes + (MODEL_AXIS,) if msize > 1 else caxis
    qgroup = group_size if transport == "int4" else 0

    def tail(values, scales, mask, psi, smoothed_sel, count_sel, *wargs):
        """The region's shared body on this device's (K_loc, N_loc) tile."""
        my = _shard_slots(values, caxis)
        agg, stats = wire_kernels(transport_mod.QuantizedDelta(
            values, scales, transport, mask.shape[0], qgroup))
        with jax.named_scope("fl.stats"):
            g = jax.lax.psum(agg(psi[my]), caxis)
            d_loc, s_loc, sqg = stats(g, mask)
            k = psi.shape[0]
            dots = jax.lax.psum(
                jnp.zeros((k,), jnp.float32).at[my].set(d_loc), stat_axes)
            sqs = jax.lax.psum(
                jnp.zeros((k,), jnp.float32).at[my].set(s_loc), stat_axes)
            if msize > 1:
                sqg = jax.lax.psum(sqg, MODEL_AXIS)
            theta = weighting.instantaneous_angle(dots, sqs, sqg)
            theta_sm = weighting.smoothed_angle(theta, smoothed_sel,
                                                count_sel)
        w, delta = weigh_and_sum(
            weigh, wargs, theta_sm, psi, g,
            lambda w: jax.lax.psum(agg(w[my]), caxis))
        return g, dots, sqs, sqg, delta, theta, theta_sm, w

    if template is None:
        row = flat_client_sharding(mesh).spec
        col = P(MODEL_AXIS) if msize > 1 else P()

        def op(values, scales, mask, *rest):
            outs = (col, P(), P(), P(), col, P(), P(), P())
            if scales is None:  # no scales operand to shard
                return jax.shard_map(
                    lambda v, *a: tail(v, None, *a), mesh=mesh,
                    in_specs=(row, col) + (P(),) * len(rest),
                    out_specs=outs, check_vma=False)(values, mask, *rest)
            return jax.shard_map(
                tail, mesh=mesh, in_specs=(row, row, col) + (P(),) * len(rest),
                out_specs=outs, check_vma=False)(values, scales, mask, *rest)

        return op

    layout = treemath.blocked_layout(template, pspecs, msize, MODEL_AXIS)
    mask = treemath.blocked_segment_mask(layout, keep)
    structure = jax.tree.structure(template)

    def body(deltas, psi, smoothed_sel, count_sel, *wargs):
        j = jax.lax.axis_index(MODEL_AXIS)
        with jax.named_scope("fl.uplink"):
            x = treemath.blocked_ravel_local(jax.tree.leaves(deltas),
                                             layout, j)
            values, scales = x, None
            if transport != "f32":
                q = transport_mod.quantize(x, transport,
                                           group_size=group_size)
                values, scales = q.values, q.scales
        g, dots, sqs, sqg, delta, theta, theta_sm, w = tail(
            values, scales, mask, psi, smoothed_sel, count_sel, *wargs)
        with jax.named_scope("fl.stats"):
            g = jax.tree.unflatten(structure, _blocked_unstack_local(
                g, layout, dtypes=jnp.float32))
        with jax.named_scope("fl.aggregate"):
            delta = jax.tree.unflatten(structure, _blocked_unstack_local(
                delta, layout, dtypes=jnp.float32))
        return g, dots, sqs, sqg, delta, theta, theta_sm, w

    spec_leaves, tree_of = _spec_tree(pspecs)
    unstacked = tree_of(spec_leaves)

    def op(deltas, *rest):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(_stacked_specs(pspecs, caxis),) + (P(),) * len(rest),
            out_specs=(unstacked, P(), P(), P(), unstacked, P(), P(), P()),
            check_vma=False)(deltas, *rest)

    return op


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


def make_blocked_roundtrip(mesh: Mesh, template_stacked: PyTree,
                           pspecs: PyTree, *, transport: str,
                           group_size: int = transport_mod.GROUP_SIZE):
    """Shard-local wire roundtrip for the TREE engine on a 2D mesh.

    On a (client x model) mesh the uplink wire is quantized per
    (client, model-shard) block — scale chunks are shard-local (see
    `make_round_ops`). The tree reference must consume the SAME
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
    layout = treemath.blocked_layout(template_stacked, pspecs,
                                     model_axis_size(mesh), MODEL_AXIS)

    def _body(deltas):
        j = jax.lax.axis_index(MODEL_AXIS)
        x = treemath.blocked_ravel_local(jax.tree.leaves(deltas), layout, j)
        q = transport_mod.quantize(x, transport, group_size=group_size)
        recon = transport_mod.dequantize(q)  # (k_loc, N_loc) f32
        return jax.tree.unflatten(
            jax.tree.structure(deltas),
            _blocked_unstack_local(recon, layout, dtypes=jnp.float32,
                                   gather_rows=True))

    stacked = _stacked_specs(pspecs, _client_axis(mesh))
    return jax.shard_map(_body, mesh=mesh, in_specs=(stacked,),
                         out_specs=stacked, check_vma=False)
