"""Parameter/activation PartitionSpec rules for the production mesh.

Mesh axes: ("pod", "data", "model") multi-pod or ("data", "model") single
pod. Tensor parallelism lives on "model"; "data" is the client axis
(parallel FL mode) or the FSDP axis (sequential mode / big-model serving);
"pod" extends the client/data axis across pods.

Rules are name-based over the param tree; every block leaf carries a
leading scan-group axis which is never sharded. Dims are only sharded when
divisible by the axis size (GSPMD would otherwise pad-and-mask, which
muddies the roofline numbers).
"""
from __future__ import annotations

import contextvars
import functools
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# name -> which logical dim to put on the model axis, counted from the END
# of the non-group dims: "last" = output features, "first" = input features.
_LAST = {
    "w_gate", "w_up", "wq", "wk", "wv", "in_proj", "dt_w", "cw_k", "cw_r",
    "w_r", "w_k", "w_v", "w_g", "wq_b", "wk_b", "wv_b",
}
_FIRST = {"w_down", "wo", "out_proj", "x_proj", "A_log", "cw_v", "w_o"}
_REPLICATE = {
    "router", "decay_a", "decay_b", "u", "w_base", "ln_x_scale", "ln_x_bias",
    "scale", "bias", "conv_b", "dt_b", "D", "b", "kv_norm", "q_norm",
    "mu_r", "mu_k", "mu_v", "mu_g", "mu_w", "cmu_k", "cmu_r", "wq_a", "wkv_a",
}


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def _leaf_spec(keys: tuple, shape: tuple, mesh: Mesh, fsdp: bool,
               replicate_extra: frozenset = frozenset()) -> P:
    model = "model" if "model" in mesh.axis_names else None
    msize = _axis_size(mesh, "model")
    dsize = _axis_size(mesh, "data")
    name = keys[-1]
    if name in replicate_extra:
        return P(*([None] * len(shape)))
    grouped = "blocks" in keys  # leading scan-group axis
    off = 1 if grouped else 0
    nd = len(shape)
    spec: list = [None] * nd

    def try_set(dim: int, axis: str, size: int) -> bool:
        if dim < off or dim >= nd or spec[dim] is not None:
            return False
        if shape[dim] % size != 0 or shape[dim] < size:
            return False
        spec[dim] = axis
        return True

    if model is not None and nd - off >= 2 and name not in _REPLICATE:
        if name in ("w_gate", "w_up", "w_down") and nd - off == 3:
            # stacked routed experts (E, d_in, d_out): expert parallelism
            if not try_set(off, "model", msize):
                try_set(nd - 1, "model", msize)
        elif name == "embed":
            if not try_set(0, "model", msize):  # vocab
                try_set(1, "model", msize)
        elif name == "lm_head":
            if not try_set(1, "model", msize):
                try_set(0, "model", msize)
        elif name == "conv_w":
            try_set(nd - 1, "model", msize)
        elif name in _LAST:
            try_set(nd - 1, "model", msize)
        elif name in _FIRST:
            try_set(nd - 2, "model", msize)

    if fsdp and "data" in mesh.axis_names and nd - off >= 2:
        # shard the largest remaining dim over the data axis
        cand = sorted(range(off, nd), key=lambda d: -shape[d])
        for d in cand:
            if spec[d] is None and try_set(d, "data", dsize):
                break
    return P(*spec)


def param_pspecs(params_or_shapes: PyTree, mesh: Mesh, *, fsdp: bool = False,
                 replicate_extra: frozenset = frozenset()) -> PyTree:
    """PartitionSpec tree matching the param tree.

    replicate_extra: leaf names forced to full replication — e.g. MQA k/v
    projections whose head count cannot fill the model axis (sharding their
    head_dim puts the contraction on the mesh and costs a T x T-score
    all-reduce per layer; replicating them is the cheaper trade).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_or_shapes)
    specs = []
    for path, leaf in flat:
        keys = tuple(getattr(k, "key", getattr(k, "name", "")) for k in path)
        specs.append(_leaf_spec(keys, tuple(leaf.shape), mesh, fsdp,
                                replicate_extra))
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params_or_shapes, mesh, *, fsdp: bool = False,
                    replicate_extra: frozenset = frozenset()):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_pspecs(params_or_shapes, mesh, fsdp=fsdp,
                     replicate_extra=replicate_extra),
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_axes(mesh: Mesh) -> tuple:
    """Mesh axes forming the batch/client dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def shard_batch_dim(mesh: Mesh, tree: PyTree, dim_of: Optional[dict] = None,
                    default_dim: int = 0):
    """NamedSharding tree putting the batch axes on `default_dim` of every
    leaf if divisible, else replicating."""
    axes = batch_axes(mesh)
    total = 1
    for a in axes:
        total *= _axis_size(mesh, a)

    def leaf(x):
        spec = [None] * len(x.shape)
        d = default_dim
        if len(x.shape) > d and x.shape[d] % total == 0 and x.shape[d] >= total:
            spec[d] = axes if len(axes) > 1 else axes[0]
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(leaf, tree)


def replicated(mesh: Mesh, tree: PyTree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


_CONSTRAINT_MESH: list = [None]
_STEP_DEVICES = contextvars.ContextVar("step_devices", default=1)


def traced_on(mesh, fn):
    """`fn`, which reads `mesh`'s device count through `step_devices`
    while it is traced. A step jitted over a mesh shows model code no
    sharding (its tracers carry none under Auto axes), so a builder that
    lays a step on a mesh wraps it in this."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        token = _STEP_DEVICES.set(mesh.size)
        try:
            return fn(*args, **kwargs)
        finally:
            _STEP_DEVICES.reset(token)

    return traced


def step_devices() -> int:
    """Devices of the mesh the step being traced is laid on (1 outside
    `traced_on`)."""
    return _STEP_DEVICES.get()


def set_constraint_mesh(mesh) -> None:
    """Register the mesh used by in-model `constrain` calls (set by the
    launch builders before tracing; `with mesh:` alone is not visible to
    traced code in this jax version)."""
    _CONSTRAINT_MESH[0] = mesh


def constrain(x, *axes):
    """Soft in-model activation constraint: `axes` gives one entry per dim —
    None, a mesh axis name, or "batch" (expands to the (pod, data) axes).

    No-op when no constraint mesh is registered (CPU smoke tests) or when a
    dim is not divisible by its axis size, so model code can call this
    unconditionally. Used to stop GSPMD from un-sharding the batch dim of
    attention scores in FSDP mode (see EXPERIMENTS.md §Perf).
    """
    mesh = _CONSTRAINT_MESH[0]
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return x
    names = mesh.axis_names
    spec = []
    for dim, a in enumerate(axes):
        if a == "batch":
            # try the full (pod, data) product, then data-only, then pod-only
            # (a 2-pod mesh with per-client B=16 can still shard 16-way)
            chosen = None
            full = tuple(n for n in ("pod", "data") if n in names)
            for cand in (full, ("data",) if "data" in names else (),
                         ("pod",) if "pod" in names else ()):
                if not cand:
                    continue
                total = 1
                for n in cand:
                    total *= mesh.shape[n]
                if x.shape[dim] % total == 0 and x.shape[dim] >= total:
                    chosen = cand if len(cand) > 1 else cand[0]
                    break
            spec.append(chosen)
        elif a in names and x.shape[dim] % mesh.shape[a] == 0 and x.shape[dim] >= mesh.shape[a]:
            spec.append(a)
        else:
            spec.append(None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
