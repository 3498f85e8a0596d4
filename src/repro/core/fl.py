"""Federated round engines: FedAdp / FedAvg as one compiled program.

Two execution modes (DESIGN.md §6):

* ``parallel`` — the K participating clients train at once, vmapped, or
  through the model's client-batched loss where the loss carries one and
  no mesh is given (`local_update_path`); on a mesh the
  client axis is sharded over ("pod", "data"). Per-client deltas are
  materialized stacked (K, ...), angles are batched reductions, and the
  weighted aggregation is one collective contraction over the client axis.
  This is the faithful high-throughput path for models that fit K-way.

* ``sequential`` — one model copy (FSDP-shardable), clients advanced by
  `lax.scan`. FedAdp needs the round's global gradient *before* weighting.
  Where the K clients' trained params fit beside the round's other trees
  (`single_pass_fits`, from K, the params' bytes and the device's
  limit), each client trains once and its trained params are kept; g,
  the angles and the weighted sum are read from them afterwards.
  Elsewhere the exact variant runs TWO passes (local training recomputed
  in pass 2 — compute x2, memory x1/K; no raveled copy of any delta). The
  key identity making two (not three) passes suffice: softmax weights
  factor as w_i = D_i e^{f(θ̃_i)} with a scalar denominator, so pass 2 can
  accumulate Σ w_i Δ_i and Σ w_i online.

  ``stale_angles=True`` is the beyond-paper one-pass variant: angles are
  measured against the *previous* round's aggregated delta (one-round
  staleness), restoring pass-1-only compute. Evaluated in EXPERIMENTS.md.

Both modes compute their angle statistics through the Pallas kernels of
`kernels.round_stats`: parallel flat engines feed `round_stats`
(client-chunked, any K) the stacked (K, N) buffer (optionally
client-row-sharded under shard_map); the sequential scan, which builds
no flat buffer, calls `leaf_stats` on each leaf of a client's trained
and starting params in the leaf's own layout, so no delta is written.

Angle convention: the paper defines θ_i between ∇F and ∇F_i with
∇F_i = -Δ_i/η (Alg. 1 l.9); the -1/η factors cancel in the cosine, so we
correlate deltas directly.

Round-state contract: every engine threads ONE `RoundState` pytree — the
server-side carry of a federated round (params, Eq. 9 angle state, the
previous aggregated delta, both error-feedback residuals, the previous
broadcast for delta-encoded downlinks, the device RNG key, and the round
counter). `round_fn(state, batches, sel_idx, data_sizes) -> (state,
metrics)` is the uniform signature for parallel tree/flat/flat_sharded
and the sequential scan alike, which is what lets `core.driver` fold a
whole training run into a single `lax.scan` with the state as the carry.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import transport as transport_mod
from repro.core import fl_shard_map, treemath, weighting
from repro.core import buffer as buffer_mod
from repro.core.weighting import AngleState
from repro.kernels import round_stats as round_stats_mod
from repro.models import sharding as models_sharding

PyTree = Any


@dataclasses.dataclass(frozen=True)
class FLConfig:
    num_clients: int  # N — population size (angle-state slots)
    clients_per_round: int  # K = |S_t|
    local_steps: int  # tau
    method: str = "fedadp"  # fedadp | fedavg | fedprox
    alpha: float = weighting.DEFAULT_ALPHA
    base_lr: float = 0.01
    lr_decay: float = 0.995  # per communication round (paper Sec. V)
    mode: str = "parallel"  # parallel | sequential
    stale_angles: bool = False  # sequential one-pass variant
    # parallel-mode execution engine: how the round's one FedAdp
    # aggregation step (`_engine_step`: Eq. 8 stats against the FedAvg
    # reference g, Eq. 9, the weights, the weighted sum) runs. The sync
    # round and the buffered flush share it.
    #   "tree" — per-leaf treemath reductions (reference; keeps sharded
    #            leaves sharded, the right trade on a model-sharded mesh)
    #   "flat" — deltas raveled once into a contiguous (K, N) buffer in
    #            the wire dtype; stats and aggregation run as single-HBM-
    #            pass Pallas kernels (fl_shard_map.wire_kernels picks them
    #            by wire). The client axis is CHUNKED inside the kernels,
    #            so any K is supported.
    #   "flat_sharded" — the same kernels in ONE shard_map region
    #            (fl_shard_map.make_round_ops: per-shard kernel calls,
    #            stat psums, replicated Eq. 9 and weights, aggregate psum)
    #            over the buffer row-sharded on the mesh client axis
    #            ("pod","data"). Requires `mesh=` in make_round_fn; K not
    #            divisible by the client axis is zero-padded (padded rows
    #            get exactly zero weight). On a 2D (client x model) mesh
    #            the buffer is a grid of (K_loc, N_loc) tiles: each device
    #            ravels its LOCAL leaf blocks and quantizes them
    #            shard-locally (scale chunks never straddle a model-axis
    #            split — the 2D wire layout), and the aggregate keeps
    #            model-sharded leaves sharded. The tree engine on such a
    #            mesh reads the same shard-local wire, dequantized, so the
    #            engines agree to 1e-5 per transport. error_feedback is
    #            incompatible with a quantized 2D wire (the residual is in
    #            global ravel order) and raises.
    # The sequential mode's statistics stream through the round_stats
    # module's `leaf_stats` kernel, one call per leaf of each client's
    # trained and starting params against the global delta.
    engine: str = "tree"  # tree | flat | flat_sharded
    # Delta transport — the client-uplink wire format (repro.transport):
    #   "f32"  — reference wire, deltas ship unmodified.
    #   "bf16" — 2 bytes/param; the flat engines read the bf16 buffer
    #            directly (the kernels' in-VMEM astype IS the dequant).
    #   "int8" — 1 byte/param + one f32 scale per (client, kernel chunk);
    #            the flat engines run the fused in-register-dequant kernels
    #            (round_stats_q / weighted_agg_q) so stats + aggregation
    #            stay one HBM pass over ~4x fewer bytes. The tree engine
    #            NEVER reads quantized buffers: it dequantizes back to the
    #            stacked tree and runs the per-leaf reference reductions.
    #   "int4" — two params per byte (packed nibble pairs) + one f32 scale
    #            per (client, `group_size` elements); the flat engines run
    #            the grouped-scale fused kernels (round_stats_q4 /
    #            weighted_agg_q4) — one HBM pass over ~8x fewer bytes.
    transport: str = "f32"  # f32 | bf16 | int8 | int4
    # int4 scale-group width: one f32 dequant scale per `group_size`
    # consecutive elements of a client's flat delta row. Must be even and
    # divide kernels' CHUNK = ROWS*LANE = 16384 (so a packed byte never
    # straddles a group and kernel tiles cover whole groups); smaller
    # groups track local magnitude better at 4/group_size bytes/param of
    # side data. Ignored by the other transports (int8 stays per-chunk).
    group_size: int = transport_mod.GROUP_SIZE
    # Server->client broadcast (downlink) wire format
    # (repro.transport.downlink): "f32" is the reference broadcast (the
    # round is then byte-identical upstream of this option); "bf16"/"int8"
    # compress the global model once per round and EVERY engine trains its
    # clients from the same dequantized reconstruction, so engine parity
    # is preserved by construction. The server always applies the
    # aggregated delta to its own uncompressed master params.
    downlink: str = "f32"  # f32 | bf16 | int8
    # Delta-encode the broadcast: ship the quantized model DIFF against
    # the previous broadcast reconstruction instead of the full model
    # (`transport.downlink.delta_compress` on the raveled (1, N) diff).
    # Per-round deltas are orders of magnitude smaller than the params
    # themselves, so the same wire format reconstructs them far more
    # accurately (the int8 scale tracks the diff's absmax, not the
    # model's). Requires downlink != "f32" (an exact broadcast has no
    # reason to diff) and threads `RoundState.bcast` — a
    # `transport.downlink.BroadcastState` with the server's chain head,
    # a `downlink_ring`-deep ring of the last delta reconstructions, and
    # a per-client (num_clients,) last-pulled-version vector, so a
    # selected (or buffered-admitted) client decodes against the base it
    # ACTUALLY holds: it replays the ring's deltas since its last pull
    # (bitwise the server head), or — if it never pulled / fell more
    # than `downlink_ring` versions behind — receives a full quantized
    # model instead (catch-up resync). Round 0 broadcasts the full model
    # to everyone. Composes with downlink_error_feedback (the EF
    # residual rides on the diff before compression).
    downlink_delta: bool = False
    # Ring depth R of the per-client delta-downlink state: the server
    # retains the delta reconstructions of the last R broadcast versions,
    # so a client up to R versions stale can catch up by replaying
    # deltas; staler clients pay a full-model resync. Memory is R * N
    # f32 on device. Only meaningful with downlink_delta=True.
    downlink_ring: int = 8
    # Carry the per-client quantization residual across rounds (EF-SGD) so
    # the compressed angle statistics stay unbiased over time. Requires
    # transport != "f32" and parallel mode; the residual lives in
    # `RoundState.ef` — a (num_clients, N) f32 array
    # (transport.init_error_feedback) that `init_round_state` allocates
    # and round_fn updates in place of the old trailing ef_state output.
    error_feedback: bool = False
    # Server-side EF mirror for the downlink: carry the broadcast residual
    # params - dequant(quant(params)) across rounds so the model the
    # clients see is unbiased over time. Requires downlink != "f32"; the
    # residual lives in `RoundState.dl_ef` — an (N,) f32 vector
    # (transport.downlink.init_downlink_error_feedback) allocated by
    # `init_round_state` and updated by round_fn each round.
    downlink_error_feedback: bool = False
    # beyond-paper: restrict angle statistics to non-expert parameters —
    # MoE routing makes expert deltas sparse/noisy, polluting the cosine.
    angle_filter: str = "all"  # all | dense_only
    # fedprox (Li et al. 2018) baseline: mu/2 ||w - w_global||^2 proximal term
    prox_mu: float = 0.0
    # Server aggregation discipline:
    #   "sync"     — the paper's lockstep round: every selected node
    #                reports before the server re-weights by angle.
    #   "buffered" — FedBuff-style buffered-async server (core.buffer):
    #                reports are admitted continuously into a K-slot
    #                device-resident buffer (`RoundState.buf`) with
    #                simulated arrival delays/dropouts, and the server
    #                flushes whenever `buffer_m` of the in-flight cohort
    #                have landed, folding a staleness discount into the
    #                FedAdp Gompertz weight (late low-contribution nodes
    #                are doubly suppressed). Requires mode="parallel".
    #                With buffer_m == K and no stragglers/dropouts it
    #                reproduces the sync round bit-for-bit.
    aggregation: str = "sync"  # sync | buffered
    # Buffered flush threshold M: aggregate when >= buffer_m reports of
    # the in-flight cohort have landed. 0 (default) means M = K =
    # clients_per_round — flush only when the whole cohort landed.
    buffer_m: int = 0
    # Staleness decay rate: a report applied `age` model versions after
    # its client pulled params is discounted by exp(-staleness_beta*age)
    # inside the aggregation weights (weighting.staleness_discount).
    staleness_beta: float = 0.3
    # Simulated arrival-time injection (buffered mode): each admitted
    # report straggles with probability `straggle_prob` (arrival delayed
    # uniformly in {1..straggle_max} server ticks) and is dropped in
    # transit with probability `dropout_prob` (never arrives; the slot
    # re-admits a fresh client next tick). Drawn from the device RNG —
    # a fixed seed is a fixed schedule; `make_round_fn(arrival_fn=)`
    # overrides the draw entirely (core.server.fixed_arrival_schedule).
    straggle_prob: float = 0.0
    straggle_max: int = 1
    dropout_prob: float = 0.0
    # Round-level telemetry (repro.telemetry). None (default) is the
    # zero-overhead off path: the metrics dict — and therefore the
    # compiled step's jaxpr — is byte-identical to a telemetry-free
    # build. "node" makes every engine's metrics dict additionally carry
    # the per-node FedAdp internals under flat "tel/*" keys (they stack
    # naturally under lax.scan): "tel/nodes" (K,) population attribution
    # for this round's theta/weights rows, "tel/cohort" (num_clients,)
    # selected mask, "tel/weight_entropy", and the wire cost
    # "tel/bytes_up"/"tel/bytes_down" (transport.round_bytes); buffered
    # mode adds "tel/ages", "tel/landed", and "tel/occupancy". The
    # host-side adapter is telemetry.sinks.emit_round_block.
    telemetry: Optional[str] = None  # None | "node"

    def validate(self) -> "FLConfig":
        """Check the config's cross-field invariants in one place.

        Raises ValueError naming the offending field. Called by both
        `make_round_fn` and `init_round_state`, so an invalid config
        fails before any buffer is allocated or a round is traced.
        Returns self so it chains: `cfg = FLConfig(...).validate()`.
        """
        if self.mode not in ("parallel", "sequential"):
            raise ValueError(
                f"unknown mode {self.mode!r} (expected 'parallel' or "
                "'sequential')")
        if self.method not in ("fedadp", "fedavg", "fedprox"):
            raise ValueError(
                f"unknown method {self.method!r} (expected 'fedadp', "
                "'fedavg', or 'fedprox')")
        if self.engine not in ("tree", "flat", "flat_sharded"):
            raise ValueError(f"unknown engine {self.engine!r}")
        if self.angle_filter not in ("all", "dense_only"):
            raise ValueError(f"unknown angle_filter {self.angle_filter!r}")
        if self.telemetry not in (None, "node"):
            raise ValueError(
                f"unknown telemetry {self.telemetry!r} (expected None — "
                "the zero-overhead off path — or 'node' for per-node "
                "round metrics)")
        if self.transport not in transport_mod.TRANSPORTS:
            raise ValueError(
                f"unknown transport {self.transport!r} (expected one of "
                f"{transport_mod.TRANSPORTS})")
        if self.downlink not in transport_mod.DOWNLINKS:
            raise ValueError(
                f"unknown downlink {self.downlink!r} (expected one of "
                f"{transport_mod.DOWNLINKS})")
        if self.transport == "int4":
            transport_mod.validate_group_size(self.group_size)
        if self.error_feedback and self.transport == "f32":
            raise ValueError(
                "error_feedback carries the quantization residual; "
                "transport='f32' has none (set transport='bf16', 'int8', "
                "or 'int4')")
        if self.downlink_error_feedback and self.downlink == "f32":
            raise ValueError(
                "downlink_error_feedback carries the broadcast "
                "quantization residual; downlink='f32' has none (set "
                "downlink='bf16' or 'int8')")
        if self.downlink_delta and self.downlink == "f32":
            raise ValueError(
                "downlink_delta broadcasts the quantized model diff "
                "against the previous broadcast; downlink='f32' ships "
                "exact params and has nothing to gain from it (set "
                "downlink='bf16' or 'int8')")
        if self.downlink_delta and self.downlink_ring < 1:
            raise ValueError(
                f"downlink_ring={self.downlink_ring} must be >= 1 (the "
                "server retains the last R broadcast deltas; a client "
                "more than R versions behind is resynced in full)")
        if not self.downlink_delta and self.downlink_ring != 8:
            raise ValueError(
                f"downlink_ring={self.downlink_ring} requires "
                "downlink_delta=True (without delta encoding every "
                "broadcast ships the full model and no ring is kept)")
        if self.mode == "sequential":
            if self.engine != "tree":
                raise ValueError(
                    f"engine={self.engine!r} requires mode='parallel' "
                    "(sequential mode never materializes the stacked "
                    "(K, N) delta buffer; its stats already stream "
                    "through round_stats)")
            if self.transport != "f32":
                raise ValueError(
                    "transport compresses the stacked parallel uplink "
                    "buffer; sequential mode streams one client at a "
                    "time (use mode='parallel' for quantized transport)")
            if self.downlink != "f32":
                raise ValueError(
                    "quantized downlink is threaded through the parallel "
                    "round engines; use mode='parallel' for downlink != "
                    "'f32'")
        if self.aggregation not in ("sync", "buffered"):
            raise ValueError(
                f"unknown aggregation {self.aggregation!r} (expected "
                "'sync' or 'buffered')")
        if self.aggregation == "buffered":
            if self.mode != "parallel":
                raise ValueError(
                    "aggregation='buffered' admits reports into the "
                    "stacked (K, N) uplink buffer and requires "
                    "mode='parallel'")
            if self.stale_angles:
                raise ValueError(
                    "stale_angles is the sequential one-pass variant; "
                    "aggregation='buffered' already measures angles at "
                    "flush time (unset stale_angles)")
            if not 0 <= self.buffer_m <= self.clients_per_round:
                raise ValueError(
                    f"buffer_m={self.buffer_m} must be in "
                    f"[0, clients_per_round={self.clients_per_round}] "
                    "(0 means flush only when the whole cohort landed)")
            if self.staleness_beta < 0:
                raise ValueError(
                    f"staleness_beta={self.staleness_beta} must be >= 0 "
                    "(the discount is exp(-staleness_beta * age))")
            if not 0.0 <= self.straggle_prob <= 1.0:
                raise ValueError(
                    f"straggle_prob={self.straggle_prob} must be a "
                    "probability in [0, 1]")
            if not 0.0 <= self.dropout_prob <= 1.0:
                raise ValueError(
                    f"dropout_prob={self.dropout_prob} must be a "
                    "probability in [0, 1]")
            if self.straggle_prob > 0 and self.straggle_max < 1:
                raise ValueError(
                    f"straggle_max={self.straggle_max} must be >= 1 when "
                    "straggle_prob > 0 (stragglers delay by 1..max ticks)")
        else:
            for field, val, default in (
                    ("buffer_m", self.buffer_m, 0),
                    ("straggle_prob", self.straggle_prob, 0.0),
                    ("dropout_prob", self.dropout_prob, 0.0)):
                if val != default:
                    raise ValueError(
                        f"{field}={val} requires aggregation='buffered' "
                        "(the sync round is lockstep: every report lands "
                        "before the server aggregates)")
        return self


class RoundState(NamedTuple):
    """The unified server-side carry of a federated round.

    One pytree threaded identically through every engine (tree / flat /
    flat_sharded / sequential): `round_fn(state, batches, sel_idx,
    data_sizes) -> (state, metrics)`. Because the whole carry is a single
    pytree with a STATIC structure, `core.driver` can scan it over rounds
    (`lax.scan`) and donate its buffers so params/EF update in place.

    Optional fields are None when the matching FLConfig flag is off —
    None is an empty pytree, so the carry structure stays fixed per
    config and the scan carry never changes shape.
    """

    params: PyTree  # the server's uncompressed master model
    angle: AngleState  # Eq. 9 smoothed angles + participation counts
    prev_delta: PyTree  # last aggregated global delta, f32 leaves
    #   (the stale_angles reference; threaded untouched otherwise)
    ef: Optional[jax.Array] = None  # (num_clients, N) uplink EF residual
    dl_ef: Optional[jax.Array] = None  # (N,) downlink EF residual
    bcast: Optional[transport_mod.downlink.BroadcastState] = None
    #   per-client downlink-delta state (downlink_delta): the broadcast
    #   chain head, the R-deep ring of the last delta reconstructions,
    #   and each client's last-pulled version (see transport.downlink)
    buf: Optional[buffer_mod.ReportBuffer] = None  # buffered-async report
    #   buffer: (K, N) in-flight report rows + per-row staleness
    #   bookkeeping (aggregation="buffered"; see core.buffer)
    rng: Optional[jax.Array] = None  # device PRNG key — owned by the
    #   data/selection pipeline (core.driver); round_fn threads it as-is
    round: Any = 0  # i32 round counter (drives the lr schedule)


def param_count(params: PyTree) -> int:
    """Total scalar parameter count N (the flat-buffer width)."""
    return sum(math.prod(p.shape) for p in jax.tree.leaves(params))


def init_round_state(fl: FLConfig, params: PyTree,
                     seed: "int | jax.Array" = 0) -> RoundState:
    """Fresh RoundState for `params` under `fl`.

    Allocates exactly the optional buffers the config calls for (uplink
    EF rows, downlink EF vector, per-client broadcast state, buffered
    report buffer) so the state structure is a pure function of the
    config — `fl.validate()` runs first, so an inconsistent config fails
    here rather than at trace time. `seed` is an int (a new
    `jax.random.key` is made) or an existing PRNG key array.
    """
    fl.validate()
    n = param_count(params)
    rng = seed if isinstance(seed, jax.Array) else jax.random.key(seed)
    return RoundState(
        params=params,
        angle=AngleState.init(fl.num_clients),
        prev_delta=init_prev_delta(params),
        ef=(transport_mod.init_error_feedback(fl.num_clients, n)
            if fl.error_feedback else None),
        dl_ef=(transport_mod.downlink.init_downlink_error_feedback(n)
               if fl.downlink_error_feedback else None),
        bcast=(transport_mod.downlink.init_broadcast_state(
            n, fl.num_clients, fl.downlink_ring)
            if fl.downlink_delta else None),
        buf=(buffer_mod.init_report_buffer(fl.clients_per_round, n)
             if fl.aggregation == "buffered" else None),
        rng=rng,
        round=jnp.int32(0),
    )


def state_to_tree(state: RoundState) -> dict:
    """RoundState -> a nested dict `checkpoint.io.save` can round-trip.

    Field-for-field: NamedTuples become dicts, optional fields stay None
    (the io layer writes `__none__` sentinels so the structure survives),
    and the typed PRNG key ships as-is (io serializes it via
    `jax.random.key_data` + an impl tag). `state_from_tree` is the
    inverse."""
    return {
        "params": state.params,
        "angle": {"smoothed": state.angle.smoothed,
                  "count": state.angle.count},
        "prev_delta": state.prev_delta,
        "ef": state.ef,
        "dl_ef": state.dl_ef,
        "bcast": (None if state.bcast is None else state.bcast._asdict()),
        "buf": (None if state.buf is None else state.buf._asdict()),
        "rng": state.rng,
        "round": state.round,
    }


def _resize_rows(a: jax.Array, k_new: int, fill=0) -> jax.Array:
    """Truncate / pad axis 0 to `k_new` rows (elastic-K restore).

    New rows are `fill` — zero for angle/EF state (fresh clients start
    like round-0 clients), `downlink.NEVER_PULLED` for the broadcast
    version vector (fresh clients need a full-model resync)."""
    k_old = a.shape[0]
    if k_new == k_old:
        return a
    if k_new < k_old:
        return a[:k_new]
    pad = jnp.full((k_new - k_old,) + a.shape[1:], fill, a.dtype)
    return jnp.concatenate([a, pad])


def state_from_tree(cfg: FLConfig, tree: dict) -> RoundState:
    """Rebuild a RoundState from `state_to_tree`'s dict under `cfg`.

    The restored state's pytree structure is the CONFIG's — each optional
    field (ef / dl_ef / bcast) must be present exactly when the matching
    flag is on, and every leaf is validated (shape AND dtype) against
    `init_round_state`'s template, so a checkpoint from a different model
    or an incompatible config fails loudly instead of mis-resuming.

    Elastic-K: when `cfg.num_clients` differs from the checkpoint's, the
    per-client state is re-sized — AngleState rows, uplink-EF rows, and
    the broadcast version vector `bcast.ver` are truncated (shrink) or
    padded (grow). New clients therefore start exactly like round-0
    clients: zero EF residual, unseen angle (smoothed=0, count=0), and a
    `NEVER_PULLED` broadcast version (their first selection is a
    full-model resync). Departed clients' slots are dropped. The
    per-model state (dl_ef, params, the broadcast ring/head) is
    K-independent and restores bit-exactly; a `downlink_ring` mismatch
    fails the template shape check below.

    Checkpoints from the pre-ring repo carried a single shared
    'prev_broadcast' vector — per-client decode bases cannot be
    reconstructed from it, so such trees are rejected with a pointed
    error rather than silently mis-upgraded.

    Old-style raw `uint32` PRNG keys (pre-typed-key checkpoints) are
    wrapped back into a typed key via `jax.random.wrap_key_data` with the
    default impl.
    """
    missing = [k for k in ("params", "angle", "prev_delta", "rng", "round")
               if tree.get(k) is None]
    if missing:
        raise ValueError(
            f"checkpoint tree lacks required RoundState fields {missing} "
            "— was it written by fl.state_to_tree?")
    if tree.get("prev_broadcast") is not None:
        raise ValueError(
            "checkpoint carries the legacy shared 'prev_broadcast' vector "
            "— it was written by a pre-ring repo revision whose "
            "downlink-delta state had no per-client decode bases; the "
            "per-client BroadcastState (ring/head/ver) cannot be "
            "reconstructed from it. Re-run the training (or restore under "
            "the revision that wrote it)")
    for name, flag, want in (
            ("ef", "error_feedback", cfg.error_feedback),
            ("dl_ef", "downlink_error_feedback", cfg.downlink_error_feedback),
            ("bcast", "downlink_delta", cfg.downlink_delta)):
        have = tree.get(name) is not None
        if want and not have:
            raise ValueError(
                f"cfg.{flag}=True but the checkpoint has no {name!r} — it "
                "was written under a config with the feature off; restore "
                "with a matching config (or re-init that buffer yourself)")
        if have and not want:
            raise ValueError(
                f"checkpoint carries {name!r} but cfg.{flag}=False — "
                "dropping a live residual would silently change the run; "
                "restore with a matching config")
    buffered = cfg.aggregation == "buffered"
    have_buf = tree.get("buf") is not None
    if buffered and not have_buf:
        raise ValueError(
            "cfg.aggregation='buffered' but the checkpoint has no 'buf' — "
            "it was written by a sync-aggregation run; restore with a "
            "matching config (or re-init the report buffer yourself)")
    if have_buf and not buffered:
        raise ValueError(
            "checkpoint carries 'buf' but cfg.aggregation='sync' — "
            "dropping the in-flight reports would silently change the "
            "run; restore with a matching config")

    params = tree["params"]
    rng = tree["rng"]
    if not jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key):
        rng = jax.random.wrap_key_data(jnp.asarray(rng, jnp.uint32))
    angle = AngleState(
        smoothed=_resize_rows(jnp.asarray(tree["angle"]["smoothed"],
                                          jnp.float32), cfg.num_clients),
        count=_resize_rows(jnp.asarray(tree["angle"]["count"], jnp.int32),
                           cfg.num_clients),
    )
    ef = tree.get("ef")
    if ef is not None:
        ef = _resize_rows(ef, cfg.num_clients)
    bcast = tree.get("bcast")
    if bcast is not None:
        bcast = transport_mod.downlink.BroadcastState(
            ring=jnp.asarray(bcast["ring"], jnp.float32),
            head=jnp.asarray(bcast["head"], jnp.float32),
            head_ver=jnp.asarray(bcast["head_ver"], jnp.int32),
            ver=_resize_rows(jnp.asarray(bcast["ver"], jnp.int32),
                             cfg.num_clients,
                             fill=transport_mod.downlink.NEVER_PULLED),
        )
    buf = tree.get("buf")
    if buf is not None:
        # in-flight reports restore verbatim (K = clients_per_round rows;
        # a K mismatch fails the template check below — resizing a report
        # buffer would orphan live slot ids, unlike the elastic per-client
        # state above).
        buf = buffer_mod.ReportBuffer(
            data=jnp.asarray(buf["data"], jnp.float32),
            slot=jnp.asarray(buf["slot"], jnp.int32),
            sizes=jnp.asarray(buf["sizes"], jnp.float32),
            age=jnp.asarray(buf["age"], jnp.int32),
            wait=jnp.asarray(buf["wait"], jnp.int32),
            free=jnp.asarray(buf["free"], bool),
        )
    state = RoundState(
        params=params, angle=angle, prev_delta=tree["prev_delta"],
        ef=ef, dl_ef=tree.get("dl_ef"), bcast=bcast, buf=buf,
        rng=rng, round=jnp.asarray(tree["round"], jnp.int32),
    )

    # validate against the config's own allocation: same pytree structure,
    # and shape/dtype equality on every leaf.
    p_sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    template = jax.eval_shape(lambda p: init_round_state(cfg, p), p_sds)
    got_def = jax.tree.structure(state)
    want_def = jax.tree.structure(template)
    if got_def != want_def:
        raise ValueError(
            "restored RoundState structure does not match "
            f"init_round_state({cfg.num_clients} clients): got {got_def}, "
            f"want {want_def}")
    got = jax.tree_util.tree_flatten_with_path(state)[0]
    want = jax.tree.leaves(template)
    for (path, leaf), ref in zip(got, want):
        name = jax.tree_util.keystr(path)
        if leaf.shape != ref.shape or leaf.dtype != ref.dtype:
            raise ValueError(
                f"checkpoint leaf {name} has shape {leaf.shape} dtype "
                f"{leaf.dtype}, but the config allocates {ref.shape} "
                f"{ref.dtype} — wrong model or incompatible config")
    return state


def local_update(loss_fn: Callable, params: PyTree, batches: PyTree, lr,
                 prox_mu: float = 0.0, grad_constraint: Optional[Callable] = None):
    """tau steps of SGD on one client. batches: leaves (tau, B, ...).

    prox_mu > 0 adds FedProx's proximal term mu/2 ||w - w(t-1)||^2 against
    the round's starting params (Li et al. 2018 — baseline for comparison).
    grad_constraint re-shards per-step gradients (e.g. onto the FSDP param
    spec so GSPMD reduce-scatters batch-partial grads instead of
    all-reducing the full tree — §Perf collective-term optimization).
    Returns (delta, mean_loss).

    A loss with `loss_fn.has_aux` returns (loss, counters), a dict of
    scalars (e.g. a model's expert-row count); the update then returns
    (delta, mean_loss, counters summed over the tau steps)."""
    if getattr(loss_fn, "has_aux", False):
        p_fin, losses, counters = _local_final_counted(
            loss_fn, params, batches, lr, prox_mu, grad_constraint)
        return (treemath.tree_sub(p_fin, params), jnp.mean(losses),
                _summed(counters))
    p_fin, losses = _local_final(loss_fn, params, batches, lr, prox_mu,
                                 grad_constraint)
    return treemath.tree_sub(p_fin, params), jnp.mean(losses)


def _local_final(loss_fn, params, batches, lr, prox_mu, grad_constraint):
    """(final params, per-step losses) of `local_update`'s tau steps."""
    if prox_mu > 0.0:
        base = loss_fn

        def loss_fn(p, b):  # noqa: F811 — intentional wrap
            prox = treemath.tree_sqnorm(treemath.tree_sub(p, params))
            return base(p, b) + 0.5 * prox_mu * prox

    def step(p, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        if grad_constraint is not None:
            g = grad_constraint(g)
        return treemath.tree_axpy(-lr, g, p), loss

    return jax.lax.scan(step, params, batches)


def _local_final_counted(loss_fn, params, batches, lr, prox_mu,
                         grad_constraint):
    """(final params, per-step losses, per-step counters) of tau steps of
    a loss that returns (loss, counters)."""

    def total(p, b):
        loss, counters = loss_fn(p, b)
        if prox_mu > 0.0:
            prox = treemath.tree_sqnorm(treemath.tree_sub(p, params))
            loss = loss + 0.5 * prox_mu * prox
        return loss, counters

    def step(p, b):
        (loss, counters), g = jax.value_and_grad(total, has_aux=True)(p, b)
        if grad_constraint is not None:
            g = grad_constraint(g)
        return treemath.tree_axpy(-lr, g, p), (loss, counters)

    p_fin, (losses, counters) = jax.lax.scan(step, params, batches)
    return p_fin, losses, counters


def _summed(counters):
    return jax.tree.map(lambda c: jnp.sum(c, axis=0), counters)


def local_update_clients(clients_loss: Callable, params: PyTree,
                         batches: PyTree, lr, prox_mu: float = 0.0,
                         grad_constraint: Optional[Callable] = None):
    """tau steps of SGD on K clients at once, one `lax.scan` over the
    steps: `jax.vmap(local_update)` over the clients, computed through a
    client-batched loss.

    params: leaves (K, ...), each client's starting params; batches:
    leaves (K, tau, B, ...). `clients_loss(params, batch)` takes the
    stacked params and one step's batch with leaves (B, K, ...) and
    returns the K clients' losses (K,). The step differentiates their
    sum, whose gradient with respect to client k's params is client k's
    own, so each client takes `local_update`'s step; `prox_mu` and
    `grad_constraint` act per client as there. Returns (deltas (K, ...),
    mean losses (K,))."""

    def total(p, b):
        losses = clients_loss(p, b)
        if prox_mu > 0.0:
            prox = treemath.tree_sqnorm_batched(treemath.tree_sub(p, params))
            losses = losses + 0.5 * prox_mu * prox
        return jnp.sum(losses), losses

    def step(p, b):
        (_, losses), g = jax.value_and_grad(total, has_aux=True)(p, b)
        if grad_constraint is not None:
            g = jax.vmap(grad_constraint)(g)
        return treemath.tree_axpy(-lr, g, p), losses

    steps = jax.tree.map(  # (K, tau, B, ...) -> (tau, B, K, ...)
        lambda a: jnp.moveaxis(a, 0, 2), batches)
    p_fin, losses = jax.lax.scan(step, params, steps)
    return treemath.tree_sub(p_fin, params), jnp.mean(losses, axis=0)


def local_update_path(loss_fn: Callable, fl: FLConfig, mesh=None) -> str:
    """The local update a round built from these arguments runs:
    "batched" (`local_update_clients`) where the loss carries a
    client-batched form as `loss_fn.clients` and the round is a parallel
    one built without a mesh (on a client-sharded mesh the batched form
    would put the sharded client axis into the model's feature axes),
    "sequential" for the sequential round, else "vmap"
    (`jax.vmap(local_update)`)."""
    if fl.mode != "parallel":
        return "sequential"
    if mesh is None and getattr(loss_fn, "clients", None) is not None:
        return "batched"
    return "vmap"


def _local_train(loss_fn, path: str, params, batches, lr, fl: FLConfig,
                 grad_constraint, delta_constraint=None):
    """The parallel round's local updates of the K clients, all from
    `params`, by the path `local_update_path` chose; `delta_constraint`
    then places the stacked deltas. Returns (deltas, losses)."""
    with jax.named_scope("fl.local_train"):
        if path == "batched":
            k = jax.tree.leaves(batches)[0].shape[0]
            stacked = jax.tree.map(
                lambda p: jnp.broadcast_to(p, (k, *p.shape)), params)
            deltas, losses = local_update_clients(
                loss_fn.clients, stacked, batches, lr, fl.prox_mu,
                grad_constraint)
        else:
            deltas, losses = jax.vmap(
                lambda b: local_update(loss_fn, params, b, lr, fl.prox_mu,
                                       grad_constraint)
            )(batches)
        if delta_constraint is not None:
            deltas = delta_constraint(deltas)
    return deltas, losses


def angle_keep_list(params: PyTree, pred: Callable) -> list:
    """One bool per leaf (flatten order): does `pred(path_keys, leaf)` keep it?"""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    keep = []
    for path, leaf in flat:
        keys = tuple(getattr(k, "key", getattr(k, "name", "")) for k in path)
        keep.append(bool(pred(keys, leaf)))
    return keep


def build_angle_mask(params: PyTree, pred: Callable) -> Callable:
    """Angle-statistics leaf filter, decided ONCE on the param tree.

    `pred(path_keys, param_leaf) -> keep?` is evaluated against the model's
    params; the returned mask then filters any tree with the same flatten
    order (params, deltas, or K-stacked deltas) down to the kept leaves —
    a list, which is itself a pytree treemath reductions accept.
    """
    keep = angle_keep_list(params, pred)

    def mask(tree):
        leaves = jax.tree_util.tree_leaves(tree)
        assert len(leaves) == len(keep), "mask/tree flatten-order mismatch"
        return [l for l, k in zip(leaves, keep) if k]

    return mask


def moe_dense_only_pred(keys, leaf) -> bool:
    """Keep everything except stacked routed-expert weights: leaves named
    w_gate/w_up/w_down under 'ffn' with an expert axis (rank >= 4 in the
    group-stacked param tree)."""
    return not (
        "ffn" in keys
        and keys[-1] in ("w_gate", "w_up", "w_down")
        and leaf.ndim >= 4
    )


def _scatter_angles(state: AngleState, sel_idx, theta):
    n = state.smoothed.shape[0]
    mask = jnp.zeros((n,), bool).at[sel_idx].set(True)
    theta_full = jnp.zeros((n,), jnp.float32).at[sel_idx].set(theta)
    return weighting.update_smoothed_angle(state, theta_full, mask)


def _scatter_angles_masked(state: AngleState, sel_idx, theta, valid):
    """Eq. 9 scatter restricted to the rows where `valid` — invalid rows
    are routed out of bounds and dropped, so a buffered flush only smooths
    the angles of the reports it actually aggregated. With `valid` all
    True this is op-for-op `_scatter_angles` (`where(True, i, n) == i` and
    an in-bounds mode="drop" scatter is the plain scatter)."""
    n = state.smoothed.shape[0]
    idx = jnp.where(valid, sel_idx, n)
    mask = jnp.zeros((n,), bool).at[idx].set(True, mode="drop")
    theta_full = jnp.zeros((n,), jnp.float32).at[idx].set(theta, mode="drop")
    return weighting.update_smoothed_angle(state, theta_full, mask)


def make_round_fn(loss_fn: Callable, fl: FLConfig,
                  delta_constraint: Optional[Callable] = None,
                  angle_pred: Optional[Callable] = None,
                  grad_constraint: Optional[Callable] = None,
                  mesh=None, arrival_fn: Optional[Callable] = None) -> Callable:
    """Build the jit-able federated round.

    round_fn(state, batches, sel_idx, data_sizes) -> (state, metrics)

    `state` is a `RoundState` (see `init_round_state`) and is threaded
    IDENTICALLY through every engine — params, Eq. 9 angle state, the
    previous aggregated delta, both EF residuals, the per-client
    broadcast state (downlink_delta), the device RNG key (untouched here;
    the driver's data pipeline owns it), and the round counter
    (incremented here; it drives the lr schedule). batches leaves:
    (K, tau, B, ...); sel_idx (K,) int32 population slots; data_sizes (K,)
    f32. `delta_constraint` optionally applies sharding constraints to
    the stacked deltas (parallel mode).

    A parallel round is built from shared phases — the state check, the
    downlink broadcast, local training, the uplink compression with error
    feedback, the engine's aggregation step and the core metrics — and
    `fl.engine` picks that step once, here (`_engine_step`): per-leaf
    reductions ("tree"), the fused kernels over the (K, N) wire buffer
    ("flat"), or those kernels in one shard_map region over the mesh
    (`fl_shard_map.make_round_ops`, "flat_sharded", which needs `mesh`:
    the buffer's client axis shards over ("pod","data"), zero-padded to
    the axis size). If the mesh also has a "model" axis of size > 1, the
    buffer becomes a 2D (client x model) tile grid: model-sharded leaves
    (models/sharding.param_pspecs rules) ravel shard-locally, quantization
    chunks are shard-local, and the aggregate keeps sharded leaves
    sharded; the TREE engine on such a mesh routes quantized transports
    through the same shard-local wire (fl_shard_map.make_blocked_roundtrip),
    so engine equivalence holds per transport on the 2D mesh too.
    Otherwise `mesh` only turns off the client-batched local update.

    With `fl.error_feedback` the round reads and rewrites `state.ef`
    ((num_clients, N) f32, rows of unselected clients untouched); with
    `fl.downlink_error_feedback` it reads and rewrites `state.dl_ef`
    ((N,) f32). `init_round_state` allocates both; a state missing a
    required buffer raises at call time.

    `fl.downlink` != "f32" compresses the broadcast global model before
    the clients' local updates (every engine trains from the identical
    dequantized reconstruction; the aggregated delta is applied to the
    server's uncompressed master params), and `fl.transport` the client
    uplink ("int4" adds `fl.group_size`-wide grouped scales).
    `fl.downlink_delta` broadcasts the compressed diff against the
    broadcast chain head carried in `state.bcast` instead of the full
    model; `state.bcast` also tracks, per client, the last broadcast
    version pulled plus an `fl.downlink_ring`-deep ring of delta
    reconstructions, so a re-selected (or buffered-admitted) client
    decodes against the base it actually holds and a client outside the
    ring's reach is charged a full-model resync.

    When `angle_pred` is None, `fl.angle_filter` selects a built-in
    predicate ("dense_only" -> `moe_dense_only_pred`); an explicit
    `angle_pred` overrides the config.

    `fl.aggregation == "buffered"` builds the buffered-async tick instead
    of the lockstep round (same signature, same engine step, with the
    staleness-aware weights): reports are admitted into `state.buf` and
    the params advance only on flush ticks.
    `arrival_fn(tick) -> (delay (K,) i32, drop (K,) bool)` overrides the
    config's random straggler/dropout draw with an explicit schedule
    (`core.server.fixed_arrival_schedule`); sync mode ignores it.

    Every builder runs each phase of the round under one
    `jax.named_scope`, so a profiler trace names the phase of each
    device op: ``fl.downlink`` (compress or decode the broadcast),
    ``fl.local_train`` (the clients' local updates), ``fl.uplink`` (wire
    compression, flattening to the (K, N) buffer, buffer admission),
    ``fl.stats`` (the global reference delta, round statistics and the
    Eq. 9 angle), ``fl.weights`` (FedAvg and Gompertz weights and their
    normalisation) and ``fl.aggregate`` (the weighted sum and its
    application to the params); the driver adds ``fl.select`` and
    ``fl.eval``. Metrics bookkeeping stays outside them.
    """
    fl.validate()
    if angle_pred is None and fl.angle_filter == "dense_only":
        angle_pred = moe_dense_only_pred
    if fl.engine == "flat_sharded" and mesh is None:
        raise ValueError(
            "engine='flat_sharded' shards the (K, N) delta buffer over "
            "the mesh client axis; pass mesh= to make_round_fn")
    if fl.mode == "parallel" and getattr(loss_fn, "has_aux", False):
        raise ValueError(
            "a loss that returns counters (loss_fn.has_aux) trains its "
            "clients one at a time: use mode='sequential'")
    if fl.mode == "sequential":
        return _make_sequential_round(loss_fn, fl, angle_pred,
                                      grad_constraint)
    step = _engine_step(fl, mesh, angle_pred)
    if fl.aggregation == "buffered":
        return _make_buffered_round(loss_fn, fl, step, delta_constraint,
                                    grad_constraint, mesh, arrival_fn)
    return _make_parallel_round(loss_fn, fl, step, delta_constraint,
                                grad_constraint, mesh)


def _lr_at(fl: FLConfig, round_idx):
    return fl.base_lr * fl.lr_decay ** jnp.asarray(round_idx, jnp.float32)


def _weight_entropy(w):
    """Shannon entropy of the (re-normalized) aggregation weights — a
    one-scalar collapse detector: ln K under FedAvg-with-equal-sizes,
    falling toward 0 as the Gompertz softmax concentrates on few nodes.
    Zero-sum rows (buffered non-flush ticks) report 0."""
    tot = jnp.sum(w)
    p = w / jnp.maximum(tot, 1e-12)
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-38)), 0.0))
    return jnp.where(tot > 0, h, 0.0)


def _telemetry_metrics(fl: FLConfig, params, node_ids, w, occupied=None,
                       down_split=None):
    """The `FLConfig(telemetry="node")` metric extension — ONE helper
    shared by all engines and both aggregation disciplines, so the tel/*
    key set cannot fork between them. `node_ids` attributes this round's
    theta/weights rows to population slots (sel_idx for sync rounds, the
    report buffer's slot column for buffered ticks); `occupied` masks
    rows that hold a live report (buffered; None = all rows live). The
    wire bytes are static per config (transport.round_bytes) and ride as
    constants so a telemetry stream is self-describing — EXCEPT under
    downlink_delta, where the round builders pass `down_split` =
    (delta_bytes, full_bytes): the ACTUAL per-round downlink cost (one
    delta payload per version a pulling client is behind, or a
    full-model resync), which replaces the static tel/bytes_down and
    additionally rides as tel/bytes_down_delta / tel/bytes_down_full."""
    n = param_count(params)
    rb = transport_mod.round_bytes(fl.clients_per_round, n, fl.transport,
                                   fl.downlink, group_size=fl.group_size)
    live_ids = (node_ids if occupied is None
                else jnp.where(occupied, node_ids, fl.num_clients))
    cohort = (jnp.zeros((fl.num_clients,), bool)
              .at[live_ids].set(True, mode="drop"))
    out = {
        "tel/nodes": jnp.asarray(node_ids, jnp.int32),
        "tel/cohort": cohort,
        "tel/weight_entropy": _weight_entropy(w),
        "tel/bytes_up": jnp.float32(rb["up"]),
        "tel/bytes_down": jnp.float32(rb["down"]),
    }
    if down_split is not None:
        down_delta, down_full = down_split
        out["tel/bytes_down"] = down_delta + down_full
        out["tel/bytes_down_delta"] = down_delta
        out["tel/bytes_down_full"] = down_full
    return out


def _down_byte_split(fl: FLConfig, n: int, ver_rows, v, pulled=None):
    """Actual downlink bytes for the clients pulling broadcast version
    `v` given their last-pulled versions `ver_rows`: a delta-served
    client pays one payload per version it is behind (delta and full
    payloads cost the same `wire_bytes(1, n, downlink)` on the wire —
    delta encoding buys reconstruction precision, not bytes); a resync
    client pays one full-model payload. `pulled` masks the rows that
    actually pulled this round (buffered admission; None = all).
    Returns (delta_bytes, full_bytes) as f32 scalars."""
    unit = transport_mod.wire_bytes(1, n, fl.downlink)
    resync = transport_mod.downlink.resync_mask(ver_rows, v,
                                                fl.downlink_ring)
    payloads_d = jnp.where(resync, 0, v - ver_rows)
    payloads_f = jnp.where(resync, 1, 0)
    if pulled is not None:
        payloads_d = jnp.where(pulled, payloads_d, 0)
        payloads_f = jnp.where(pulled, payloads_f, 0)
    return (jnp.sum(payloads_d).astype(jnp.float32) * unit,
            jnp.sum(payloads_f).astype(jnp.float32) * unit)


def _pad_rows(a, kp: int, fill=0.0):
    """Pad axis 0 to kp rows with a constant (client-axis shard padding).

    jnp.pad, NOT concatenate-with-a-zero-block: XLA's SPMD partitioner has
    been observed to mis-partition a concatenate feeding a shard_map region
    on 2D (client x model) host-device meshes, silently corrupting the
    padded buffer; a pad op partitions correctly.
    """
    k = a.shape[0]
    if kp == k:
        return a
    return jnp.pad(a, [(0, kp - k)] + [(0, 0)] * (a.ndim - 1),
                   constant_values=jnp.asarray(fill, a.dtype))


def _check_state(fl: FLConfig, state: RoundState) -> None:
    """Raise if `state` lacks a buffer the config calls for."""
    for needed, flag, field, init in (
            (fl.aggregation == "buffered", "aggregation='buffered'", "buf",
             "core.buffer.init_report_buffer"),
            (fl.error_feedback, "error_feedback=True", "ef",
             "transport.init_error_feedback"),
            (fl.downlink_error_feedback, "downlink_error_feedback=True",
             "dl_ef", "transport.downlink.init_downlink_error_feedback"),
            (fl.downlink_delta, "downlink_delta=True", "bcast",
             "transport.downlink.init_broadcast_state")):
        if needed and getattr(state, field) is None:
            raise ValueError(
                f"fl.{flag}: state.{field} is missing — build the state "
                f"with fl.init_round_state (or {init})")


def _downlink(fl: FLConfig, state: RoundState, sel_idx, pulled=None):
    """Server -> client broadcast: compress the global model to the
    `fl.downlink` wire.

    The server keeps `state.params` as its uncompressed master copy (the
    aggregated delta is applied to it); every client trains from the SAME
    dequantized reconstruction, so the engines cannot fork. `pulled`
    masks the selected rows that actually pull this broadcast (buffered
    admission; None = all): only they move their last-pulled version and
    are charged downlink bytes. Returns (the params the clients train
    from, the new `dl_ef`, the new `bcast`, the telemetry's (delta, full)
    downlink bytes or None)."""
    params = state.params
    new_dl, new_bcast, down_split = state.dl_ef, state.bcast, None
    with jax.named_scope("fl.downlink"):
        if fl.downlink != "f32":
            pvec, punravel = treemath.tree_ravel(params)
            if fl.downlink_delta:
                # delta encoding: compress the model DIFF against the
                # chain head (the canonical reconstruction) — per-round
                # diffs are small, so the quant scales track them tightly.
                pvec = pvec - state.bcast.head
            if fl.downlink_error_feedback:
                # EF-SGD mirror: replay the carried broadcast residual,
                # then carry what this round's compression drops.
                pvec = pvec + state.dl_ef
            qd = transport_mod.downlink.compress(pvec, fl.downlink)
            recon = transport_mod.downlink.decompress(qd)
            if fl.downlink_error_feedback:
                new_dl = pvec - recon
            if fl.downlink_delta:
                # publish version v = head_ver + 1 into the ring and
                # advance the chain head (recon is this version's delta
                # reconstruction D_v); a pulling client receives the new
                # head (delta-decoded or resynced), so its last-pulled
                # version moves to v.
                new_bcast = transport_mod.downlink.advance_broadcast(
                    state.bcast, recon)
                recon = new_bcast.head
                v = new_bcast.head_ver
                if fl.telemetry:
                    down_split = _down_byte_split(
                        fl, pvec.shape[0], state.bcast.ver[sel_idx], v,
                        pulled)
                if pulled is not None:
                    v = jnp.where(pulled, v, new_bcast.ver[sel_idx])
                new_bcast = new_bcast._replace(
                    ver=new_bcast.ver.at[sel_idx].set(v))
            params = punravel(recon)
    return params, new_dl, new_bcast, down_split


def _compress_uplink(fl: FLConfig, deltas, ef, sel_idx, pulled=None):
    """Client uplink: ravel the stacked deltas into the (K, N) f32 buffer
    and compress it to the `fl.transport` wire.

    With `fl.error_feedback` (EF-SGD) the selected rows' carried residual
    is replayed into this round's signal and what quantization drops is
    carried instead, except on rows that did not ship (`pulled` False:
    buffered admission), which keep theirs. Returns (the wire as a
    `transport.QuantizedDelta`, the unravel of an (N,) vector to one
    param tree, the new `ef`)."""
    flat, unravel = treemath.tree_ravel_stacked(deltas)
    if fl.transport == "f32":
        return transport_mod.QuantizedDelta(flat, None, "f32"), unravel, ef
    if fl.error_feedback:
        flat = flat + ef[sel_idx]
    q = transport_mod.quantize(flat, fl.transport, group_size=fl.group_size)
    if fl.error_feedback:
        resid = flat - transport_mod.dequantize(q)
        if pulled is not None:
            resid = jnp.where(pulled[:, None], resid, ef[sel_idx])
        ef = ef.at[sel_idx].set(resid)
    return q, unravel, ef


def _column_mask(params, angle_pred):
    """(N,) 0/1 angle-filter mask over the ravel order, or None."""
    if not angle_pred:
        return None
    return treemath.segment_mask(params, angle_keep_list(params, angle_pred))


def _engine_step(fl: FLConfig, mesh, angle_pred) -> Callable:
    """The FedAdp aggregation step of `fl.engine`, chosen once per round
    builder: Eq. 8's statistics against the psi-weighted reference delta
    g, Eq. 9, the weights and the weighted sum.

    step(params, reports, psi, smoothed_sel, count_sel, weigh, wargs)
      -> (g, dots, sqs, sqg, delta, theta, theta_sm, w)

    `reports` is the K-stacked delta tree (the tree engine, and
    flat_sharded in the sync round on a 2D mesh, which ravels and
    quantizes in its region) or the (wire, unravel) pair of
    `_compress_uplink` (the flat engines). `psi` weighs the reference g,
    `smoothed_sel`/`count_sel` are the reports' Eq. 9 slots, and
    `weigh(theta_sm, *wargs) -> w` weighs the reports (None: w = psi and
    the delta is g — FedAvg and FedProx). `params` gives the angle
    filter's leaves and the param dtypes: g comes back an f32 tree, delta
    a tree at the param dtypes (f32 accumulate, one cast).

    "tree" runs the per-leaf treemath reductions (the reference), "flat"
    the fused kernels of `fl_shard_map.wire_kernels` over the (K, N)
    buffer, and "flat_sharded" the same kernels in one shard_map region
    (`fl_shard_map.make_round_ops`) with the client axis zero-padded to
    the mesh: padded rows get zero psi and size, so exactly zero weight
    and zero stats contribution, and the (K,) outputs are sliced back.
    """
    if fl.engine == "tree":
        return functools.partial(_tree_step, angle_pred)
    if fl.engine == "flat":
        return functools.partial(_flat_step, angle_pred)
    csize = fl_shard_map.client_axis_size(mesh)
    msize = fl_shard_map.model_axis_size(mesh)
    tree_in = msize > 1 and fl.aggregation == "sync"
    sharding = fl_shard_map.flat_client_sharding(mesh)

    def step(params, reports, psi, smoothed_sel, count_sel, weigh, wargs):
        k = psi.shape[0]
        kp = -(-k // csize) * csize
        rows = [a if jnp.ndim(a) == 0 else _pad_rows(a, kp)
                for a in (psi, smoothed_sel, count_sel, *wargs)]
        if tree_in:
            with jax.named_scope("fl.uplink"):
                deltas = jax.tree.map(lambda d: _pad_rows(d, kp), reports)
            keep = angle_keep_list(params, angle_pred) if angle_pred else None
            op = fl_shard_map.make_round_ops(
                mesh, weigh=weigh, transport=fl.transport,
                group_size=fl.group_size, template=deltas,
                pspecs=models_sharding.param_pspecs(params, mesh), keep=keep)
            g, dots, sqs, sqg, delta, theta, theta_sm, w = op(deltas, *rows)
            with jax.named_scope("fl.aggregate"):
                delta = jax.tree.map(lambda d, p: d.astype(p.dtype), delta,
                                     params)
        else:
            q, unravel = reports
            n = q.n if q.n >= 0 else q.values.shape[1]
            npad = -(-n // msize) * msize
            mask = _column_mask(params, angle_pred)
            if mask is None:
                mask = jnp.ones((n,), jnp.float32)
            with jax.named_scope("fl.uplink"):
                values = _pad_rows(q.values, kp)
                if npad != n:
                    # zero columns: zero in the rows and in g, so every
                    # stat contribution is exactly zero; sliced off below.
                    values = jnp.pad(values, ((0, 0), (0, npad - n)))
                    mask = jnp.pad(mask, (0, npad - n))
                values = jax.lax.with_sharding_constraint(values, sharding)
                scales = None if q.scales is None else \
                    jax.lax.with_sharding_constraint(
                        _pad_rows(q.scales, kp, 1.0), sharding)
            op = fl_shard_map.make_round_ops(
                mesh, weigh=weigh, transport=q.transport,
                group_size=fl.group_size)
            g, dots, sqs, sqg, delta, theta, theta_sm, w = op(
                values, scales, mask, *rows)
            if npad != n:
                g, delta = g[:n], delta[:n]
            with jax.named_scope("fl.stats"):
                g = unravel(g, jnp.float32)
            with jax.named_scope("fl.aggregate"):
                delta = unravel(delta)
        return g, dots[:k], sqs[:k], sqg, delta, theta[:k], theta_sm[:k], w[:k]

    return step


def _tree_step(angle_pred, params, deltas, psi, smoothed_sel, count_sel,
               weigh, wargs):
    """`_engine_step` of the tree engine."""
    with jax.named_scope("fl.stats"):
        angle_mask = (build_angle_mask(params, angle_pred)
                      if angle_pred else None)
        # f32: rounding g to a (possibly bf16) leaf dtype before the stats
        # would lose the angle signal and diverge from the flat engines;
        # it also matches init_prev_delta's f32 threading.
        g = treemath.tree_weighted_sum(deltas, psi, jnp.float32)
        d_view = angle_mask(deltas) if angle_mask else deltas
        g_view = angle_mask(g) if angle_mask else g
        dots = treemath.tree_vdot_batched(d_view, g_view)
        sqs = treemath.tree_sqnorm_batched(d_view)
        sqg = treemath.tree_sqnorm(g_view)
        theta = weighting.instantaneous_angle(dots, sqs, sqg)
        theta_sm = weighting.smoothed_angle(theta, smoothed_sel, count_sel)
    w, delta = fl_shard_map.weigh_and_sum(
        weigh, wargs, theta_sm, psi, g,
        lambda w: treemath.tree_weighted_sum(deltas, w, jnp.float32))
    with jax.named_scope("fl.aggregate"):
        delta = jax.tree.map(lambda d, p: d.astype(p.dtype), delta, params)
    return g, dots, sqs, sqg, delta, theta, theta_sm, w


def _flat_step(angle_pred, params, reports, psi, smoothed_sel, count_sel,
               weigh, wargs):
    """`_engine_step` of the flat engine: one pass of the fused kernels
    over the (K, N) wire buffer for g and the stats, one for the weighted
    sum (the client axis is chunked inside the kernels, so any K fits)."""
    q, unravel = reports
    agg, stats = fl_shard_map.wire_kernels(q)
    with jax.named_scope("fl.stats"):
        mask = _column_mask(params, angle_pred)
        g_flat = agg(psi)
        dots, sqs, sqg = stats(g_flat, mask)
        g = unravel(g_flat, jnp.float32)
        theta = weighting.instantaneous_angle(dots, sqs, sqg)
        theta_sm = weighting.smoothed_angle(theta, smoothed_sel, count_sel)
    w, delta = fl_shard_map.weigh_and_sum(weigh, wargs, theta_sm, psi,
                                          g_flat, agg)
    with jax.named_scope("fl.aggregate"):
        delta = unravel(delta)
    return g, dots, sqs, sqg, delta, theta, theta_sm, w


def _core_metrics(losses, lr, dots, sqs, sqg, theta, theta_sm, w,
                  landed=None, n_landed=None):
    """The metrics every round reports. The divergence is Fig. 7's
    (1/K) sum_i ||dF - dF_i|| with dF ~ -delta/lr, over the `landed` rows
    (`n_landed` of them) where given."""
    gap = jnp.sqrt(jnp.maximum(sqs - 2 * dots + sqg, 0.0))
    if landed is None:
        div = jnp.mean(gap) / lr
    else:
        div = jnp.sum(jnp.where(landed, gap, 0.0)) / n_landed / lr
    return {
        "loss": jnp.mean(losses), "theta": theta, "theta_smoothed": theta_sm,
        "weights": w, "divergence": div, "lr": lr, "cos": jnp.cos(theta),
        "expected_contribution": weighting.expected_contribution(
            w, jnp.cos(theta)),
    }


def _make_parallel_round(loss_fn, fl: FLConfig, step, delta_constraint,
                         grad_constraint, mesh):
    """The lockstep round: every selected client pulls the broadcast,
    trains and reports, and the server aggregates all K reports."""
    path = local_update_path(loss_fn, fl, mesh)
    # A mesh with a "model" axis of size > 1 switches the wire to the 2D
    # (client x model) blocked layout: quantization chunks are SHARD-LOCAL
    # (never straddling a model-axis split) and model-sharded leaves are
    # raveled per shard (no all-gather). flat_sharded quantizes inside its
    # region; the tree engine consumes the same wire through
    # fl_shard_map.make_blocked_roundtrip.
    wire_2d = (mesh is not None and fl.engine in ("tree", "flat_sharded")
               and fl_shard_map.model_axis_size(mesh) > 1)
    if wire_2d and fl.transport != "f32" and fl.error_feedback:
        raise ValueError(
            "error_feedback carries a global (num_clients, N) residual in "
            "tree-ravel order, but a (client x model) mesh quantizes the "
            "wire in shard-local blocked order; drop error_feedback or "
            "use a client-only mesh")
    weigh = None
    if fl.method == "fedadp":
        def weigh(theta_sm, sizes):
            return weighting.fedadp_weights(theta_sm, sizes, fl.alpha)

    def round_fn(state: RoundState, batches, sel_idx, data_sizes):
        _check_state(fl, state)
        angle_state = state.angle
        lr = _lr_at(fl, state.round)
        params, new_dl, new_bcast, down_split = _downlink(fl, state, sel_idx)
        deltas, losses = _local_train(loss_fn, path, params, batches, lr,
                                      fl, grad_constraint, delta_constraint)
        with jax.named_scope("fl.weights"):
            psi_avg = weighting.fedavg_weights(data_sizes)

        new_ef = state.ef
        with jax.named_scope("fl.uplink"):
            if wire_2d:
                reports = deltas
                if fl.engine == "tree" and fl.transport != "f32":
                    # the tree reference reads the dequantized shard-local
                    # wire (flat_sharded quantizes it in its region).
                    k = data_sizes.shape[0]
                    csize = fl_shard_map.client_axis_size(mesh)
                    kp = -(-k // csize) * csize
                    padded = jax.tree.map(lambda d: _pad_rows(d, kp), deltas)
                    rt = fl_shard_map.make_blocked_roundtrip(
                        mesh, padded,
                        models_sharding.param_pspecs(params, mesh),
                        transport=fl.transport, group_size=fl.group_size)
                    reports = jax.tree.map(lambda d: d[:k], rt(padded))
            elif fl.engine == "tree" and fl.transport == "f32":
                reports = deltas
            else:
                q, unravel, new_ef = _compress_uplink(fl, deltas, state.ef,
                                                      sel_idx)
                # reference contract: the tree engine never reads the wire
                # buffer — it reads the dequantized stacked tree, in f32
                # (rounding to a bf16 leaf dtype would add a second loss
                # the flat engines, which stream the wire, never incur).
                reports = ((q, unravel) if fl.engine != "tree" else
                           treemath.tree_unravel_stacked(
                               deltas, transport_mod.dequantize(q),
                               jnp.float32))

        with jax.named_scope("fl.stats"):
            smoothed_sel = angle_state.smoothed[sel_idx]
            count_sel = angle_state.count[sel_idx]
        g_avg, dots, sqs, sqg, delta, theta, theta_sm, w = step(
            params, reports, psi_avg, smoothed_sel, count_sel, weigh,
            (data_sizes,))
        with jax.named_scope("fl.stats"):
            new_angle = _scatter_angles(angle_state, sel_idx, theta)
        # the delta lands on the server's uncompressed master params — the
        # downlink reconstruction is what the CLIENTS trained from.
        with jax.named_scope("fl.aggregate"):
            new_params = treemath.tree_add(state.params, delta)

        metrics = _core_metrics(losses, lr, dots, sqs, sqg, theta, theta_sm,
                                w)
        if fl.telemetry:
            metrics.update(_telemetry_metrics(fl, params, sel_idx, w,
                                              down_split=down_split))
        return state._replace(
            params=new_params, angle=new_angle, prev_delta=g_avg,
            ef=new_ef, dl_ef=new_dl, bcast=new_bcast,
            round=state.round + 1,
        ), metrics

    return round_fn


def _make_buffered_round(loss_fn, fl: FLConfig, step, delta_constraint,
                         grad_constraint, mesh, arrival_fn):
    """The buffered-async server tick (aggregation="buffered").

    Same `round_fn(state, batches, sel_idx, data_sizes)` signature as the
    lockstep round, but one call is one server TICK, not one model
    version: the K candidate clients pull the current broadcast, train,
    and their reports are ADMITTED into the free slots of the K-row
    report buffer (`state.buf`, see core.buffer) with simulated arrival
    delays; the params advance only on ticks where at least `buffer_m`
    of the in-flight reports have LANDED. `state.round` counts ticks (it
    still drives the lr schedule and `arrival_fn(tick)` indexing); a
    report's staleness `age` counts flushes — actual model versions —
    between its pull and its aggregation.

    Everything is mask-based so the tick is shape-static: non-admitted
    candidates are computed and discarded (occupied slots, busy clients,
    in-transit dropouts), non-landed rows get exactly zero aggregation
    weight, and a non-flush tick applies `jnp.where(do_flush, ...)`
    no-ops to params/angles/prev_delta. With buffer_m == K and no
    stragglers/dropouts every tick admits, lands, and flushes the whole
    cohort at age 0, and each masked op reduces bit-exactly to its sync
    counterpart — that equivalence is pinned per engine by
    tests/test_buffered.py.
    """
    stochastic = (arrival_fn is None
                  and (fl.straggle_prob > 0 or fl.dropout_prob > 0))
    path = local_update_path(loss_fn, fl, mesh)
    m_flush = fl.buffer_m if fl.buffer_m > 0 else fl.clients_per_round
    weigh = None
    if fl.method == "fedadp":
        def weigh(theta_sm, sizes, age, landed, flush, held):
            # a non-flush tick applies no Eq. 9: its weights are those of
            # the carried angles.
            return weighting.buffered_fedadp_weights(
                jnp.where(flush, theta_sm, held), sizes, age, landed,
                fl.alpha, fl.staleness_beta)

    def round_fn(state: RoundState, batches, sel_idx, data_sizes):
        _check_state(fl, state)
        angle_state = state.angle
        lr = _lr_at(fl, state.round)
        k = fl.clients_per_round

        # ---- arrival injection: when do this tick's reports land? ----
        # RNG discipline: the key is only consumed when the config is
        # actually stochastic, so the deterministic case threads
        # state.rng untouched exactly like the sync round (this is part
        # of the bit-exact sync-equivalence contract).
        with jax.named_scope("fl.uplink"):
            new_rng = state.rng
            if arrival_fn is not None:
                delay, drop = arrival_fn(state.round)
                delay = jnp.asarray(delay, jnp.int32)
                drop = jnp.asarray(drop, bool)
            elif stochastic:
                new_rng, k_arr = jax.random.split(state.rng)
                delay, drop = buffer_mod.draw_arrivals(
                    k_arr, k, fl.straggle_prob, fl.straggle_max,
                    fl.dropout_prob)
            else:
                delay = jnp.zeros((k,), jnp.int32)
                drop = jnp.zeros((k,), bool)
            # a free slot admits its candidate unless the client already
            # has a report in flight (full participation re-offers
            # everyone) or the report drops in transit (the slot stays
            # free — liveness never waits on a timeout).
            busy = buffer_mod.population_busy(state.buf, fl.num_clients)
            admit = state.buf.free & ~busy[sel_idx] & ~drop

        # every candidate trains from the CURRENT broadcast, so the
        # downlink EF and the broadcast chain advance every tick; only the
        # ADMITTED candidates pull it — admission fixes a buffered
        # client's decode base — so busy and dropped candidates are
        # neither version-advanced nor charged downlink bytes.
        params, new_dl, new_bcast, down_split = _downlink(
            fl, state, sel_idx, pulled=admit)
        deltas, losses = _local_train(loss_fn, path, params, batches, lr,
                                      fl, grad_constraint, delta_constraint)

        # ---- client uplink: compress to the wire, buffer the f32
        # reconstruction (the tree engine never reads the wire, and rows
        # must survive across ticks independent of the transport); a
        # non-admitted report never shipped, so its EF residual stays
        # carried ----
        with jax.named_scope("fl.uplink"):
            q, unravel, new_ef = _compress_uplink(fl, deltas, state.ef,
                                                  sel_idx, pulled=admit)
            rows = (q.values if fl.transport == "f32"
                    else transport_mod.dequantize(q))
            buf = buffer_mod.admit(state.buf, admit, rows, sel_idx,
                                   data_sizes, delay)

        with jax.named_scope("fl.weights"):
            landed = buffer_mod.landed_mask(buf)
            num_landed = jnp.sum(landed.astype(jnp.int32))
            do_flush = num_landed >= m_flush
            # staleness-discounted FedAvg weights over the landed rows — the
            # angle-reference global delta g, exactly psi_avg when every row
            # landed at age 0.
            psi_b = weighting.buffered_fedavg_weights(
                buf.sizes, buf.age, landed, fl.staleness_beta)

        with jax.named_scope("fl.stats"):
            smoothed_sel = angle_state.smoothed[buf.slot]
            count_sel = angle_state.count[buf.slot]
            reports = (treemath.tree_unravel_stacked(deltas, buf.data,
                                                     jnp.float32)
                       if fl.engine == "tree" else
                       (transport_mod.QuantizedDelta(buf.data, None, "f32"),
                        unravel))
        g_avg, dots, sqs, sqg, delta, theta, _, w = step(
            params, reports, psi_b, smoothed_sel, count_sel, weigh,
            (buf.sizes, buf.age, landed, do_flush, smoothed_sel))

        # Eq. 9 over the LANDED reports only, applied only on flush ticks
        # (both masks reduce to the sync scatter when everything landed).
        with jax.named_scope("fl.stats"):
            ang_flushed = _scatter_angles_masked(angle_state, buf.slot,
                                                 theta, landed)
            new_angle = jax.tree.map(lambda a, b: jnp.where(do_flush, a, b),
                                     ang_flushed, angle_state)
            theta_sm = new_angle.smoothed[buf.slot]

        # flush: apply the aggregated delta to the master params — or, on
        # a non-flush tick, carry everything unchanged (where no-ops).
        with jax.named_scope("fl.aggregate"):
            new_params = jax.tree.map(
                lambda a, b: jnp.where(do_flush, a, b),
                treemath.tree_add(state.params, delta), state.params)
            new_prev = jax.tree.map(lambda a, b: jnp.where(do_flush, a, b),
                                    g_avg, state.prev_delta)
            final_buf = buffer_mod.advance(buf, landed, do_flush)

        nl_f = jnp.maximum(num_landed.astype(jnp.float32), 1.0)
        metrics = _core_metrics(losses, lr, dots, sqs, sqg, theta, theta_sm,
                                w, landed, nl_f)
        metrics.update({
            "flushed": do_flush.astype(jnp.int32),
            "buffer_landed": num_landed,
            "staleness": jnp.sum(jnp.where(landed, buf.age, 0)
                                 .astype(jnp.float32)) / nl_f,
        })
        if fl.telemetry:
            # attribution follows the BUFFER rows (theta/weights are
            # computed over them), not this tick's candidates; ages and
            # the landed mask are per-row, occupancy counts live slots.
            metrics.update(_telemetry_metrics(fl, params, buf.slot, w,
                                              occupied=~buf.free,
                                              down_split=down_split))
            metrics["tel/ages"] = buf.age
            metrics["tel/landed"] = landed
            metrics["tel/occupancy"] = jnp.sum((~buf.free)
                                               .astype(jnp.int32))
        return state._replace(
            params=new_params, angle=new_angle, prev_delta=new_prev,
            ef=new_ef, dl_ef=new_dl, bcast=new_bcast,
            buf=final_buf, rng=new_rng, round=state.round + 1,
        ), metrics

    return round_fn


# Full-size trees the single-pass sequential round holds besides its K
# kept clients: the params, the FedAvg delta g (in `prev_delta`'s place)
# and the training client's grads; a client trains in the slot it is
# kept in.
SINGLE_PASS_TREES = 3
# The share of the device's limit left to the rest of the round:
# activations, batches, the stats' leaf copies and the allocator's
# fragmentation. Calibrated on dsv2lite's chip share (K = 4, 2.14 GB
# trees): its 2-round block compiles for a v5e to 16,270,754,376 B, 7.6 %
# of the chip's 16,909,336,064 B above its (K + 3) trees.
SINGLE_PASS_MARGIN = 0.08


def device_bytes_limit() -> Optional[int]:
    """The local device's memory limit in bytes, or None where the device
    reports none (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")


def single_pass_fits(k: int, tree_bytes: int, limit: Optional[int]) -> bool:
    """Whether the sequential round can keep all K clients' trained params
    and train each once a round: (K + SINGLE_PASS_TREES) trees of
    `tree_bytes` (the whole params tree, not one device's shard, so an
    FSDP mesh errs toward two passes) within `limit` less
    SINGLE_PASS_MARGIN of it. A device that reports no limit (`limit`
    None, the CPU) gets the two-pass round: nothing says K trees fit."""
    if not limit:
        return False
    return (k + SINGLE_PASS_TREES) * tree_bytes <= (
        1.0 - SINGLE_PASS_MARGIN) * limit


def _make_sequential_round(loss_fn, fl: FLConfig, angle_pred=None,
                           grad_constraint=None):
    """The round that trains one client at a time, for a model of which
    the chip holds one training copy. No client's delta is ever written
    out: deltas are read as (trained params - params), leaf by leaf, and
    the statistics stream through `round_stats.leaf_stats`. Which
    schedule runs follows from K, the params' bytes and the device's
    limit (`single_pass_fits`, at trace time):

    - single pass: each client trains once and its trained params are
      kept, K trees in the params' dtype (the last one where it trained).
      g, each client's statistics against g, Eq. 9, the weights and the
      weighted sum are then read from the kept trees, in client order
      and with the two-pass round's float ops.
    - two passes, where the K trees would not fit: pass 1 accumulates the
      FedAvg delta g in what becomes `prev_delta`; pass 2 retrains the
      same clients on the same batches, streams each client's statistics
      against g and accumulates the Gompertz-weighted sum. Besides the
      params and `prev_delta` it holds three full-size f32 trees: the
      weighted sum and the training client's params and grads.

    `stale_angles` measures angles against the previous round's g in one
    pass of the second kind. The metrics' `train_passes` counts how often
    the round trained its clients (1.0 or 2.0). A loss with counters
    (`loss_fn.has_aux`) adds them, summed over every client trained, to
    the round's metrics."""
    has_aux = getattr(loss_fn, "has_aux", False)

    def train(params, b_i, lr):
        """(client's final params, mean loss, counters)."""
        if has_aux:
            p_fin, losses, counters = _local_final_counted(
                loss_fn, params, b_i, lr, fl.prox_mu, grad_constraint)
            return p_fin, jnp.mean(losses), _summed(counters)
        p_fin, losses = _local_final(loss_fn, params, b_i, lr, fl.prox_mu,
                                     grad_constraint)
        return p_fin, jnp.mean(losses), {}

    def axpy_delta(a, p_fin, params, acc):
        """acc + a (p_fin - params), leaf by leaf, in f32."""
        return jax.tree.map(
            lambda f, p, c: c + a * (f.astype(jnp.float32)
                                     - p.astype(jnp.float32)),
            p_fin, params, acc)

    def stats(p_fin, params, g, keep):
        dot = sq = sqg = jnp.zeros((), jnp.float32)
        leaves = zip(jax.tree.leaves(p_fin), jax.tree.leaves(params),
                     jax.tree.leaves(g))
        for i, (f, p, gl) in enumerate(leaves):
            if keep is None or keep[i]:
                d_, s_, g_ = round_stats_mod.leaf_stats(f, p, gl)
                dot, sq, sqg = dot + d_, sq + s_, sqg + g_
        return dot, sq, sqg

    def add(a, b):
        return jax.tree.map(jnp.add, a, b)

    def apply(params, num, den):
        """params + num / den, cast back to the params' dtypes."""
        delta = treemath.tree_scale(num, 1.0 / jnp.maximum(den, 1e-12))
        return jax.tree.map(
            lambda p, d: (p.astype(jnp.float32) + d).astype(p.dtype),
            params, delta)

    def round_fn(state: RoundState, batches, sel_idx, data_sizes):
        params, angle_state = state.params, state.angle
        lr = _lr_at(fl, state.round)
        keep = angle_keep_list(params, angle_pred) if angle_pred else None
        with jax.named_scope("fl.weights"):
            psi_avg = data_sizes / jnp.sum(data_sizes)
        zeros32 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        counts0 = {}
        if has_aux:  # zeros shaped as one step's counters
            step0 = jax.tree.map(lambda a: a[0, 0], batches)
            counts0 = jax.tree.map(
                jnp.zeros_like, jax.eval_shape(loss_fn, params, step0)[1])

        def smoothed(dot, sq, sqg, idx):
            """Eq. 8's angle and Eq. 9's mean of the clients in `idx`."""
            theta = weighting.instantaneous_angle(dot, sq, sqg)
            return theta, weighting.smoothed_angle(
                theta, angle_state.smoothed[idx], angle_state.count[idx])

        def unnormalised(sm, D):
            """The weights before normalisation: D e^{f(sm)}, or D."""
            if fl.method == "fedadp":
                return D * jnp.exp(weighting.gompertz(sm, fl.alpha))
            return D

        k = sel_idx.shape[0]
        tree_bytes = sum(p.size * p.dtype.itemsize
                         for p in jax.tree.leaves(params))
        single = not fl.stale_angles and single_pass_fits(
            k, tree_bytes, device_bytes_limit())

        if single:
            # ---- one pass: train each client once and keep what it
            # trained, the K - 1 first stacked and the K-th where it
            # trained. Client i writes client i - 1's params into the
            # stack before it trains (the barrier orders the two), so it
            # trains in the slot they leave. ----
            def p0(carry, xs):
                kept, last, counts = carry
                b_i, i = xs
                if k > 1:
                    kept = jax.tree.map(
                        lambda s, l: jax.lax.dynamic_update_index_in_dim(
                            s, l, jnp.maximum(i - 1, 0), 0), kept, last)
                kept, start = jax.lax.optimization_barrier((kept, params))
                with jax.named_scope("fl.local_train"):
                    p_i, loss, c_i = train(start, b_i, lr)
                return (kept, p_i, add(counts, c_i)), loss

            kept0 = jax.tree.map(
                lambda p: jnp.zeros((k - 1,) + p.shape, p.dtype), params)
            (kept, p_k, counts), losses = jax.lax.scan(
                p0, (kept0, params, counts0),
                (batches, jnp.arange(k, dtype=jnp.int32)))
            trained = [jax.tree.map(lambda a, i=i: a[i], kept)
                       for i in range(k - 1)] + [p_k]

            with jax.named_scope("fl.stats"):
                g_ref = zeros32
                for i, p_i in enumerate(trained):
                    g_ref = axpy_delta(psi_avg[i], p_i, params, g_ref)
                dots, sqs, sqgs = (jnp.stack(s) for s in zip(
                    *(stats(p_i, params, g_ref, keep) for p_i in trained)))
                theta, theta_sm = smoothed(dots, sqs, sqgs, sel_idx)
            with jax.named_scope("fl.weights"):
                w_un = unnormalised(theta_sm, data_sizes.astype(jnp.float32))
            with jax.named_scope("fl.aggregate"):
                num, den = zeros32, jnp.zeros((), jnp.float32)
                for i, p_i in enumerate(trained):
                    num = axpy_delta(w_un[i], p_i, params, num)
                    den = den + w_un[i]
                new_params = apply(params, num, den)
            new_prev, passes = g_ref, 1.0
        else:
            if not fl.stale_angles:
                # ---- pass 1: global (FedAvg-weighted) delta ----
                def p1(carry, xs):
                    acc, counts = carry
                    b_i, psi_i = xs
                    with jax.named_scope("fl.local_train"):
                        p_i, loss, c_i = train(params, b_i, lr)
                    with jax.named_scope("fl.stats"):
                        acc = axpy_delta(psi_i, p_i, params, acc)
                    return (acc, add(counts, c_i)), loss

                (g_ref, counts0), losses = jax.lax.scan(
                    p1, (zeros32, counts0), (batches, psi_avg))
                g_acc0 = None
            else:
                g_ref = state.prev_delta
                losses = None
                g_acc0 = zeros32

            # ---- pass 2 (or single stale pass): stats + online weighted
            # sum ----
            def p2(carry, xs):
                num, den, g_acc, counts = carry
                b_i, psi_i, D_i, idx_i = xs
                with jax.named_scope("fl.local_train"):
                    p_i, loss, c_i = train(params, b_i, lr)
                with jax.named_scope("fl.stats"):
                    dot, sq, sqg_i = stats(p_i, params, g_ref, keep)
                    theta_i, sm = smoothed(dot, sq, sqg_i, idx_i)
                with jax.named_scope("fl.weights"):
                    w_i = unnormalised(sm, D_i)
                with jax.named_scope("fl.aggregate"):
                    num = axpy_delta(w_i, p_i, params, num)
                    if g_acc is not None:  # the stale pass's FedAvg delta
                        g_acc = axpy_delta(psi_i, p_i, params, g_acc)
                return ((num, den + w_i, g_acc, add(counts, c_i)),
                        (theta_i, sm, dot, sq, sqg_i, loss))

            (num, den, g_acc, counts), ys = jax.lax.scan(
                p2, (zeros32, jnp.zeros((), jnp.float32), g_acc0, counts0),
                (batches, psi_avg, data_sizes.astype(jnp.float32), sel_idx),
            )
            theta, theta_sm, dots, sqs, sqgs, losses2 = ys
            with jax.named_scope("fl.aggregate"):
                new_params = apply(params, num, den)
            if fl.stale_angles:
                losses, new_prev, passes = losses2, g_acc, 1.0
            else:
                new_prev, passes = g_ref, 2.0
        with jax.named_scope("fl.stats"):
            new_state = _scatter_angles(angle_state, sel_idx, theta)
        with jax.named_scope("fl.weights"):
            w = (
                weighting.fedadp_weights(theta_sm, data_sizes, fl.alpha)
                if fl.method == "fedadp"
                else psi_avg
            )
        metrics = _core_metrics(losses, lr, dots, sqs, sqgs, theta,
                                theta_sm, w)
        metrics.update(counts)
        metrics["train_passes"] = jnp.float32(passes)
        if fl.telemetry:
            metrics.update(_telemetry_metrics(fl, params, sel_idx, w))
        return state._replace(
            params=new_params, angle=new_state, prev_delta=new_prev,
            round=state.round + 1,
        ), metrics

    return round_fn


def init_prev_delta(params: PyTree) -> PyTree:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
