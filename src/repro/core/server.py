"""Federated server loop for the paper's classification experiments.

A thin host-side wrapper over the device-resident driver
(`core.driver`): the node datasets are stacked onto the device once, and
every round — client selection, per-client epoch batching, the compiled
round itself, and the test eval — runs from the device RNG inside one
compiled step whose carry is the unified `fl.RoundState`.

Two execution modes share that step bit-for-bit:

* `run(mode="stepwise")` (and `step()`) — one jit dispatch +
  `device_get` per round (the per-round tests' path, and the easiest
  to poke at).
* `run(mode="scanned")` — the whole run as chunked `lax.scan` blocks
  with host-side early exit between blocks (`driver.run_rounds`),
  removing the per-round dispatch/sync overhead entirely. Table-I
  semantics (eval cadence, rounds-to-target) are preserved exactly.
  (`run_scanned()` survives as a warn-once deprecation shim.)
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.core import clients as clients_mod
from repro.core import driver as driver_mod
from repro.core import fl as fl_mod
from repro.data.synthetic import Dataset
from repro.models import small
from repro.telemetry import schema as tel_schema
from repro.telemetry import sinks as tel_sinks
from repro.telemetry import spans as tel_spans


def fixed_arrival_schedule(delays, drops):
    """Explicit per-tick arrival schedule for the buffered server.

    `delays` is (T, K) int — the arrival delay (in server ticks; 0 = on
    time) of each of tick t's K candidate reports — and `drops` is
    (T, K) bool — reports lost in transit (never admitted). Returns an
    `arrival_fn(tick) -> (delay, drop)` for `fl.make_round_fn` /
    `FedServer(arrival_fn=)`, replacing the config's random
    straggle/dropout draw with this deterministic schedule (the
    straggler-semantics tests pin exact flush behaviour with it). Ticks
    at or beyond T reuse the last row — make it zeros/False for an
    all-on-time tail.
    """
    delays = jnp.asarray(delays, jnp.int32)
    drops = jnp.asarray(drops, bool)
    if delays.shape != drops.shape:
        raise ValueError(
            f"delays {delays.shape} and drops {drops.shape} must be the "
            "same (T, K) shape")
    t_max = delays.shape[0] - 1

    def arrival_fn(tick):
        t = jnp.minimum(jnp.asarray(tick, jnp.int32), t_max)
        return delays[t], drops[t]

    return arrival_fn


@dataclasses.dataclass
class History:
    accuracy: list
    loss: list
    divergence: list
    rounds_to_target: Optional[int]
    final_accuracy: float
    thetas: list  # per-round smoothed angles of the selected clients
    weights: list
    # a language model's (`clients.lm_client`): per round, the token-
    # expert rows through the held experts' products in local training
    # (every client trained, in each pass the sequential round ran); per
    # eval round, the eval's mean loss and its expert rows
    expert_rows: list = dataclasses.field(default_factory=list)
    eval_loss: list = dataclasses.field(default_factory=list)
    eval_expert_rows: list = dataclasses.field(default_factory=list)
    # the sequential round's: per round, how often it trained its clients
    # (1.0: once, its trained params kept; 2.0: trained again for the
    # angles)
    train_passes: list = dataclasses.field(default_factory=list)


class FedServer:
    """Cross-device FL simulation, device-resident (paper Section V)."""

    def __init__(
        self,
        model,  # "mlr" | "cnn" | a clients.ClientModel
        fl: fl_mod.FLConfig,
        nodes: list,  # list[Dataset]
        test: Dataset,
        batch_size: int,
        seed: int = 0,
        angle_pred=None,
        mesh=None,
        arrival_fn=None,
    ):
        # fl.engine selects the round execution path ("tree" reference,
        # the flat-buffer Pallas path, or the client-sharded
        # "flat_sharded" variant — the latter needs `mesh`) and
        # fl.angle_filter the built-in angle predicate; all flow through
        # make_round_fn unchanged. fl.transport compresses the client
        # uplink and fl.downlink the server broadcast (optionally
        # delta-encoded via fl.downlink_delta); the EF residual carries
        # live inside the RoundState. fl.aggregation="buffered" turns
        # each step into a buffered-async server tick; `arrival_fn`
        # (fixed_arrival_schedule) then overrides the config's random
        # straggler/dropout draw.
        # Each node trains fl.local_steps minibatches of `batch_size` a
        # round, the first of a fresh permutation of its data (at most one
        # epoch).
        self.fl = fl
        self.nodes = nodes
        self.test = test
        self.batch_size = batch_size
        client = (model if isinstance(model, clients_mod.ClientModel)
                  else clients_mod.image_client(model))
        init_fn, self.apply_fn = client.init, client.apply
        loss_fn = client.loss
        # "batched", "vmap" or "sequential": how the step's local updates
        # run (`fl.local_update_path`); every run's manifest records it
        self.local_update = fl_mod.local_update_path(loss_fn, fl, mesh)

        self.data = client.stack(nodes, batch_size, fl.local_steps)
        eval_fn = client.make_eval(test)
        self._eval_fn = eval_fn
        self._step_fn = driver_mod.make_step_fn(
            loss_fn, fl, self.data, eval_fn=eval_fn, angle_pred=angle_pred,
            mesh=mesh, arrival_fn=arrival_fn)
        self._step_jit = jax.jit(self._step_fn)
        self._run_block = driver_mod.make_scan_runner(self._step_fn)

        def fresh_state(s: int) -> fl_mod.RoundState:
            # one seed, two independent streams: weight init and the
            # driver's selection/batching RNG must not share key material
            k_init, k_drv = jax.random.split(jax.random.key(s))
            return fl_mod.init_round_state(fl, init_fn(k_init), seed=k_drv)

        self._fresh_state = fresh_state
        self._seed = seed
        self.state = fresh_state(seed)
        # the host spans of every reset, step and run of this server
        # (`telemetry.spans`); `spans.counts` is the per-name call count
        self.spans = tel_spans.SpanTimer()

    def reset(self, seed: Optional[int] = None) -> None:
        """Reinitialize the RoundState (fresh params, angles, RNG stream)
        WITHOUT re-jitting — e.g. warm the jit cache with a throwaway
        round, then reset before a timed or recorded run."""
        with self.spans.span("reset"):
            self.state = self._fresh_state(
                self._seed if seed is None else seed)

    # RoundState is the single source of truth; these views keep the
    # pre-refactor attribute surface (checkpointing, tests, examples).
    @property
    def params(self):
        return self.state.params

    @property
    def angle_state(self):
        return self.state.angle

    @property
    def round(self) -> int:
        return int(self.state.round)

    def step(self, eval_every: int = 0) -> dict:
        """One stepwise round; returns host metrics. eval_every > 0 adds
        metrics["accuracy"] after rounds where (r+1) % eval_every == 0
        (-1.0 on other rounds)."""
        with self.spans.span("dispatch"):
            self.state, metrics = self._step_jit(self.state,
                                                 jnp.int32(eval_every))
        with self.spans.span("fetch"):
            return jax.device_get(metrics)

    def evaluate(self) -> float:
        """Test accuracy of the current master params (on the host for an
        image model)."""
        if self.apply_fn is None:
            return float(jax.jit(self._eval_fn)(self.state.params)["accuracy"])
        return small.accuracy(self.apply_fn, self.state.params,
                              self.test.x, self.test.y)

    def run(self, rounds: int, target_acc: Optional[float] = None,
            eval_every: int = 1, *, mode: str = "stepwise",
            verbose: bool = False, block: int = 8,
            ckpt_dir: Optional[str] = None, ckpt_every_blocks: int = 1,
            ckpt_keep: int = 3, sink=None,
            telemetry_every: int = 1) -> History:
        """Train for `rounds` rounds; the single public run surface.

        mode="stepwise" dispatches one jitted step per round (the
        per-round tests' path, easiest to poke at; `verbose` prints the
        per-eval progress line). mode="scanned" runs the same step as
        chunked `lax.scan` blocks (`driver.run_rounds`): `block` rounds
        per dispatch with host early-exit between blocks, and `ckpt_dir`
        snapshotting the full RoundState at block boundaries (see
        `restore` for the other half of a kill/resume). The two modes
        share the step function bit-for-bit — only dispatch granularity
        differs — and their History semantics match exactly: per-round
        entries stop at rounds_to_target, which is the ABSOLUTE round
        index (eval cadence stays phased on `state.round` when resuming
        a mid-run state).

        `sink` (a `repro.telemetry` TelemetrySink) streams the run as
        schema events — manifest first (its ``extra`` holds
        `self.local_update`), one ``round`` event per round
        (subsampled by `telemetry_every`), per-node FedAdp rows when the
        config has `telemetry="node"`, and a ``summary`` last. Both
        modes feed the sink through the same adapter
        (`telemetry.sinks.emit_round_block`), so the streams are
        comparable to 1e-5 — a pinned test, not a hope.

        The run is a ``run`` span of `self.spans` holding a
        ``read_round`` span for each blocking read of the round counter,
        ``dispatch`` and ``fetch`` for each step or block, and (scanned)
        ``history`` for building the History; with a `sink` their
        ``span`` events join the stream.
        """
        if mode not in ("stepwise", "scanned"):
            raise ValueError(
                f"unknown mode {mode!r} (expected 'stepwise' or 'scanned')")
        if sink is not None:
            tel_sinks.emit_manifest(
                sink, self.fl, extra={"local_update": self.local_update})
        with self._run_span(sink):
            with self.spans.span("read_round"):
                start = int(self.state.round)
            if mode == "stepwise":
                hist = History([], [], [], None, 0.0, [], [])
                ran = 0
                for r in range(rounds):
                    m = self.step(eval_every=eval_every)
                    ran += 1
                    self._append(hist, m)
                    if sink is not None:
                        tel_sinks.emit_round_block(sink, m, start + r,
                                                   every=telemetry_every)
                    acc = float(m["accuracy"])
                    if tel_schema.is_real_accuracy(acc):
                        self._append_eval(hist, m)
                        if verbose:
                            print(f"round {r+1:4d} loss {m['loss']:.4f} "
                                  f"acc {acc:.4f}")
                        if (target_acc and acc >= target_acc
                                and hist.rounds_to_target is None):
                            hist.rounds_to_target = start + r + 1
                            break
            else:
                self.state, ms, rtt, ran = driver_mod.run_rounds(
                    self._run_block, self.state, rounds,
                    eval_every=eval_every, target_acc=target_acc,
                    block=block, ckpt_dir=ckpt_dir,
                    ckpt_every_blocks=ckpt_every_blocks,
                    ckpt_keep=ckpt_keep, sink=sink,
                    telemetry_every=telemetry_every, spans=self.spans)
                with self.spans.span("history"):
                    hist = History([], [], [], rtt, 0.0, [], [])
                    stop = rtt - start if rtt is not None else ran
                    for r in range(stop):
                        self._append(hist, {k: v[r] for k, v in ms.items()})
                        acc = float(ms["accuracy"][r])
                        if tel_schema.is_real_accuracy(acc):
                            self._append_eval(
                                hist, {k: v[r] for k, v in ms.items()})
        hist.final_accuracy = hist.accuracy[-1] if hist.accuracy else 0.0
        if sink is not None:
            tel_sinks.emit_summary(
                sink, rounds=ran,
                final_accuracy=hist.final_accuracy or None,
                rounds_to_target=hist.rounds_to_target,
                target_acc=target_acc)
        return hist

    @contextlib.contextmanager
    def _run_span(self, sink):
        """The ``run`` span, with `sink` receiving this run's span events."""
        self.spans.sink = sink
        try:
            with self.spans.span("run"):
                yield
        finally:
            self.spans.sink = None

    _warned_run_scanned = False

    def run_scanned(self, rounds: int, target_acc: Optional[float] = None,
                    eval_every: int = 1, block: int = 8,
                    ckpt_dir: Optional[str] = None,
                    ckpt_every_blocks: int = 1,
                    ckpt_keep: int = 3) -> History:
        """Deprecated shim: use `run(..., mode="scanned")`."""
        if not FedServer._warned_run_scanned:
            warnings.warn(
                "FedServer.run_scanned(...) is deprecated; use "
                "FedServer.run(..., mode='scanned')",
                DeprecationWarning, stacklevel=2)
            FedServer._warned_run_scanned = True
        return self.run(rounds, target_acc, eval_every, mode="scanned",
                        block=block, ckpt_dir=ckpt_dir,
                        ckpt_every_blocks=ckpt_every_blocks,
                        ckpt_keep=ckpt_keep)

    def save_checkpoint(self, ckpt_dir: str, keep: int = 3) -> str:
        """Snapshot the current RoundState into `ckpt_dir` (atomic write,
        `latest` pointer), keyed by the absolute round index."""
        return ckpt_io.save_checkpoint(
            ckpt_dir, self.round, fl_mod.state_to_tree(self.state),
            keep=keep)

    def restore(self, source: str) -> int:
        """Resume from a checkpoint: `source` is a checkpoint directory
        (the `latest` pointer is followed) or a single .npz path. The
        restored RoundState is validated against — and elastically
        re-sized to — THIS server's config (`fl.state_from_tree`), so a
        fleet that grew or shrank since the snapshot restores with new
        clients at zero EF residual / unseen angle. Returns the absolute
        round index training will resume from."""
        if os.path.isdir(source):
            loaded = ckpt_io.load_latest(source)
            if loaded is None:
                raise FileNotFoundError(
                    f"no checkpoint found in directory {source!r}")
            _, tree = loaded
        else:
            tree = ckpt_io.load(source)
        state = fl_mod.state_from_tree(self.fl, tree)
        # the codec validates the state against its OWN params; the server
        # additionally pins them to this model's allocation.
        cur = jax.tree.map(lambda a: (a.shape, a.dtype), self.state.params)
        new = jax.tree.map(lambda a: (a.shape, a.dtype), state.params)
        if cur != new:
            raise ValueError(
                "checkpoint params do not match this server's model "
                f"(got {new}, want {cur})")
        self.state = state
        return self.round

    @staticmethod
    def _append(hist: History, m: dict) -> None:
        hist.loss.append(float(m["loss"]))
        hist.divergence.append(float(m["divergence"]))
        hist.thetas.append(np.asarray(m["theta_smoothed"]))
        hist.weights.append(np.asarray(m["weights"]))
        if "expert_rows" in m:
            hist.expert_rows.append(int(m["expert_rows"]))
        if "train_passes" in m:
            hist.train_passes.append(float(m["train_passes"]))

    @staticmethod
    def _append_eval(hist: History, m: dict) -> None:
        """The readings of a round whose eval ran."""
        hist.accuracy.append(float(m["accuracy"]))
        if "eval_loss" in m:
            hist.eval_loss.append(float(m["eval_loss"]))
            hist.eval_expert_rows.append(int(m["eval_expert_rows"]))


def _epoch_batcher(ds: Dataset, batch_size: int, seed: int):
    """Host-side reference batcher (the driver's device pipeline replaced
    it in FedServer): yields one epoch of shuffled minibatches per call,
    (tau, B, ...) — the paper's tau = E*D_i/B with E=1."""
    n = len(ds.y)
    tau = n // batch_size
    if tau < 1:
        raise ValueError(
            f"node dataset has {n} samples but batch_size={batch_size}: "
            f"tau = {n}//{batch_size} = 0 local steps — lower batch_size "
            "or grow the node's dataset")
    rng = np.random.default_rng(seed)
    while True:
        order = rng.permutation(n)[: tau * batch_size]
        xb = ds.x[order].reshape(tau, batch_size, *ds.x.shape[1:])
        yb = ds.y[order].reshape(tau, batch_size)
        yield xb, yb
