"""Device-resident federated training driver.

`core.server.FedServer` used to pay a full host round-trip per round:
numpy epoch batching, one jit dispatch, a `device_get`, and a host-side
eval — so on small models the wall clock was dominated by dispatch/sync
overhead rather than the round kernels. This module moves the whole loop
onto the device:

* **Data pipeline** — the node datasets are stacked ONCE into device
  arrays (`stack_nodes`); per-round, per-client epoch permutations are
  drawn with `jax.random` inside the compiled step (`epoch_batches`), so
  no host batching or H2D copy happens between rounds. Ragged node sizes
  are handled by a masked-argsort permutation (padding rows are never
  sampled); `batch_size > min node size` (tau = 0 local steps) raises a
  clear ValueError naming the offending node instead of a reshape error.

* **Round step** — `make_step_fn` folds client selection (device RNG,
  subset without replacement), batching, the `fl.make_round_fn` round,
  and an optional in-scan eval into one `step(state, eval_every)` whose
  carry is the unified `fl.RoundState`. The same step drives BOTH the
  stepwise server (one jit dispatch per round — the per-round tests'
  path) and the scanned driver, which is what pins scanned == stepwise.

* **Scanned driver** — `make_scan_runner` wraps the step in a
  `lax.scan` over a block of E rounds (jit-compiled once per block
  length, state buffers donated so params/EF update in place off-CPU);
  `run_rounds` chains blocks with a host-side early-exit check between
  them, preserving the paper's Table-I semantics exactly: an eval fires
  after rounds where (r+1) % eval_every == 0, and rounds_to_target is
  the first such round whose accuracy reaches the target (the scan may
  run up to one block past it; the report is exact). `run_rounds` can
  also snapshot the full RoundState at block boundaries
  (`ckpt_dir=` / `ckpt_every_blocks=`) so a preempted run restores
  bit-exactly via `fl.state_from_tree` + `checkpoint.io.load_latest`.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.core import buffer as buffer_mod
from repro.core import fl as fl_mod
from repro.telemetry import schema as tel_schema
from repro.telemetry import sinks as tel_sinks
from repro.telemetry import spans as tel_spans

PyTree = Any

# the in-scan eval fill value for rounds where the lax.cond-gated eval
# did not run — owned by the telemetry schema so sinks/flstat mask the
# SAME constant the compiled step writes (never ingest it as data).
EVAL_SENTINEL = tel_schema.EVAL_SENTINEL


class ClientData(NamedTuple):
    """Device-resident stacked node datasets.

    x/y are stacked over the client axis and zero-padded to the largest
    node (`sizes` keeps the true per-node counts; the epoch permutation
    never samples a padded row). `tau` = n_i // batch_size is the static
    per-round local step count — equal across nodes by construction
    (stacked (K, tau, B, ...) round batches admit exactly one tau).
    """

    x: jax.Array  # (C, n_max, ...) features
    y: jax.Array  # (C, n_max) int labels
    sizes: jax.Array  # (C,) i32 true per-node sample counts
    tau: int  # local steps per round (static)
    batch_size: int  # B (static)


def stack_nodes(nodes: list, batch_size: int,
                local_steps: Optional[int] = None) -> ClientData:
    """Stack host node datasets into one device-resident ClientData.

    tau is `local_steps` where given: each round a node trains on the
    first tau * batch_size rows of a fresh permutation of its data, which
    may not pass one epoch. Otherwise tau is one epoch, len // batch_size,
    equal on every node.

    Raises ValueError when a node is too small for even one batch
    (tau = len // batch_size = 0 — the old numpy batcher crashed with an
    opaque reshape error here), when `local_steps` passes a node's epoch,
    or when nodes disagree on tau.
    """
    taus = [len(ds.y) // batch_size for ds in nodes]
    if local_steps is not None:
        for i, (ds, tau) in enumerate(zip(nodes, taus)):
            if local_steps > tau:
                raise ValueError(
                    f"local_steps={local_steps} passes one epoch of node "
                    f"{i}: {len(ds.y)} samples make {tau} batches of "
                    f"{batch_size}")
        taus = [local_steps] * len(nodes)
    for i, (ds, tau) in enumerate(zip(nodes, taus)):
        if tau < 1:
            raise ValueError(
                f"node {i} has {len(ds.y)} samples but batch_size="
                f"{batch_size}: tau = {len(ds.y)}//{batch_size} = 0 local "
                "steps — lower batch_size or grow the node's dataset")
    if len(set(taus)) != 1:
        raise ValueError(
            f"nodes disagree on local steps tau = n_i//batch_size: {taus} "
            "— stacked (K, tau, B, ...) round batches admit exactly one "
            "tau (equalize node sizes or batch them separately)")
    n_max = max(len(ds.y) for ds in nodes)

    def pad(a):
        if a.shape[0] == n_max:
            return a
        fill = np.zeros((n_max - a.shape[0],) + a.shape[1:], a.dtype)
        return np.concatenate([a, fill])

    return ClientData(
        x=jnp.asarray(np.stack([pad(np.asarray(ds.x)) for ds in nodes])),
        y=jnp.asarray(np.stack([pad(np.asarray(ds.y)) for ds in nodes])),
        sizes=jnp.asarray([len(ds.y) for ds in nodes], jnp.int32),
        tau=taus[0],
        batch_size=batch_size,
    )


def select_clients(key, num_clients: int, k: int) -> jax.Array:
    """(k,) i32 population slots for this round's cohort.

    Full participation (k >= num_clients) is the deterministic identity —
    matching the host server's old behaviour bit-for-bit; a strict subset
    is drawn uniformly without replacement from the device RNG.
    """
    if k >= num_clients:
        return jnp.arange(num_clients, dtype=jnp.int32)
    return jax.random.permutation(key, num_clients)[:k].astype(jnp.int32)


def select_clients_avoiding(key, num_clients: int, k: int,
                            busy: jax.Array) -> jax.Array:
    """Subset selection that prefers clients with no report in flight.

    The buffered server must not re-select a busy client (its new report
    would collide with the buffered one in the Eq. 9 scatter), so busy
    clients sort strictly after every free one: uniform keys in [0, 1)
    get +1 where busy, and the k smallest win. Only when fewer than k
    clients are free do busy ones appear among the candidates — and the
    round's admission mask (`core.buffer`) filters those out. Full
    participation stays the deterministic identity (`select_clients`):
    every client is a candidate every tick; admission masks the busy ones.
    """
    if k >= num_clients:
        return jnp.arange(num_clients, dtype=jnp.int32)
    u = jax.random.uniform(key, (num_clients,))
    u = jnp.where(busy, u + 1.0, u)
    return jnp.argsort(u)[:k].astype(jnp.int32)


def epoch_batches(key, data: ClientData, sel: jax.Array):
    """One epoch of shuffled minibatches per selected client, on device.

    Returns (xb, yb) with leaves (K, tau, B, ...) — the paper's
    tau = E*D_i/B with E=1, exactly what the numpy `_epoch_batcher`
    yielded, but drawn from the device RNG: per-client keys are folded
    from the GLOBAL population slot, so a client's stream depends only on
    (round key, client id), never on who else was selected. Ragged node
    sizes use a masked argsort (rows past sizes[c] get +inf and sort
    last), so padding is never sampled.
    """
    count = data.tau * data.batch_size
    n_max = data.x.shape[1]

    def one(c):
        k = jax.random.fold_in(key, c)
        u = jax.random.uniform(k, (n_max,))
        u = jnp.where(jnp.arange(n_max) < data.sizes[c], u, jnp.inf)
        idx = jnp.argsort(u)[:count]
        xb = data.x[c][idx].reshape(
            (data.tau, data.batch_size) + data.x.shape[2:])
        yb = data.y[c][idx].reshape(
            (data.tau, data.batch_size) + data.y.shape[2:])
        return xb, yb

    return jax.vmap(one)(sel)


def make_eval_fn(apply_fn: Callable, test_x, test_y,
                 chunk: int = 2048) -> Callable:
    """Device-side test accuracy: params -> f32 fraction correct.

    The test set is padded to a multiple of `chunk` with label -1 (argmax
    over real logits is never negative, so padding can't score) and
    scanned in chunks, bounding eval activation memory for conv models.
    """
    n = test_x.shape[0]
    chunk = min(chunk, n)
    m = -(-n // chunk)
    pad = m * chunk - n
    xs = jnp.concatenate(
        [jnp.asarray(test_x),
         jnp.zeros((pad,) + test_x.shape[1:], test_x.dtype)])
    ys = jnp.concatenate(
        [jnp.asarray(test_y, jnp.int32), jnp.full((pad,), -1, jnp.int32)])
    xs = xs.reshape((m, chunk) + test_x.shape[1:])
    ys = ys.reshape(m, chunk)

    def eval_fn(params):
        def body(tot, xy):
            xc, yc = xy
            pred = jnp.argmax(apply_fn(params, xc), axis=-1)
            return tot + jnp.sum((pred == yc).astype(jnp.int32)), None

        correct, _ = jax.lax.scan(body, jnp.int32(0), (xs, ys))
        return correct.astype(jnp.float32) / n

    return eval_fn


def make_step_fn(loss_fn: Callable, fl: fl_mod.FLConfig, data: ClientData,
                 *, eval_fn: Optional[Callable] = None,
                 angle_pred: Optional[Callable] = None,
                 mesh=None, arrival_fn: Optional[Callable] = None) -> Callable:
    """One fully device-resident federated round.

    step(state, eval_every) -> (state, metrics): split the state's RNG,
    select this round's cohort, draw each client's epoch batches, run the
    compiled round, and (when `eval_fn` is given) conditionally append
    `metrics["accuracy"]` (or every key of the dict `eval_fn` returns,
    "accuracy" among them) — evaluated only after rounds where
    round % eval_every == 0 post-increment (i.e. (r+1) % eval_every == 0),
    the named `EVAL_SENTINEL` (-1.0) otherwise, so the eval forward pass
    is skipped via `lax.cond` on non-eval rounds. `eval_every` is a
    traced i32 (0 disables eval without recompiling). Sinks and
    `scripts/flstat.py` mask the sentinel; host code must test
    `acc != EVAL_SENTINEL` rather than reinvent the fill value.

    The SAME function is the stepwise server's jitted step and the
    scanned driver's scan body — equivalence by construction.

    With `fl.aggregation == "buffered"` each step is one server TICK
    (see `fl._make_buffered_round`): subset selection avoids clients
    whose report is still in flight (`select_clients_avoiding` over
    `state.buf`), and `arrival_fn` (an explicit per-tick delay/dropout
    schedule, e.g. `core.server.fixed_arrival_schedule`) flows through
    to the round builder. Both are inert for sync configs.

    Selection and batching run under the `jax.named_scope` ``fl.select``
    and the eval under ``fl.eval``; the round's own phases carry the
    ``fl.*`` scopes `fl.make_round_fn` lists.
    """
    buffered = fl.aggregation == "buffered"
    round_fn = fl_mod.make_round_fn(loss_fn, fl, angle_pred=angle_pred,
                                    mesh=mesh, arrival_fn=arrival_fn)

    def step(state: fl_mod.RoundState, eval_every):
        with jax.named_scope("fl.select"):
            rng, k_sel, k_bat = jax.random.split(state.rng, 3)
            if buffered and fl.clients_per_round < fl.num_clients:
                busy = buffer_mod.population_busy(state.buf,
                                                  fl.num_clients)
                sel = select_clients_avoiding(k_sel, fl.num_clients,
                                              fl.clients_per_round, busy)
            else:
                sel = select_clients(k_sel, fl.num_clients,
                                     fl.clients_per_round)
            batches = epoch_batches(k_bat, data, sel)
            sizes = data.sizes[sel].astype(jnp.float32)
        state, metrics = round_fn(state._replace(rng=rng), batches, sel,
                                  sizes)
        if eval_fn is not None:
            with jax.named_scope("fl.eval"):
                do_eval = (eval_every > 0) & (state.round % eval_every == 0)
                out = jax.eval_shape(eval_fn, state.params)
                if isinstance(out, dict):  # accuracy and more readings
                    res = jax.lax.cond(
                        do_eval, eval_fn,
                        lambda p: {k: jnp.full(v.shape, EVAL_SENTINEL,
                                               v.dtype)
                                   for k, v in out.items()},
                        state.params)
                    return state, dict(metrics, **res)
                acc = jax.lax.cond(do_eval, eval_fn,
                                   lambda p: jnp.float32(EVAL_SENTINEL),
                                   state.params)
            metrics = dict(metrics, accuracy=acc)
        return state, metrics

    return step


def make_scan_runner(step_fn: Callable, donate: Optional[bool] = None):
    """jit-compiled `lax.scan` of `step_fn` over a static block length.

    run_block(state, eval_every, length=E) -> (state, stacked metrics).
    The RoundState carry is donated (params/EF buffers update in place)
    on backends that implement donation; CPU XLA does not, so donation
    defaults off there to avoid per-call warnings.
    """
    if donate is None:
        donate = jax.default_backend() != "cpu"

    def run_block(state, eval_every, length):
        def body(s, _):
            return step_fn(s, eval_every)

        return jax.lax.scan(body, state, length=length)

    kw = {"static_argnames": ("length",)}
    if donate:
        kw["donate_argnums"] = (0,)
    return jax.jit(run_block, **kw)


def run_rounds(run_block: Callable, state: fl_mod.RoundState, rounds: int,
               *, eval_every: int = 1, target_acc: Optional[float] = None,
               block: int = 8, ckpt_dir: Optional[str] = None,
               ckpt_every_blocks: int = 1, ckpt_keep: int = 3,
               sink=None, telemetry_every: int = 1,
               spans: Optional[tel_spans.SpanTimer] = None):
    """Chunked scan over rounds with host-side early exit and optional
    block-boundary checkpointing.

    Scans `block` rounds per dispatch (one compile per distinct block
    length — at most two: the block and the final remainder); between
    blocks the host checks the in-scan eval accuracies against
    `target_acc`. Table-I semantics are preserved: rounds_to_target is
    the exact (r+1) of the first eval round at or above the target, even
    though the device may have run to the end of that block. Rounds are
    counted GLOBALLY from `state.round` — a state restored from a
    checkpoint at round R resumes at R, its eval cadence stays phased on
    the absolute round index, and rounds_to_target reports the same
    number the uninterrupted run would.

    `ckpt_dir` snapshots the FULL RoundState (fl.state_to_tree ->
    checkpoint.io.save_checkpoint: atomic write + `latest` pointer,
    newest `ckpt_keep` archives retained) after every
    `ckpt_every_blocks`-th block and always at exit, so a killed run
    loses at most `ckpt_every_blocks * block` rounds and restores
    bit-exactly (fl.state_from_tree) at a block boundary.

    `sink` (a `telemetry.sinks.TelemetrySink`) receives schema events at
    every scan-block boundary — one ``round`` event per round run (the
    final partial block is exact-length, never padded, so no de-padding
    ambiguity reaches the stream) plus per-node rows when the config's
    `telemetry="node"` metrics are present; `telemetry_every` subsamples
    the emitted rounds. `spans` (a `telemetry.spans.SpanTimer`; one is
    created over `sink` when omitted) bounds the read of the starting
    round counter as ``read_round``, each block as a ``scan_block`` span
    holding its ``dispatch`` and the ``fetch`` (device_get) of its
    metrics, checkpoint writes as ``checkpoint``, and event emission as
    ``sink_emit`` — the wall-clock-per-round numbers flstat reports come
    from these.

    Returns (state, metrics, rounds_to_target, rounds_run) where metrics
    holds per-round host arrays stacked over every round run THIS call
    (`rounds_run` counts the same; rounds_to_target is absolute).
    """
    if spans is None:
        spans = tel_spans.SpanTimer(sink)
    with spans.span("read_round"):
        base = int(jax.device_get(state.round))
    saved_at = None

    def checkpoint(round_now):
        nonlocal saved_at
        with spans.span("checkpoint", round=round_now):
            ckpt_io.save_checkpoint(ckpt_dir, round_now,
                                    fl_mod.state_to_tree(state),
                                    keep=ckpt_keep)
        saved_at = round_now

    blocks = []
    done = 0
    n_blocks = 0
    rounds_to_target = None
    while done < rounds and rounds_to_target is None:
        length = min(block, rounds - done)
        with spans.span("scan_block", round=base + done):
            with spans.span("dispatch"):
                state, ms = run_block(state, jnp.int32(eval_every),
                                      length=length)
            with spans.span("fetch"):
                ms = jax.device_get(ms)
        blocks.append(ms)
        if sink is not None:
            with spans.span("sink_emit", round=base + done):
                tel_sinks.emit_round_block(sink, ms, base + done,
                                           every=telemetry_every)
        if target_acc is not None and "accuracy" in ms:
            hit = np.flatnonzero(np.asarray(ms["accuracy"]) >= target_acc)
            if hit.size:
                rounds_to_target = base + done + int(hit[0]) + 1
        done += length
        n_blocks += 1
        if ckpt_dir is not None and n_blocks % ckpt_every_blocks == 0:
            checkpoint(base + done)
    if ckpt_dir is not None and saved_at != base + done:
        checkpoint(base + done)
    metrics = {
        k: np.concatenate([np.atleast_1d(np.asarray(m[k])) for m in blocks])
        for k in blocks[0]
    } if blocks else {}
    return state, metrics, rounds_to_target, done
