"""Smoke test: the FedAdp flat-engine round on a TPU, through `FedServer`.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the sharded flat engine

Phase A runs the paper's deployment (`examples/fedadp_noniid.py`): the
1,663,370-parameter CNN on 10 nodes (5 IID + 5 one-class, 600 samples
each), full participation, batch 32, tau = 18, FedAdp with alpha = 5.
For the uplink/downlink wire pairs f32/f32, int8/int8 and int4/int8 it
runs 4 rounds with engine="flat" and 4 with engine="tree", each once
stepwise and once scanned (block=2, the donated-carry driver). Phase B
runs the `mlr` task in the same deployment, f32/f32, flat against tree:
K*N sits below the kernels' size threshold, so flat takes the XLA
fallback there.

`--chips 4` runs only the client-sharded engine: phase A's CNN with
f32/f32 and int8/int8 as engine="flat_sharded" on a (4,) "data" mesh
(K=10 over 4 shards, so the client axis is padded) and on a (2, 2)
"data" x "model" mesh, each against engine="tree" on the same mesh. On
the (4,) mesh it also feeds one fixed delta buffer at the CNN's width
through flat_sharded's aggregation region and holds it to a float64
reference, so aggregation is checked with local training taken out.

The script fails (non-zero exit, no "ok" line) unless every loss is
finite, flat matches tree and scanned matches stepwise after round 1,
test accuracy after round 4 agrees across a wire pair's runs, and the
compiled flat step holds a Mosaic kernel (`tpu_custom_call`). The
figures it prints are smoke figures, not benchmark figures. It needs a
TPU backend: on any other it exits non-zero and never falls back.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# After round 1 the runs compared here share params, data and selection,
# so they may differ only by f32 reduction order: the 1e-5 that the CPU
# tests hold for every wire pair (tests/test_transport.py).
TOL = 1e-5
# On the (4,) mesh, where K=10 clients do not divide over 4 shards,
# flat_sharded's row sharding propagates into local training, and its
# round-1 losses already differ from tree's (1.4e-6 relative on v5e)
# before any aggregation; FedAdp's angles amplify that delta noise. So
# end to end on that mesh the comparison allows the measured spread
# (params within 2.8x and per-client stats within 71x the 1e-5 bound on
# v5e), while the aggregation itself is held to TOL on fixed deltas
# (`Smoke.aggregation`). The (2, 2) mesh splits the clients evenly and
# matches at TOL end to end.
TOL_PARTITIONED = {"params": 1e-4, "stats": 3e-3}
# Test accuracy after round 4 over 3,000 images: 1e-5 agreement of the
# trajectories leaves at most a few borderline images flipped.
ACC_BOUND = 0.01
ROUNDS = 4
NODES = [("iid", None)] * 5 + [("xclass", 1)] * 5


def log(msg: str) -> None:
    print(msg, flush=True)


def max_rel_diff(a, b, tol: float) -> float:
    """Largest |a - b| / (tol + tol |b|) over all leaves: <= 1 passes
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol)."""
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        err = np.abs(x - y) / (tol + tol * np.abs(y))
        worst = max(worst, float(np.max(err)))
    return worst


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


class Smoke:
    def __init__(self, repro, task, nodes):
        self.repro, self.task, self.nodes = repro, task, nodes
        self.failures: list[str] = []

    def check(self, ok: bool, what: str) -> None:
        log(f"  check {'PASS' if ok else 'FAIL'}: {what}")
        if not ok:
            self.failures.append(what)

    def server(self, model, *, engine, uplink="f32", downlink="f32",
               mesh=None, batch_size=32, lr=0.02):
        tau = 600 // batch_size
        cfg = self.repro.FLConfig(
            num_clients=10, clients_per_round=10, local_steps=tau,
            method="fedadp", alpha=5.0, base_lr=lr, engine=engine,
            transport=uplink, downlink=downlink).validate()
        train, test = self.task
        return self.repro.FedServer(model, cfg, self.nodes, test,
                                    batch_size=batch_size, seed=0, mesh=mesh)

    def stepwise(self, name, srv, *, kernel_expected):
        """4 stepwise rounds. Returns round-1 params and stats, and the
        round-4 accuracy; prints compile and steady round seconds."""
        times, losses, first = [], [], None
        for r in range(ROUNDS):
            t0 = time.perf_counter()
            m = srv.step(eval_every=ROUNDS)
            times.append(time.perf_counter() - t0)
            losses.append(float(m["loss"]))
            if r == 0:
                first = (jax.device_get(srv.params),
                         {k: np.asarray(m[k]) for k in ("loss",
                                                        "theta_smoothed",
                                                        "weights")})
        acc = float(m["accuracy"])
        steady = statistics.median(times[1:])
        log(f"  [{name}] stepwise: losses {losses} acc@{ROUNDS} {acc:.4f} "
            f"compile_s {times[0] - steady:.2f} steady_round_s {steady:.4f} "
            f"peak_device_bytes {peak_bytes()} (smoke figures)")
        self.check(all(np.isfinite(losses)), f"{name}: finite losses")
        if kernel_expected is not None:
            t0 = time.perf_counter()
            hlo = srv._step_jit.lower(srv.state, jnp.int32(ROUNDS)) \
                .compile().as_text()
            has = "tpu_custom_call" in hlo
            log(f"  [{name}] step HLO tpu_custom_call={has} "
                f"(lower+compile {time.perf_counter() - t0:.2f}s)")
            if kernel_expected:
                self.check(has, f"{name}: Mosaic kernel in the compiled step")
        return first, acc

    def scanned(self, name, srv):
        """Round 1 as a 1-round block, then rounds 2-4 in blocks of 2;
        the carry is donated on every call."""
        srv.reset()
        t0 = time.perf_counter()
        h1 = srv.run(1, eval_every=ROUNDS, mode="scanned", block=2)
        t1 = time.perf_counter() - t0
        first = (jax.device_get(srv.params),
                 {"loss": h1.loss[0], "theta_smoothed": h1.thetas[0],
                  "weights": h1.weights[0]})
        t0 = time.perf_counter()
        h2 = srv.run(ROUNDS - 1, eval_every=ROUNDS, mode="scanned", block=2)
        t2 = time.perf_counter() - t0
        losses = h1.loss + h2.loss
        acc = h2.accuracy[-1]
        log(f"  [{name}] scanned: losses {losses} acc@{ROUNDS} {acc:.4f} "
            f"first_block_s {t1:.2f} rest_s {t2:.2f} "
            f"peak_device_bytes {peak_bytes()} (smoke figures)")
        self.check(all(np.isfinite(losses)), f"{name}: finite losses")
        return first, acc

    def same(self, what, a, b, tol=None):
        """Round-1 params and per-client stats (with the round's loss)
        agree to rtol=atol=tol[...] (default TOL for both)."""
        tol = tol or {"params": TOL, "stats": TOL}
        pa, sa = a
        pb, sb = b
        dp = max_rel_diff(pa, pb, tol["params"])
        ds = max(max_rel_diff(sa[k], sb[k], tol["stats"]) for k in sa)
        log(f"  {what}: params max_rel_diff {dp:.3g} at {tol['params']}, "
            f"per-client stats and loss max_rel_diff {ds:.3g} at "
            f"{tol['stats']} (<= 1 passes)")
        self.check(dp <= 1 and ds <= 1, f"{what} after round 1")

    def aggregation(self, what, mesh, up, n, k=10, alpha=5.0):
        """flat_sharded's aggregation with local training taken out.

        One fixed (k, n) delta buffer on `up`'s wire goes through the
        region the round runs (`make_round_ops`, client rows zero-padded
        to the mesh as `core.fl` pads them) and through a float64 NumPy
        evaluation of Eqs. 1, 8, 9 and 11 on the same dequantized wire.
        Round 1: no smoothing history, equal data sizes."""
        from repro import transport
        from repro.core import fl_shard_map, weighting

        kb, kx = jax.random.split(jax.random.key(7))
        spread = jnp.linspace(0.5, 3.0, k)[:, None]  # a range of angles
        x = 1e-3 * (jax.random.normal(kb, (n,))
                    + spread * jax.random.normal(kx, (k, n)))
        if up == "f32":
            wire, ref = (x, None), x
        else:
            q = transport.quantize(x, up)
            wire, ref = (q.values, q.scales), transport.dequantize(q)
        caxis = fl_shard_map.client_axis_size(mesh)
        kp = -(-k // caxis) * caxis

        def pad(a, fill=0.0):
            return jnp.pad(a, [(0, kp - k)] + [(0, 0)] * (a.ndim - 1),
                           constant_values=fill)

        rows = fl_shard_map.flat_client_sharding(mesh)
        wire = tuple(a if a is None else jax.device_put(pad(a, fill), rows)
                     for a, fill in zip(wire, (0.0, 1.0)))
        sizes = jnp.full((k,), 600.0, jnp.float32)
        ops = fl_shard_map.make_round_ops(
            mesh, weigh=lambda t, d: weighting.fedadp_weights(t, d, alpha),
            transport=up)
        g, dots, sqs, sqg, delta, theta, _, w = jax.jit(ops)(
            *wire, jnp.ones((n,), jnp.float32), pad(sizes / jnp.sum(sizes)),
            jnp.zeros((kp,), jnp.float32), jnp.zeros((kp,), jnp.int32),
            pad(sizes))

        xr = np.asarray(ref, np.float64)
        s = np.asarray(sizes, np.float64)
        g64 = (s / s.sum()) @ xr
        dots64, sqs64, sqg64 = xr @ g64, np.sum(xr * xr, axis=1), g64 @ g64
        theta64 = np.arccos(dots64 / np.sqrt(sqs64 * sqg64))
        logits = alpha * (1.0 - np.exp(-np.exp(-alpha * (theta64 - 1.0))))
        e = np.exp(logits + np.log(s) - np.max(logits + np.log(s)))
        w64 = e / e.sum()
        got = {"global_delta": g, "dots": dots[:k], "sqnorms": sqs[:k],
               "sqg": sqg, "theta": theta[:k], "weights": w[:k],
               "round_delta": delta}
        want = {"global_delta": g64, "dots": dots64, "sqnorms": sqs64,
                "sqg": sqg64, "theta": theta64, "weights": w64,
                "round_delta": w64 @ xr}
        diffs = {key: max_rel_diff(got[key], want[key], TOL) for key in got}
        log(f"  {what} (fixed deltas, rows {k} -> {kp}) vs float64: "
            + ", ".join(f"{key} {d:.3g}" for key, d in diffs.items())
            + f" at {TOL} (<= 1 passes)")
        self.check(max(diffs.values()) <= 1, f"{what} vs float64")

    def accuracies(self, what, accs):
        spread = max(accs) - min(accs)
        log(f"  {what}: acc@{ROUNDS} {accs} spread {spread:.4f}")
        self.check(spread <= ACC_BOUND,
                   f"{what}: accuracy spread <= {ACC_BOUND}")


def one_chip(sm: Smoke) -> None:
    log("phase A: cnn, 10 nodes (5 IID + 5 one-class), tau=18, fedadp")
    for up, down in (("f32", "f32"), ("int8", "int8"), ("int4", "int8")):
        pair = f"{up}/{down}"
        runs, accs = {}, []
        for engine in ("flat", "tree"):
            srv = sm.server("cnn", engine=engine, uplink=up, downlink=down)
            name = f"cnn {pair} {engine}"
            runs[engine, "stepwise"], acc = sm.stepwise(
                name, srv, kernel_expected=True if engine == "flat" else None)
            accs.append(acc)
            runs[engine, "scanned"], acc = sm.scanned(name, srv)
            accs.append(acc)
            sm.same(f"cnn {pair} {engine}: scanned == stepwise",
                    runs[engine, "scanned"], runs[engine, "stepwise"])
        sm.same(f"cnn {pair}: flat == tree", runs["flat", "stepwise"],
                runs["tree", "stepwise"])
        sm.accuracies(f"cnn {pair} (flat/tree x stepwise/scanned)", accs)

    log("phase B: mlr, same deployment, f32/f32 (XLA fallback below "
        "the kernel size threshold)")
    runs, accs = {}, []
    for engine in ("flat", "tree"):
        srv = sm.server("mlr", engine=engine, batch_size=50, lr=0.05)
        runs[engine], acc = sm.stepwise(
            f"mlr f32/f32 {engine}", srv,
            kernel_expected=False if engine == "flat" else None)
        accs.append(acc)
    sm.same("mlr f32/f32: flat == tree", runs["flat"], runs["tree"])
    sm.accuracies("mlr f32/f32 (flat/tree)", accs)


def four_chips(sm: Smoke) -> None:
    log("four chips: cnn, K=10, flat_sharded against tree on each mesh")
    for shape, axes in (((4,), ("data",)), ((2, 2), ("data", "model"))):
        mesh = sm.repro.make_mesh(shape, axes)
        for up, down in (("f32", "f32"), ("int8", "int8")):
            tag = f"cnn {up}/{down} mesh {dict(zip(axes, shape))}"
            runs, accs = {}, []
            for engine in ("flat_sharded", "tree"):
                srv = sm.server("cnn", engine=engine, uplink=up,
                                downlink=down, mesh=mesh)
                runs[engine], acc = sm.stepwise(
                    f"{tag} {engine}", srv,
                    kernel_expected=True if engine != "tree" else None)
                accs.append(acc)
            sm.same(f"{tag}: flat_sharded == tree", runs["flat_sharded"],
                    runs["tree"],
                    tol=TOL_PARTITIONED if len(shape) == 1 else None)
            sm.accuracies(f"{tag} (flat_sharded/tree)", accs)
            if len(shape) == 1:
                n = sum(p.size for p in jax.tree.leaves(runs["tree"][0]))
                sm.aggregation(f"{tag}: flat_sharded aggregation", mesh, up,
                               n)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU backend, found {backend!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    import repro
    from repro.data import synthetic

    log(f"compile cache: {repro.use_compile_cache()}")
    log(f"device: {devices[0].device_kind} x{len(devices)}")
    t0 = time.perf_counter()
    task = synthetic.make_image_task(seed=0, num_train=20000, num_test=3000)
    nodes = synthetic.make_federated(task[0], NODES, samples_per_node=600,
                                     seed=1)
    sm = Smoke(repro, task, nodes)
    (four_chips if args.chips == 4 else one_chip)(sm)
    log(f"total {time.perf_counter() - t0:.1f}s")
    if sm.failures:
        print("chip_smoke FAILED:\n  " + "\n  ".join(sm.failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
