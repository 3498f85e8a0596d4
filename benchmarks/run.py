"""Benchmark driver — one function per paper table/figure, plus kernel
micro-benchmarks and the roofline post-processor.

Prints ``name,us_per_call,derived`` CSV lines. `us_per_call` is the wall
time per federated round (or per kernel call); `derived` is the
table/figure quantity (rounds-to-target, accuracy, divergence ratio, ...).

Usage:  PYTHONPATH=src python -m benchmarks.run [--full] [--tiny] [--only NAME]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import repro
from benchmarks.common import emit, get_task, node_spec, run_fl


def table1_rounds(full: bool = False) -> None:
    """Paper Table I: rounds to target accuracy, FedAdp vs FedAvg, per
    heterogeneity setting (x-class non-IID)."""
    settings = [("5iid+5non1", node_spec(5, 5, 1)), ("3iid+7non2", node_spec(3, 7, 2))]
    if full:
        settings += [
            ("3iid+7non1", node_spec(3, 7, 1)),
            ("6iid+4non1", node_spec(6, 4, 1)),
            ("5iid+5non2", node_spec(5, 5, 2)),
            ("6iid+4non2", node_spec(6, 4, 2)),
        ]
    rounds = 120 if full else 60
    for name, spec in settings:
        per = {}
        for method in ("fedavg", "fedadp"):
            hist, spr = run_fl(method, spec, rounds=rounds, target=0.85)
            r = hist.rounds_to_target or f">{rounds}"
            per[method] = r
            emit(f"table1/{name}/{method}", spr * 1e6, r)
        if isinstance(per["fedadp"], int) and isinstance(per["fedavg"], int):
            red = 100.0 * (1 - per["fedadp"] / per["fedavg"])
            emit(f"table1/{name}/reduction_pct", 0.0, f"{red:.1f}")


def fig1_noniid_impact(full: bool = False) -> None:
    """Paper Fig. 1: non-IID participation slows FedAvg convergence."""
    for name, spec in [
        ("10iid", node_spec(10, 0, 1)),
        ("5iid+5non1", node_spec(5, 5, 1)),
        ("3iid+7non1", node_spec(3, 7, 1)),
        ("3iid+7non2", node_spec(3, 7, 2)),
    ]:
        hist, spr = run_fl("fedavg", spec, rounds=30, target=None)
        emit(f"fig1/fedavg/{name}/acc@30", spr * 1e6, f"{hist.final_accuracy:.4f}")


def fig5_general_heterogeneity(full: bool = False) -> None:
    """Paper Fig. 5: general (random x_i) heterogeneity, no pure-IID nodes."""
    rng = np.random.default_rng(0)
    case1 = [("xclass", int(x)) for x in rng.permutation(np.arange(1, 11))]
    lo = [("xclass", int(x)) for x in rng.integers(1, 6, 5)]
    hi = [("xclass", int(x)) for x in rng.integers(6, 11, 5)]
    for cname, spec in [("case1", case1), ("case2", lo + hi)]:
        for method in ("fedavg", "fedadp"):
            hist, spr = run_fl(method, spec, rounds=40, target=None)
            emit(
                f"fig5/{cname}/{method}/acc@40",
                spr * 1e6,
                f"{hist.final_accuracy:.4f}",
            )


def fig6_alpha_sweep(full: bool = False) -> None:
    """Paper Fig. 6: effect of the Gompertz alpha (best ~5)."""
    alphas = (1, 2, 5, 7, 10) if full else (2, 5, 10)
    for alpha in alphas:
        hist, spr = run_fl(
            "fedadp",
            node_spec(5, 5, 1),
            rounds=30,
            target=None,
            alpha=float(alpha),
        )
        emit(f"fig6/alpha={alpha}/acc@30", spr * 1e6, f"{hist.final_accuracy:.4f}")


def fig7_divergence(full: bool = False) -> None:
    """Paper Fig. 7: FedAdp shrinks cross-client gradient divergence."""
    div = {}
    for method in ("fedavg", "fedadp"):
        hist, spr = run_fl(method, node_spec(5, 5, 1), rounds=25, target=None)
        div[method] = float(np.mean(hist.divergence[5:]))
        emit(f"fig7/{method}/divergence", spr * 1e6, f"{div[method]:.4f}")
    emit("fig7/ratio_adp_over_avg", 0.0, f"{div['fedadp']/div['fedavg']:.3f}")


def method_ablation(full: bool = False) -> None:
    """Beyond-paper ablation: FedAvg vs FedProx (mu=0.1) vs FedAdp on the
    5 IID + 5 one-class split (rounds to 85%)."""
    from repro.data import synthetic

    train, test = get_task()
    nodes = synthetic.make_federated(
        train, node_spec(5, 5, 1), samples_per_node=600, seed=1
    )
    rounds = 120 if full else 60
    for method, mu in (("fedavg", 0.0), ("fedprox", 0.1), ("fedadp", 0.0)):
        cfg = repro.FLConfig(
            num_clients=10,
            clients_per_round=10,
            local_steps=12,
            method=method,
            prox_mu=mu,
            base_lr=0.05,
        )
        server = repro.FedServer("mlr", cfg, nodes, test, batch_size=50, seed=0)
        import time as _t

        t0 = _t.time()
        hist = server.run(rounds, target_acc=0.85, eval_every=2)
        spr = (_t.time() - t0) / max(len(hist.loss), 1)
        emit(
            f"ablation/{method}/rounds_to_85",
            spr * 1e6,
            hist.rounds_to_target or f">{rounds}",
        )


def kernel_micro(full: bool = False, tiny: bool = False) -> None:
    """Pallas kernels (interpret mode) vs XLA reference on identical inputs.

    Interpret-mode timing is NOT TPU performance — the roofline analysis in
    EXPERIMENTS.md covers the TPU projection; this records correctness-path
    cost and the ref/XLA baseline. `tiny` uses the smallest buffer that
    still takes the kernel path (K * N = SMALL_ELEMS)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import grad_dot, ref, round_stats, weighted_agg

    n = 1 << 17 if tiny else (1 << 22 if full else 1 << 20)
    a = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (n,), jnp.float32)
    x = jax.random.normal(jax.random.key(2), (8, n // 8), jnp.float32)
    w = jax.random.uniform(jax.random.key(3), (8,))

    def timeit(fn, *args):
        fn(*args)  # compile
        t0 = time.time()
        for _ in range(3):
            jax.block_until_ready(fn(*args))
        return (time.time() - t0) / 3 * 1e6

    emit(
        "kernel/grad_dot/pallas_interp",
        timeit(grad_dot.grad_dot_stats, a, b),
        f"n={n}",
    )
    emit(
        "kernel/grad_dot/xla_ref",
        timeit(jax.jit(ref.grad_dot_stats), a, b),
        f"n={n}",
    )
    emit(
        "kernel/weighted_agg/pallas_interp",
        timeit(weighted_agg.weighted_agg, w, x),
        f"shape={x.shape}",
    )
    emit(
        "kernel/weighted_agg/xla_ref",
        timeit(jax.jit(ref.weighted_agg), w, x),
        f"shape={x.shape}",
    )
    g = jax.random.normal(jax.random.key(4), (n // 8,), jnp.float32)
    emit(
        "kernel/round_stats/pallas_interp",
        timeit(round_stats.round_stats, x, g),
        f"shape={x.shape}",
    )
    emit(
        "kernel/round_stats/xla_ref",
        timeit(jax.jit(ref.round_stats), x, g),
        f"shape={x.shape}",
    )


def engine_ab(full: bool = False, tiny: bool = False) -> None:
    """Tree vs flat round-engine A/B across a K sweep, plus the
    client-sharded flat engine when more than one device is visible and a
    2D (client x model) mesh sweep when at least 4 are.

    Sweeps K in {8, 32, 64, 128} (chunked kernels: K > 32 used to be a
    trace-time error), times each engine per round, and writes the sweep
    to BENCH_engine.json for the CI bench-smoke artifact: per-record
    measured µs next to the model-bytes HBM-bound floor
    (benchmarks.roofline.flat_round_hbm_bound_us), per-K flat/tree
    ratios, and the K=8 small-d acceptance flag (flat <= 1.2x tree at
    K=8, d=1024 — the cliff the min-elems XLA fallback removes). `tiny`
    shrinks shapes for the interpret-mode CI smoke job.

    On CPU the flat path runs the Pallas kernels in interpret mode, so
    every measured number here is the CORRECTNESS path (labelled "mode":
    "interpret-correctness-path" in the records), not a TPU projection —
    the hbm_bound_us column is the projection."""
    import json

    import jax
    import jax.numpy as jnp

    from benchmarks.roofline import flat_round_hbm_bound_us

    ks = (4, 8) if tiny else (8, 32, 64, 128)
    d = 1 << 10 if tiny else (1 << 16 if full else 1 << 14)
    tau, B = 2, 4
    engines = ["tree", "flat"]
    mesh = None
    if jax.device_count() > 1:
        mesh = repro.make_mesh((jax.device_count(),), ("data",))
        engines.append("flat_sharded")
    mode = (
        "interpret-correctness-path"
        if jax.default_backend() == "cpu"
        else jax.default_backend()
    )
    rng = np.random.default_rng(0)
    params = {"w": jnp.zeros((d, 1), jnp.float32), "b": jnp.zeros((1,), jnp.float32)}
    n_flat = d + 1

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    def time_round(cfg, m, params, loss, args):
        rf = jax.jit(repro.make_round_fn(loss, cfg, mesh=m))
        full_args = (repro.init_round_state(cfg, params),) + args
        jax.block_until_ready(rf(*full_args))  # compile
        t0 = time.time()
        reps = 5
        for _ in range(reps):
            jax.block_until_ready(rf(*full_args))
        return (time.time() - t0) / reps * 1e6

    records = []
    ratios = {}
    for K in ks:
        X = jnp.asarray(rng.normal(size=(K, tau, B, d)).astype(np.float32))
        Y = jnp.asarray(rng.normal(size=(K, tau, B, 1)).astype(np.float32))
        sel = jnp.arange(K, dtype=jnp.int32)
        sizes = jnp.ones((K,), jnp.float32)
        us = {}
        for engine in engines:
            if engine == "flat_sharded" and K % jax.device_count():
                continue
            cfg = repro.FLConfig(
                num_clients=K,
                clients_per_round=K,
                local_steps=tau,
                method="fedadp",
                engine=engine,
                base_lr=0.05,
            )
            devs = jax.device_count() if engine == "flat_sharded" else 1
            us[engine] = time_round(cfg, mesh, params, loss_fn, ((X, Y), sel, sizes))
            emit(f"engine_ab/K={K}/{engine}/round", us[engine], f"d={d}")
            records.append(
                {
                    "K": K,
                    "d": d,
                    "engine": engine,
                    "mode": mode,
                    "us_per_round": us[engine],
                    "hbm_bound_us": flat_round_hbm_bound_us(K, n_flat, devices=devs),
                }
            )
        ratios[str(K)] = us["flat"] / us["tree"]
        emit(f"engine_ab/K={K}/flat_over_tree", 0.0, f"{ratios[str(K)]:.3f}")

    # ---- 2D (client x model) mesh sweep: flat vs tree on the same mesh --
    mesh2d_records = []
    dc = jax.device_count()
    if dc >= 4 and dc % 2 == 0:
        d_in, h = max(d // 8, 8), 8
        params2 = {
            "wq": jnp.zeros((d_in, h), jnp.float32),
            "w_down": jnp.zeros((h, 1), jnp.float32),
            "b": jnp.zeros((1,), jnp.float32),
        }
        n2 = d_in * h + h + 1

        def loss2(p, batch):
            x, y = batch
            return jnp.mean(((x @ p["wq"]) @ p["w_down"] + p["b"] - y) ** 2)

        K2 = 8
        X2 = jnp.asarray(rng.normal(size=(K2, tau, B, d_in)).astype(np.float32))
        Y2 = jnp.asarray(rng.normal(size=(K2, tau, B, 1)).astype(np.float32))
        args2 = (
            (X2, Y2),
            jnp.arange(K2, dtype=jnp.int32),
            jnp.ones((K2,), jnp.float32),
        )
        for cdim in sorted({2, dc // 2}):
            mdim = dc // cdim
            m2 = repro.make_mesh((cdim, mdim), ("data", "model"))
            hbm2 = flat_round_hbm_bound_us(K2, n2, devices=dc)
            with m2:
                for engine in ("tree", "flat_sharded"):
                    cfg = repro.FLConfig(
                        num_clients=K2,
                        clients_per_round=K2,
                        local_steps=tau,
                        method="fedadp",
                        engine=engine,
                        base_lr=0.05,
                    )
                    u = time_round(cfg, m2, params2, loss2, args2)
                    emit(f"engine_ab/mesh2d={cdim}x{mdim}/{engine}/round", u, f"n={n2}")
                    mesh2d_records.append(
                        {
                            "mesh": f"{cdim}x{mdim}",
                            "K": K2,
                            "n": n2,
                            "engine": engine,
                            "mode": mode,
                            "us_per_round": u,
                            "hbm_bound_us": hbm2,
                        }
                    )
    from repro.telemetry.manifest import run_manifest

    # acceptance: the K=8 small-d flat-engine cliff stays gone — flat is
    # within 1.2x of tree at K=8, d=1024 on the interpret path.
    k8_cliff_ok = None
    if d == (1 << 10) and "8" in ratios:
        k8_cliff_ok = bool(ratios["8"] <= 1.2)
    payload = {
        "bench": "engine_ab",
        "d": d,
        "tiny": tiny,
        "device_count": jax.device_count(),
        "mode": mode,
        "manifest": run_manifest(),
        "records": records,
        "flat_over_tree": ratios,
        "k8_cliff_ok": k8_cliff_ok,
        "mesh2d": mesh2d_records,
    }
    with open("BENCH_engine.json", "w") as f:
        json.dump(payload, f, indent=2)
    emit("engine_ab/json", 0.0, "BENCH_engine.json")


def transport_sweep(full: bool = False, tiny: bool = False) -> None:
    """Bidirectional wire A/B: (uplink, downlink) x K over the flat engine.

    For each uplink wire format (f32 / bf16 / int8 / int4) and K in
    {8, 32, 64, 128}, times a full federated round through
    `FLConfig(transport=...)` with the reference f32 downlink and reports
    BOTH directions of the wire (`transport.round_bytes`: bytes_up is the
    delta uplink incl. scale side data, bytes_down the model broadcast);
    a second sweep holds the uplink at int4 and walks the downlink
    formats (f32 / bf16 / int8) at the first K. A delta-downlink leg
    then runs a rotating-cohort SUBSET-selection round (per-client
    broadcast state, downlink_delta=True) and reports the ACTUAL
    delta-vs-full down-byte split from the tel/bytes_down_* metrics —
    the number the static broadcast figure over-states whenever clients
    resync. Everything lands in BENCH_transport.json for the CI
    bench-smoke artifact.

    Unless `tiny`, also pins convergence parity on the non-IID synthetic
    task (5 IID + 5 one-class nodes): rounds-to-target under the int8 and
    int4 uplinks AND under the fully-compressed int4+int8-downlink pair
    must stay within 10% of the f32 wire (the acceptance bound; the same
    matrix is pinned as a TEST in tests/test_golden_convergence.py).

    On CPU the kernels run in interpret mode, so us_per_round measures the
    correctness path; bytes are exact either way."""
    import json

    import jax
    import jax.numpy as jnp

    from repro import transport as transport_mod

    ks = (4, 8) if tiny else (8, 32, 64, 128)
    d = 1 << 10 if tiny else (1 << 16 if full else 1 << 14)
    tau, B = 2, 4
    n_params = d + 1  # w (d, 1) + b (1,)
    rng = np.random.default_rng(0)
    params = {"w": jnp.zeros((d, 1), jnp.float32), "b": jnp.zeros((1,), jnp.float32)}

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    def time_round(K, data, tr, dl):
        cfg = repro.FLConfig(
            num_clients=K,
            clients_per_round=K,
            local_steps=tau,
            method="fedadp",
            engine="flat",
            transport=tr,
            downlink=dl,
            base_lr=0.05,
        )
        rf = jax.jit(repro.make_round_fn(loss_fn, cfg))
        sel = jnp.arange(K, dtype=jnp.int32)
        sizes = jnp.ones((K,), jnp.float32)
        args = (repro.init_round_state(cfg, params), data, sel, sizes)
        jax.block_until_ready(rf(*args))  # compile
        t0 = time.time()
        reps = 5
        for _ in range(reps):
            jax.block_until_ready(rf(*args))
        return (time.time() - t0) / reps * 1e6

    records = []

    def record(K, data, tr, dl):
        us = time_round(K, data, tr, dl)
        rb = transport_mod.round_bytes(K, n_params, tr, dl)
        emit(
            f"transport/K={K}/{tr}/dl={dl}/round",
            us,
            f"up={rb['up']} down={rb['down']}",
        )
        records.append(
            {
                "K": K,
                "d": d,
                "transport": tr,
                "downlink": dl,
                "us_per_round": us,
                "bytes_up": rb["up"],
                "bytes_down": rb["down"],
                "bytes_per_round": rb["total"],
            }
        )
        return rb

    for K in ks:
        data = (
            jnp.asarray(rng.normal(size=(K, tau, B, d)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(K, tau, B, 1)).astype(np.float32)),
        )
        wb = {tr: record(K, data, tr, "f32")["up"] for tr in transport_mod.TRANSPORTS}
        emit(
            f"transport/K={K}/int8_bytes_over_f32",
            0.0,
            f"{wb['int8'] / wb['f32']:.4f}",
        )
        # acceptance: the int4 uplink moves ~0.125x the f32 bytes
        emit(
            f"transport/K={K}/int4_bytes_over_f32",
            0.0,
            f"{wb['int4'] / wb['f32']:.4f}",
        )
        if K == ks[0]:
            # downlink sweep at the smallest K: uplink held at int4, the
            # broadcast walked over every downlink format
            down = {
                dl: record(K, data, "int4", dl)["down"]
                for dl in transport_mod.DOWNLINKS
                if dl != "f32"
            }
            down["f32"] = transport_mod.round_bytes(K, n_params, "int4")["down"]
            emit(
                f"transport/K={K}/int8_down_over_f32_down",
                0.0,
                f"{down['int8'] / down['f32']:.4f}",
            )

    # delta-downlink byte split: a short SUBSET-selection run (half the
    # population per round) over the per-client broadcast state, with the
    # actual per-round delta-vs-full down bytes read back from the
    # tel/bytes_down_* metrics (resyncs pay a full quantized model; the
    # static round_bytes broadcast figure is only the degenerate
    # full-participation bound).
    K = ks[0]
    ksel = K // 2
    cfg = repro.FLConfig(
        num_clients=K,
        clients_per_round=ksel,
        local_steps=tau,
        method="fedadp",
        engine="flat",
        transport="int4",
        downlink="int8",
        downlink_delta=True,
        downlink_ring=2,
        base_lr=0.05,
        telemetry="node",
    )
    rf = jax.jit(repro.make_round_fn(loss_fn, cfg))
    data = (
        jnp.asarray(rng.normal(size=(ksel, tau, B, d)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(ksel, tau, B, 1)).astype(np.float32)),
    )
    sizes = jnp.ones((ksel,), jnp.float32)
    state = repro.init_round_state(cfg, params)
    delta_rounds, down_delta = [], 0.0
    down_full = 0.0
    T = 8
    for t in range(T):
        # rotate the cohort so clients fall behind and re-pull: the first
        # pass pays full-model resyncs (never-pulled clients), later
        # rounds pay multi-version delta catch-ups through the ring
        sel = jnp.asarray([(t * ksel + i) % K for i in range(ksel)], jnp.int32)
        state, metrics = rf(state, data, sel, sizes)
        dd = float(metrics["tel/bytes_down_delta"])
        df = float(metrics["tel/bytes_down_full"])
        down_delta += dd
        down_full += df
        delta_rounds.append(
            {"round": t, "bytes_down_delta": dd, "bytes_down_full": df}
        )
    static_down = transport_mod.round_bytes(ksel, n_params, "int4", "int8")["down"]
    emit(
        f"transport/delta_split/K={K}/sel={ksel}",
        0.0,
        f"delta={down_delta:.0f} full={down_full:.0f} "
        f"static_down={static_down * T}",
    )
    delta_split = {
        "K": K,
        "clients_per_round": ksel,
        "downlink_ring": 2,
        "transport": "int4",
        "downlink": "int8",
        "rounds": delta_rounds,
        "bytes_down_delta_total": down_delta,
        "bytes_down_full_total": down_full,
        "static_broadcast_down_total": static_down * T,
    }

    convergence = None
    if not tiny:
        rounds = 120 if full else 60
        per = {}
        for tr, dl in (
            ("f32", "f32"),
            ("int8", "f32"),
            ("int4", "f32"),
            ("int4", "int8"),
        ):
            hist, spr = run_fl(
                "fedadp",
                node_spec(5, 5, 1),
                rounds=rounds,
                target=0.85,
                engine="flat",
                transport=tr,
                downlink=dl,
            )
            name = tr if dl == "f32" else f"{tr}+dl_{dl}"
            per[name] = hist.rounds_to_target
            emit(
                f"transport/convergence/{name}/rounds_to_85",
                spr * 1e6,
                per[name] or f">{rounds}",
            )
        # a wire that never reached the target is a parity FAILURE, not a
        # skipped measurement — record it as such so the artifact can't be
        # mistaken for a --tiny run (where convergence stays null).
        ratios = {}
        for name in ("int8", "int4", "int4+dl_int8"):
            r = per[name] / per["f32"] if per["f32"] and per[name] else None
            ratios[name] = r
            emit(
                f"transport/convergence/{name}_over_f32",
                0.0,
                f"{r:.3f}" if r else "no-convergence",
            )
        convergence = {
            "rounds": per,
            "ratios": ratios,
            "within_10pct": all(r is not None and r <= 1.1 for r in ratios.values()),
        }

    from repro.telemetry.manifest import run_manifest

    payload = {
        "bench": "transport_sweep",
        "d": d,
        "n_params": n_params,
        "tiny": tiny,
        "transports": list(transport_mod.TRANSPORTS),
        "downlinks": list(transport_mod.DOWNLINKS),
        "manifest": run_manifest(),
        "records": records,
        "downlink_delta": delta_split,
        "convergence": convergence,
    }
    with open("BENCH_transport.json", "w") as f:
        json.dump(payload, f, indent=2)
    emit("transport/json", 0.0, "BENCH_transport.json")


def driver_ab(full: bool = False, tiny: bool = False) -> None:
    """Python-loop vs scanned round driver A/B across a K sweep.

    Both paths run the SAME compiled device-resident step (selection +
    batching + round + conditional eval from the device RNG); the
    python-loop path dispatches it once per round and `device_get`s the
    metrics each time (the pre-driver FedServer cadence), while the
    scanned path folds all R rounds into one `lax.scan` dispatch
    (`FedServer.run(mode="scanned")` with block=R). The gap is therefore pure
    dispatch/sync overhead — exactly what the device-resident driver
    exists to remove. Results land in BENCH_driver.json for the CI
    bench-smoke artifact; acceptance is scanned <= python-loop at every K.
    """
    import json

    from repro.data import synthetic

    ks = (4, 8) if tiny else (8, 32, 64, 128)
    samples, batch = (8, 4) if tiny else (100, 50)
    reps, R = (3, 8) if tiny else (5, 8)
    train, test = synthetic.make_image_task(
        seed=0, num_train=512 if tiny else 4000, num_test=128 if tiny else 512
    )
    records, ratios = [], {}
    for K in ks:
        nodes = synthetic.make_federated(
            train, [("iid", None)] * K, samples_per_node=samples, seed=1
        )
        cfg = repro.FLConfig(
            num_clients=K,
            clients_per_round=K,
            local_steps=samples // batch,
            method="fedadp",
            base_lr=0.05,
        )
        server = repro.FedServer("mlr", cfg, nodes, test, batch_size=batch, seed=0)

        def loop_path():
            for _ in range(R):
                server.step()

        def scan_path():
            server.run(R, eval_every=0, mode="scanned", block=R)

        server.step()  # compile the stepwise dispatch
        scan_path()  # compile the scan block
        # interleave the two paths' reps so slow machine-load drift hits
        # both equally (back-to-back rep blocks skew the ratio)
        loop_us, scan_us = _best_us_interleaved(loop_path, scan_path, reps)
        loop_us, scan_us = loop_us / R, scan_us / R

        emit(f"driver_ab/K={K}/python_loop/round", loop_us, f"R={R}")
        emit(f"driver_ab/K={K}/scanned/round", scan_us, f"R={R}")
        ratios[K] = scan_us / loop_us
        emit(f"driver_ab/K={K}/scanned_over_loop", 0.0, f"{ratios[K]:.3f}")
        records += [
            {"K": K, "path": "python_loop", "us_per_round": loop_us},
            {"K": K, "path": "scanned", "us_per_round": scan_us},
        ]
    from repro.telemetry.manifest import run_manifest

    payload = {
        "bench": "driver_ab",
        "tiny": tiny,
        "rounds_per_dispatch": R,
        "manifest": run_manifest(),
        "records": records,
        "scanned_over_loop": {str(k): v for k, v in ratios.items()},
        # the acceptance claim the artifact carries: the scanned driver is
        # never slower than the per-round dispatch loop
        "scanned_leq_loop_all_k": all(v <= 1.0 for v in ratios.values()),
    }
    with open("BENCH_driver.json", "w") as f:
        json.dump(payload, f, indent=2)
    emit("driver_ab/json", 0.0, "BENCH_driver.json")


def telemetry_bench(full: bool = False, tiny: bool = False) -> None:
    """Telemetry-layer end-to-end: a scanned fedadp run streamed to a
    JSONL sink, then summarized back by the flstat logic.

    Writes the stream itself as the artifact (BENCH_telemetry.jsonl —
    the CI bench-smoke job schema-validates it and asserts the softmax
    weight-sum invariant with scripts/flstat.py) and emits the
    acceptance claim: rounds-to-85% recomputed from the stream ALONE
    must agree with the in-process History. `tiny` shrinks the task for
    the CI smoke job (the target is usually not reached there — the
    claim then checks that both sides agree on "not reached")."""
    from repro.telemetry import report as tel_report
    from repro.telemetry.sinks import JSONLSink, load_events

    target = 0.85
    rounds = 10 if tiny else (120 if full else 60)
    spec = node_spec(2, 2, 1) if tiny else node_spec(5, 5, 1)
    sink = JSONLSink("BENCH_telemetry.jsonl")
    hist, spr = run_fl(
        "fedadp", spec, rounds=rounds, target=target, scan=True,
        samples=100 if tiny else 600, batch_size=25 if tiny else 50,
        telemetry="node", sink=sink,
    )
    sink.close()
    events = load_events(sink.path)
    s = tel_report.summarize(events, target=target)
    checked = tel_report.check_weight_sums(events)
    emit("telemetry/rounds_streamed", spr * 1e6, s["rounds"])
    emit("telemetry/weight_sum_rounds_ok", 0.0, checked)
    emit(
        "telemetry/rounds_to_85/flstat",
        0.0,
        s["rounds_to_target"] or f">{rounds}",
    )
    emit(
        "telemetry/rounds_to_85/agrees_with_history",
        0.0,
        s["rounds_to_target"] == hist.rounds_to_target,
    )
    emit("telemetry/jsonl", 0.0, "BENCH_telemetry.jsonl")


def _best_us_interleaved(fn_a, fn_b, reps: int):
    """Best-of-`reps` wall time of each fn in microseconds, reps
    interleaved a/b/a/b so load drift cannot bias the comparison."""
    best_a = best_b = float("inf")
    for _ in range(reps):
        t0 = time.time()
        fn_a()
        best_a = min(best_a, time.time() - t0)
        t0 = time.time()
        fn_b()
        best_b = min(best_b, time.time() - t0)
    return best_a * 1e6, best_b * 1e6


def roofline_table(full: bool = False) -> None:
    """Post-process results/dryrun.jsonl into roofline terms (if present)."""
    import json
    import os

    # prefer the loop-aware records (scoped analysis + perf-iteration tags)
    candidates = ("results/roofline.jsonl", "results/dryrun.jsonl")
    path = next((p for p in candidates if os.path.exists(p)), None)
    if path is None:
        emit("roofline/skipped", 0.0, "run repro.launch.dryrun --all first")
        return
    from benchmarks.roofline import load_records, roofline_rows

    rows = roofline_rows(load_records(path))
    for r in rows:
        emit(
            f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
            0.0,
            f"comp={r['t_compute']:.2e}s mem={r['t_memory']:.2e}s "
            f"coll={r['t_collective']:.2e}s dom={r['bottleneck']}",
        )


BENCHES = {
    "table1": table1_rounds,
    "fig1": fig1_noniid_impact,
    "fig5": fig5_general_heterogeneity,
    "fig6": fig6_alpha_sweep,
    "fig7": fig7_divergence,
    "ablation": method_ablation,
    "kernels": kernel_micro,
    "engine": engine_ab,
    "transport": transport_sweep,
    "driver": driver_ab,
    "telemetry": telemetry_bench,
    "roofline": roofline_table,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale settings (slow)")
    ap.add_argument("--tiny", action="store_true", help="CI smoke shapes")
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    args = ap.parse_args()
    repro.use_compile_cache()
    names = [args.only] if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        kwargs = {"full": args.full}
        if name in ("kernels", "engine", "transport", "driver", "telemetry"):
            kwargs["tiny"] = args.tiny
        BENCHES[name](**kwargs)


if __name__ == "__main__":
    main()
