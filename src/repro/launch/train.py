"""Production federated-training launcher.

On a real TPU pod this runs the same compiled round the dry-run lowers,
over the production mesh; on this CPU container use --host-mesh with a
reduced (smoke) arch to execute end-to-end.

  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --host-mesh \
      --smoke --rounds 5
  # pod usage (unchanged code path):
  python -m repro.launch.train --arch gemma-2b --rounds 1000 [--multi-pod]
  # preemptible runs: --ckpt DIR [--ckpt-every N] snapshots the FULL
  # RoundState (params, angles, EF, RNG, round); --resume continues
  # bit-exactly from the latest snapshot:
  python -m repro.launch.train --arch gemma-2b --rounds 1000 \
      --ckpt /ckpts/run1 --ckpt-every 50 --resume
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--method", choices=["fedadp", "fedavg"], default="fedadp")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--host-mesh", action="store_true",
                    help="1-device mesh (CPU execution)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--stale", action="store_true")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint DIRECTORY: the full RoundState is "
                         "snapshotted there (atomic, `latest` pointer)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="also checkpoint every N rounds (0: only at end)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt; "
                         "training continues bit-exactly at the saved "
                         "round (--rounds is the TOTAL round budget)")
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="stream round/node/span telemetry to "
                         "DIR/telemetry.jsonl (repro.telemetry JSONL "
                         "schema; summarize with scripts/flstat.py). "
                         "Builds the step with FLConfig(telemetry='node') "
                         "— omit for the telemetry-free jaxpr")
    ap.add_argument("--telemetry-every", type=int, default=1, metavar="N",
                    help="emit round/node events only every N rounds "
                         "(spans and manifest always emit)")
    args = ap.parse_args()
    if args.resume and not args.ckpt:
        ap.error("--resume needs --ckpt (the directory to resume from)")
    if args.telemetry_every < 1:
        ap.error("--telemetry-every must be >= 1")

    import dataclasses
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.configs import registry, shapes as shapes_mod
    from repro.data import synthetic
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.models import transformer

    repro.use_compile_cache()

    name = args.arch + ("-smoke" if args.smoke else "")
    cfg = registry.get(name)
    mesh = make_host_mesh() if args.host_mesh else make_production_mesh(
        multi_pod=args.multi_pod)
    shape = shapes_mod.SHAPES["train_4k"]
    if args.seq or args.global_batch:
        shape = dataclasses.replace(
            shape, seq_len=args.seq or shape.seq_len,
            global_batch=args.global_batch or shape.global_batch,
        )

    fn, sds, in_shard, out_shard, meta = steps.build_train_step(
        cfg, mesh, shape, method=args.method, stale=args.stale,
        local_steps=args.tau,
        telemetry="node" if args.telemetry else None,
    )
    K, B, tau = meta["K"], meta["B"], meta["tau"]
    print(f"arch={cfg.name} mode={meta['fl_mode']} K={K} B={B} tau={tau} "
          f"T={shape.seq_len} mesh={dict(mesh.shape)}")

    from repro.checkpoint import io as ckpt_io
    from repro.telemetry import report as tel_report
    from repro.telemetry import sinks as tel_sinks
    from repro.telemetry import spans as tel_spans

    sink = None
    spans = tel_spans.SpanTimer()
    if args.telemetry:
        import os

        sink = tel_sinks.JSONLSink(os.path.join(args.telemetry,
                                                "telemetry.jsonl"))
        spans = tel_spans.SpanTimer(sink)

    with mesh:
        step = jax.jit(fn, in_shardings=in_shard, out_shardings=out_shard)
        # the exact config build_train_step lowered with — RoundState's
        # pytree structure is a function of it, so a hand-rebuilt copy
        # could silently diverge from the compiled signature
        flcfg = repro.FLConfig(**meta["flcfg"])
        start = 0
        if args.resume:
            loaded = ckpt_io.load_latest(args.ckpt)
            if loaded is None:
                raise SystemExit(f"--resume: no checkpoint in {args.ckpt}")
            step_no, tree = loaded
            state = repro.state_from_tree(flcfg, tree)
            start = int(state.round)
            print(f"resumed {args.ckpt} @ round {start} (ckpt_{step_no:08d})")
        else:
            params = transformer.init_params(jax.random.key(0), cfg)
            state = repro.init_round_state(flcfg, params)
        state = jax.device_put(state, in_shard[0])
        sel = jnp.arange(K, dtype=jnp.int32)
        sizes = jnp.ones((K,))
        if sink is not None:
            tel_sinks.emit_manifest(sink, flcfg,
                                    extra={"arch": cfg.name,
                                           "mesh": dict(mesh.shape),
                                           "start_round": start})

        def checkpoint(round_no: int) -> None:
            with spans.span("checkpoint", round=round_no):
                ckpt_io.save_checkpoint(args.ckpt, round_no,
                                        repro.state_to_tree(state))
            print(f"checkpoint -> {args.ckpt} @ round {round_no}")

        for r in range(start, args.rounds):
            # round-seeded synthetic batches: the stream a resumed run
            # sees at round r is identical to the uninterrupted run's
            toks = synthetic.lm_token_batches(
                seed=r, num_clients=K, batch=tau * B, seq=shape.seq_len,
                vocab=cfg.vocab_size,
            ).reshape(K, tau, B, shape.seq_len)
            batch = {"tokens": jnp.asarray(toks)}
            for k2, spec in sds[1].items():
                if k2 != "tokens":
                    batch[k2] = jnp.zeros(spec.shape, spec.dtype)
            t0 = time.time()
            with spans.span("round", round=r + 1):
                state, m = step(state, batch, sel, sizes)
                m = jax.device_get(m)
            print(f"round {r:4d} loss {float(m['loss']):.4f} "
                  f"div {float(m['divergence']):.3f} ({time.time()-t0:.1f}s)")
            if sink is not None:
                tel_sinks.emit_round_block(sink, m, r,
                                           every=args.telemetry_every)
            if (args.ckpt and args.ckpt_every
                    and (r + 1) % args.ckpt_every == 0):
                checkpoint(r + 1)
        if args.ckpt:
            checkpoint(int(jax.device_get(state.round)))
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(jax.device_get(state.params)):
            h.update(np.ascontiguousarray(leaf).tobytes())
        print("params_sha256", h.hexdigest())
        if sink is not None:
            tel_sinks.emit_summary(sink, rounds=args.rounds - start)
            sink.close()
            s = tel_report.summarize(tel_sinks.load_events(sink.path))
            print(f"telemetry -> {sink.path}")
            print(tel_report.oneline(s))


if __name__ == "__main__":
    main()
