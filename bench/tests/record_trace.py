"""Record the small chip trace that `bench/tests/test_scopes.py` reads:

    python3 -m bench.tests.record_trace <out.xplane.pb.gz>

on one TPU chip, from the root of a checkout. The cell is the paper CNN
shrunk to 2 clients of 32 samples and 32 test images (the step program
carries its data as constants, which the trace's metadata holds), with
int8 wires both ways so that every ``fl.*`` scope of the round runs
device ops. Its programs are compiled first; the trace then holds, under
a "bench/window" span, one training of one 2-round block:
`FedServer.reset` and `FedServer.run(2, mode="scanned", block=2)`, with
the program's own "fl/..." spans. Of the host plane it keeps only the
lines that hold those spans: the reader uses no other host line, and
the runtime's threads would take the file past 200 KB.
"""
from __future__ import annotations

import glob
import gzip
import os
import shutil
import sys
import tempfile


def keep_span_lines(raw: bytes) -> bytes:
    """The serialized XSpace `raw` with only those host-plane lines that
    hold a "bench/..." or "fl/..." span."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        keep = [line for line in plane.lines
                if any(names.get(e.metadata_id, "").startswith(
                    ("bench/", "fl/")) for e in line.events)]
        del plane.lines[:]
        plane.lines.extend(keep)
    return space.SerializeToString()


def main(out: str) -> int:
    sys.path.insert(0, os.path.join(os.getcwd(), "src"))
    import jax

    import repro
    from repro.data import synthetic

    if jax.default_backend() != "tpu":
        print("record_trace: needs a TPU chip", file=sys.stderr)
        return 2
    train, test = synthetic.make_image_task(seed=0, num_train=600,
                                            num_test=32)
    nodes = synthetic.make_federated(train, [("iid", None)] * 2,
                                     samples_per_node=32, seed=1)
    fl = repro.FLConfig(num_clients=2, clients_per_round=2, local_steps=1,
                        engine="flat", transport="int8", downlink="int8")
    server = repro.FedServer("cnn", fl, nodes, test, batch_size=32)

    def training(seed):
        server.reset(seed)
        server.run(2, eval_every=1, mode="scanned", block=2)

    training(0)  # compiles every program the trace runs
    jax.block_until_ready(server.state)
    tmp = tempfile.mkdtemp(prefix="record-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        training(1)
        jax.block_until_ready(server.state)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(path, "rb") as f, gzip.open(out, "wb", compresslevel=9) as g:
        g.write(keep_span_lines(f.read()))
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_trace: {out} {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
