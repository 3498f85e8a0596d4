"""The image family: FedAdp's MNIST-shaped task for `FedServer`'s image
models (`mlr`, `cnn`).

A family module is what `bench.run` knows of a kind of client model and
its data. It names the configuration file's "family" and sits at
`bench/families/<family>.py`; the harness calls, and only these:

* `make_data(traffic) -> (nodes, test)`: the clients' data and the eval
  set, from the traffic file;
* `make_server(repro, fl, traffic, nodes, test, seed)`: the program's
  server over them, built through the program's own entry;
* `reference_setup(traffic)`, `reference_data(nodes, test)` and
  `follow(setup, data, seed, rounds, eval_every)`: the plain reference,
  which `bench.run.run_check` (and `bench.calibrate`) run after the
  window; `follow` returns `bench.check.readings`'s reference side;
* `reference_variants(traffic)` (`bench.calibrate` only): the control
  and the planted faults that the limits are set against;
* `round_flops(config, traffic)` and `eval_flops(config, traffic)`: the
  model FLOPs of one round's local training and of one eval, which
  `step_mfu` reads;
* `validate_traffic(traffic)`: the family's rules of a traffic file,
  for the test suite; raises ValueError.

This one is a thin adapter over `bench.data`, `bench.reference` and
`bench.counts`.
"""
from __future__ import annotations

from bench import counts, reference
from bench import data as bench_data


def make_data(traffic: dict):
    return bench_data.make_nodes(traffic)


def make_server(repro, fl, traffic: dict, nodes, test, seed: int):
    return repro.FedServer(traffic["model"], fl, nodes, test,
                           batch_size=traffic["batch_size"], seed=seed)


def reference_setup(traffic: dict):
    return reference.setup_from(traffic)


def reference_data(nodes, test):
    return reference.device_data(nodes, test)


def follow(setup, data, seed: int, rounds: int, eval_every: int) -> dict:
    return reference.follow(setup, data, seed, rounds, eval_every)


def reference_variants(traffic: dict) -> dict:
    """The bfloat16 control and the half-minibatch fault."""
    return {"control": reference.setup_from(traffic, reference.CONTROL),
            "half_batch": reference.setup_from(traffic, half_batch=True)}


def round_flops(config: dict, traffic: dict) -> int:
    return counts.round_flops(config, traffic, eval_round=False)


def eval_flops(config: dict, traffic: dict) -> int:
    return traffic["data"]["num_test"] * counts.forward_flops(config)


def validate_traffic(traffic: dict) -> None:
    """tau is one pass over each node's samples (E = 1, as in Table I)."""
    split = traffic["data"]["split"]
    samples = split.get("samples") or (
        traffic["data"]["num_train"]
        // (split["clients"] * split["shards_per_client"])
        * split["shards_per_client"])
    tau = traffic["fl"]["local_steps"]
    if tau != samples // traffic["batch_size"]:
        raise ValueError(f"local_steps {tau} is not one pass over "
                         f"{samples} samples in batches of "
                         f"{traffic['batch_size']}")
