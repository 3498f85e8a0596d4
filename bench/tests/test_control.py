"""The check's control: the plain reference computed in bfloat16, the
step below the float32 the configurations state, put in the program's
place, fails the cell's limits; the float32 reference against itself
passes them. At a size a CPU test run can hold: a few clients and
rounds of each cell's model."""
import json
import os

import numpy as np
import pytest

from bench import check, reference
from bench import data as bench_data

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _traffic(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        tr = json.load(f)
    model = {"cnn.paper": "cnn", "mlr.paper": "mlr"}[name]
    return dict(tr, model=model)


def small(tr: dict, clients: int, samples: int, steps: int) -> dict:
    """The cell's model, wires and optimiser at a few clients and steps."""
    fl = dict(tr["fl"], num_clients=clients,
              clients_per_round=min(clients, tr["fl"]["clients_per_round"]),
              local_steps=steps)
    return dict(tr, fl=fl, batch_size=samples // steps)


@pytest.mark.parametrize("name,clients,samples,steps,rounds", [
    ("cnn.paper", 2, 64, 2, 2),
    ("mlr.paper", 4, 120, 4, 4),
])
def test_control_fails_and_reference_passes(name, clients, samples, steps,
                                            rounds):
    tr = small(_traffic(name), clients, samples, steps)
    limits = tr["check"]["limits"]
    train, test = bench_data.image_task(0, 2000, 400)
    nodes = bench_data.xclass_split(
        train, [["iid", None], ["xclass", 1]] * (clients // 2), samples, 0)
    data = reference.device_data(nodes, test)
    every = 1
    seed = 3000000007
    ref = reference.follow(reference.setup_from(tr), data, seed, rounds, every)
    ctl = reference.follow(reference.setup_from(tr, reference.CONTROL), data,
                           seed, rounds, every)
    same = check.readings(ref, ref)
    assert all(v == 0 for v in same.values()), same
    found = check.readings(ctl, ref)
    ok, table = check.judge({k: v for k, v in found.items() if k in limits},
                            {k: v for k, v in limits.items() if k in found})
    assert not ok, table
    assert np.isfinite(list(found.values())).all()
