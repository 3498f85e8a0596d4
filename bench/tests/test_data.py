"""The benchmark's vectorised data copy against the program's generator."""
import numpy as np
import pytest

from bench import data as bench_data
from repro.data import synthetic


@pytest.mark.parametrize("seed", [0, 7])
def test_image_task_matches_program_element_for_element(seed):
    train, test = synthetic.make_image_task(seed=seed, num_train=300,
                                            num_test=120)
    b_train, b_test = bench_data.image_task(seed, 300, 120)
    for ours, theirs in ((b_train, train), (b_test, test)):
        assert ours.x.dtype == theirs.x.dtype
        np.testing.assert_array_equal(ours.x, theirs.x)
        np.testing.assert_array_equal(ours.y, theirs.y)


def test_xclass_split_matches_program():
    train, _ = synthetic.make_image_task(seed=1, num_train=400, num_test=10)
    spec = [("iid", None)] * 3 + [("xclass", 1)] * 2 + [("xclass", 2)]
    theirs = synthetic.make_federated(train, spec, samples_per_node=50,
                                      seed=3)
    ours = bench_data.xclass_split(bench_data.Dataset(*train), spec, 50, 3)
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(a.x, b.x)
        np.testing.assert_array_equal(a.y, b.y)


def test_shard_split_gives_each_client_its_label_shards():
    train, _ = bench_data.image_task(2, 2000, 10)
    nodes = bench_data.shard_split(train, clients=10, shards_per_client=2,
                                   seed=5)
    assert all(len(n.y) == 200 for n in nodes)
    rows = np.concatenate([n.x.reshape(len(n.y), -1) for n in nodes])
    # every training row is used once: the shards partition the set
    assert len(np.unique(rows, axis=0)) == 2000
    # a shard is a run of the label-sorted rows: at most 2 labels per
    # shard boundary, so each client sees few labels
    assert max(len(np.unique(n.y)) for n in nodes) <= 4
