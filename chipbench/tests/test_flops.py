"""Operation and byte counts against counts made by hand."""
import json
import os

import numpy as np
import pytest

import flops
import reference

HERE = os.path.dirname(os.path.abspath(__file__))


def model_of(config: str) -> dict:
    path = os.path.join(os.path.dirname(HERE), "configs", config + ".json")
    with open(path) as f:
        return json.load(f)["model"]


CIFAR = model_of("cnn-cifar10-n100")
# the paper CNN at LEAF FEMNIST's shapes (28x28x1, 62 classes)
FEMNIST = dict(CIFAR, in_channels=1, image_size=28, n_classes=62)


@pytest.mark.parametrize("model, fwd, params", [
    # conv1 28*28*6*75 + conv2 10*10*16*150 + 400*120 + 120*84 + 84*10
    (CIFAR, 2 * (352_800 + 240_000 + 48_000 + 10_080 + 840), 62_006),
    # conv1 24*24*6*25 + conv2 8*8*16*150 + 256*120 + 120*84 + 84*62
    (FEMNIST, 2 * (86_400 + 153_600 + 30_720 + 10_080 + 5_208), 48_846),
])
def test_cnn_counts(model, fwd, params):
    assert flops.cnn_forward_flops(model) == fwd
    assert fwd in (1_303_440, 572_016)
    assert flops.cnn_params(model) == params
    assert sum(int(np.prod(s)) for s in
               reference.param_shapes(model).values()) == params


def test_param_count_matches_the_program():
    from repro.configs.paper_cnn import CNNConfig
    from repro.models.classifier import PaperCNN
    import jax
    from jax.flatten_util import ravel_pytree

    for model in (CIFAR, FEMNIST):
        params = PaperCNN(CNNConfig(**model)).init(jax.random.PRNGKey(0))
        assert ravel_pytree(params)[0].shape[0] == flops.cnn_params(model)
        # the reference lays leaves out in the program's flattened order
        assert sorted(params) == list(reference.param_shapes(model))


def graphs():
    """One graph as dense masks and as the equivalent neighbor lists."""
    rng = np.random.default_rng(3)
    n, b = 12, 4
    lists = np.full((n, b), -1, np.int32)
    dense = np.eye(n, dtype=bool)
    for k in range(n):
        peers = np.sort(rng.choice([i for i in range(n) if i != k],
                                   size=int(rng.integers(0, b + 1)),
                                   replace=False))
        lists[k, :len(peers)] = peers
        dense[k, peers] = True
    return dense, lists


def test_dense_and_lists_count_alike():
    dense, lists = graphs()
    members = int(dense.sum())            # C_k u {k} over all k
    assert flops.peers_per_client(dense).tolist() == \
        flops.peers_per_client(lists).tolist()
    p = 62_006
    assert flops.mix_flops(p, dense) == flops.mix_flops(p, lists) \
        == 2 * p * members
    assert flops.mix_bytes(p, dense) == flops.mix_bytes(p, lists) \
        == 8 * 12 * p + 8 * members
    dep = {"n_val": 100, "n_clients": 12, "n_train": 500}
    assert flops.refresh_flops(CIFAR, dep, dense) == \
        flops.refresh_flops(CIFAR, dep, lists) == \
        4 * 1_303_440 * 100 * (members - 12)


def test_round_flops_by_hand():
    dense, lists = graphs()
    dep = {"n_val": 100, "n_clients": 12, "n_train": 500}
    train = {"batch_size": 16}
    fwd = 1_303_440
    want = (3 * fwd * 12 * 5 * 496 + fwd * 12 * 100
            + 2 * 62_006 * int(dense.sum())
            + 4 * fwd * 100 * (int(dense.sum()) - 12))
    for g in (dense, lists):
        assert flops.round_flops(CIFAR, dep, train, {"tau_train": 5},
                                 g, g) == want
    assert flops.round_flops(CIFAR, dep, train, {"tau_train": 5}, dense,
                             dense, refresh=False) == \
        want - 4 * fwd * 100 * (int(dense.sum()) - 12)


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_s(1e9, 8e8, peak)
    assert bound == "memory" and t == pytest.approx(8e8 / 819e9)
    t, bound = flops.roofline_s(1e15, 8e8, peak)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)
