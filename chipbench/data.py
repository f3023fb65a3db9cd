"""Federated data made from the seed, on the device, in one jitted call.

The generative family is that of the program's synthetic benchmark
(`make_federated_classification`): each of ``n_clusters`` hidden client
clusters has Gaussian class prototypes (smoothed twice along the image
width), a client's labels come from its class distribution
(pathological: ``classes_per_client`` classes shared by the whole
cluster; dirichlet: Dir(``alpha``) per client), and an image is its
prototype plus N(0, ``noise``²) noise in the training split and N(0,
``holdout_noise``²) in the validation and test splits. Every client holds
equal-sized splits and weight p_k = 1/N.

The host draws only the small tables (cluster of each client, class
distributions) with numpy; the images and labels, hundreds of MB, are
drawn on the device, so no array of that size crosses from the host.
The same seed gives the same data.
"""
from __future__ import annotations

from functools import partial

import numpy as np


def class_tables(dep: dict, seed: int):
    """(cluster of each client (N,), class distribution of each client
    (N, n_classes)), drawn on the host from ``seed``."""
    rng = np.random.default_rng(seed)
    n, c, k = dep["n_clients"], dep["n_classes"], dep["n_clusters"]
    cluster = np.arange(n) % k
    rng.shuffle(cluster)
    if dep["partition"] == "pathological":
        # every cluster holds classes_per_client distinct classes, dealt
        # round-robin from shuffled class orders so all classes are used
        per = dep["classes_per_client"]
        deck = np.concatenate([rng.permutation(c)
                               for _ in range(-(-k * per // c) + 1)])
        dist = np.zeros((k, c))
        for g in range(k):
            cls = list(dict.fromkeys(deck[g * per:(g + 1) * per].tolist()))
            while len(cls) < per:
                extra = int(rng.integers(c))
                if extra not in cls:
                    cls.append(extra)
            dist[g, cls] = 1.0 / per
        dists = dist[cluster]
    elif dep["partition"] == "dirichlet":
        dists = rng.dirichlet([dep["alpha"]] * c, size=n)
    else:
        raise ValueError(f"unknown partition {dep['partition']!r}")
    return cluster.astype(np.int32), dists


def make_data(dep: dict, seed: int):
    """The deployment ``dep`` (a configuration's ``deployment`` group) as
    a `repro.data.FederatedData` of device-resident arrays, from
    ``seed``."""
    import jax
    import jax.numpy as jnp

    from repro.data import FederatedData

    cluster, dists = class_tables(dep, seed)
    shape = tuple(dep["image_shape"])
    sizes = (dep["n_train"], dep["n_val"], dep["n_test"])
    noises = (dep["noise"], dep["holdout_noise"], dep["holdout_noise"])

    @partial(jax.jit, static_argnums=(3,))
    def draw(key, cluster, logp, sizes):
        kp, *ks = jax.random.split(key, 1 + 2 * len(sizes))
        protos = jax.random.normal(
            kp, (dep["n_clusters"], dep["n_classes"]) + shape)
        for _ in range(2):
            protos = (0.5 * protos + 0.25 * jnp.roll(protos, 1, axis=-2)
                      + 0.25 * jnp.roll(protos, -1, axis=-2))
        out = []
        for i, n in enumerate(sizes):
            y = jax.random.categorical(ks[2 * i], logp[:, None, :],
                                       shape=(len(cluster), n))
            x = protos[cluster[:, None], y] + noises[i] * \
                jax.random.normal(ks[2 * i + 1], (len(cluster), n) + shape)
            out += [x.astype(jnp.float32), y.astype(jnp.int32)]
        return out

    with np.errstate(divide="ignore"):
        logp = np.log(dists).astype(np.float32)
    arrays = draw(jax.random.PRNGKey(seed), jnp.asarray(cluster),
                  jnp.asarray(logp), sizes)
    jax.block_until_ready(arrays)
    n = dep["n_clients"]
    return FederatedData(*arrays, p=np.full(n, 1.0 / n), cluster=cluster,
                         n_classes=dep["n_classes"])
