"""Operations and bytes the DPFL algorithm needs, counted from shapes.

These are the algorithm's counts, not an implementation's: a GGC refresh
costs four reward probes per candidate that the algorithm considers
(|Omega_k \\ {k}| of them), not the N per client that a dense scan
visits, and the Eq.-4 mix reads each model once whatever kernel runs it.
A multiply-add counts as two operations.
"""
from __future__ import annotations

import numpy as np


def cnn_dims(model: dict):
    """Spatial side after each conv-5 + pool-2 stage of the paper CNN."""
    s1 = model["image_size"] - 4
    s2 = (s1 // 2) - 4
    return s1, s2, s2 // 2


def cnn_forward_flops(model: dict) -> int:
    """2 x multiply-adds of one image through the paper's CNN
    (conv5-pool-conv5-pool-fc-fc-fc): convolutions and dense layers;
    bias adds, ReLUs and pooling are not counted."""
    c_in, c1, c2 = model["in_channels"], model["c1"], model["c2"]
    s1, s2, s3 = cnn_dims(model)
    macs = (s1 * s1 * c1 * 25 * c_in            # conv1, VALID 5x5
            + s2 * s2 * c2 * 25 * c1            # conv2, VALID 5x5
            + s3 * s3 * c2 * model["fc1"]
            + model["fc1"] * model["fc2"]
            + model["fc2"] * model["n_classes"])
    return 2 * macs


def cnn_params(model: dict) -> int:
    """P, the parameters of one client's model."""
    from reference import param_shapes

    return sum(int(np.prod(s)) for s in param_shapes(model).values())


def peers_per_client(graph) -> np.ndarray:
    """Off-diagonal peers of each client, from dense (N, N) bool masks
    (diagonal ignored) or (N, B) int neighbor lists (-1 pads)."""
    g = np.asarray(graph)
    if g.dtype == bool:
        return g.sum(1) - np.diagonal(g).astype(int)
    rows = np.arange(g.shape[0])[:, None]
    return ((g >= 0) & (g != rows)).sum(1)


def train_flops(model: dict, dep: dict, train: dict, epochs: int) -> int:
    """Forward and backward (3 x forward) of every minibatch that
    ``epochs`` local epochs run, over all clients."""
    bs = train["batch_size"]
    per_epoch = (dep["n_train"] // bs) * bs
    return 3 * cnn_forward_flops(model) * dep["n_clients"] * epochs \
        * per_epoch


def eval_flops(model: dict, dep: dict) -> int:
    """Every client's validation forward of the round's evaluation."""
    return cnn_forward_flops(model) * dep["n_clients"] * dep["n_val"]


def refresh_flops(model: dict, dep: dict, omega) -> int:
    """The GGC refresh's reward probes: four forwards of the client's
    validation split per candidate in Omega_k, k excluded."""
    return 4 * cnn_forward_flops(model) * dep["n_val"] * \
        int(peers_per_client(omega).sum())


def mix_flops(n_params: int, graph) -> int:
    """Eq.-4: one multiply-add per parameter per member of C_k u {k}."""
    members = int(peers_per_client(graph).sum()) + len(np.asarray(graph))
    return 2 * n_params * members


def mix_bytes(n_params: int, graph) -> int:
    """Eq.-4 moves the (N, P) fp32 panel in once and out once, plus one
    fp32 weight and one int32 peer id per member of C_k u {k}."""
    g = np.asarray(graph)
    members = int(peers_per_client(g).sum()) + len(g)
    return 2 * 4 * len(g) * n_params + 8 * members


def round_flops(model: dict, dep: dict, train: dict, traffic: dict,
                omega, graph, refresh: bool = True) -> int:
    """One DPFL round: local training, the refresh (when it runs), the
    mix over ``graph`` (C_k) and the validation forward."""
    p = cnn_params(model)
    total = (train_flops(model, dep, train, traffic["tau_train"])
             + eval_flops(model, dep) + mix_flops(p, graph))
    if refresh:
        total += refresh_flops(model, dep, omega)
    return total


def roofline_s(flops: float, nbytes: float, peak: dict):
    """Least time on the chip and the bound that sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
