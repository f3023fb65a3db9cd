"""The comparison that decides ``correct``.

The system's first rounds after the preprocessing are recorded: the
(N, P) model panel handed to round 1 and after each of rounds 1..R, each
round's validation accuracies and collaboration graph. The reference
(`reference.py`) then follows the same run from the seed on the same
data: the initial model, the tau_init local epochs, the Eq.-4 mix over
Omega, and per round the local epochs, the GGC refresh, the mix and the
evaluation. It takes from the system only its graph decisions (Omega and
each round's C_k), as a served model's reference takes the served
tokens; the models it mixes and probes are its own. Numbers compared:

- ``comm``: every round of the window, the largest difference between
  the system's download counter and the count the algorithm prescribes
  (|Omega_k \\ {k}| summed on a refresh round, |C_k| otherwise): exact;
- ``graph``: every round of the window, how many clients' C_k break the
  algorithm's guarantees (a peer outside Omega_k, the client itself, a
  peer twice, more than B peers): exact;
- ``change``: per parameter leaf, the gap between the norms of the
  panel's change over rounds 1..R in the system and in the reference,
  over the reference's norm of that leaf or of the median leaf,
  whichever is larger; the worst leaf;
- ``panel``: the same scale, of the norm of the difference between the
  two panels after round R; the worst leaf;
- ``client``: per client, the norm of the difference between its two
  models after round R over the norm of its change over rounds 1..R in
  the reference (or the median client's, whichever is larger); the worst
  client;
- ``acc``: the largest gap between the clients' mean validation accuracy
  in the system and in the reference, over rounds 1..R;
- ``best``: after round R, the largest gap between a client's best
  validation accuracy in the system and the best of its accuracies over
  rounds 1..R in the reference;
- ``decision``: over the system's GGC decisions, the largest change of
  the reference's rewards (nats of validation loss) that the decision
  needs to be the one the reference takes under the seed's coin flip
  (`reference.reward_gap`).
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("comm", "graph", "change", "panel", "client", "acc", "best",
           "decision")


def as_lists(graph, width: int) -> np.ndarray:
    """A graph as (N, W) int32 neighbor lists: dense (N, N) bool masks
    (diagonal ignored) are listed, W = ``width`` or the most peers a
    client has, if more; lists pass through."""
    g = np.asarray(graph)
    if g.dtype != bool:
        return g.astype(np.int32)
    off = g & ~np.eye(len(g), dtype=bool)
    out = np.full((len(g), max(width, int(off.sum(1).max()))), -1,
                  np.int32)
    for k, row in enumerate(off):
        peers = np.flatnonzero(row)
        out[k, :len(peers)] = peers
    return out


def broken_lists(omega, graph, budget: int) -> int:
    """How many clients' lists in ``graph`` hold a peer outside their
    list in ``omega``, themselves, a peer twice, or more than ``budget``
    peers."""
    bad = 0
    for k, (cand, row) in enumerate(zip(np.asarray(omega),
                                        np.asarray(graph))):
        peers = row[row >= 0].tolist()
        bad += (len(peers) > budget or len(set(peers)) < len(peers)
                or k in peers
                or not set(peers) <= set(cand[cand >= 0].tolist()))
    return bad


def expected_comm(omega, graphs, period: int) -> list:
    """Downloads the algorithm prescribes for rounds 0, 1, ...: all of
    Omega (off the diagonal) on a refresh round, the previous round's C_k
    otherwise. ``graphs[t]`` is the graph round t ended with, as lists."""
    return [int((np.asarray(omega) >= 0).sum()) if t % period == 0
            else int((np.asarray(graphs[t - 1]) >= 0).sum())
            for t in range(len(graphs))]


def leaf_norms(panel, slices: dict) -> np.ndarray:
    p = np.asarray(panel, np.float64)
    return np.array([np.linalg.norm(p[:, sl]) for sl in slices.values()])


def numbers(slices: dict, got: dict, want: dict) -> dict:
    """``change``, ``panel``, ``client``, ``acc``, ``best`` and
    ``decision`` of records ``got`` against the reference's ``want``
    (each: "S0" panel, "S" list of panels, "val" list of (N,)
    accuracies; ``got`` also "best", the (N,) best accuracies after the
    last round, and ``want`` "gaps")."""
    n_got = leaf_norms(np.asarray(got["S"][-1], np.float64)
                       - np.asarray(got["S0"], np.float64), slices)
    d_want = (np.asarray(want["S"][-1], np.float64)
             - np.asarray(want["S0"], np.float64))
    n_want = leaf_norms(d_want, slices)
    scale = np.maximum(n_want, np.median(n_want))
    gap = (np.asarray(got["S"][-1], np.float64)
           - np.asarray(want["S"][-1], np.float64))
    diff = leaf_norms(gap, slices)
    moved = np.linalg.norm(d_want, axis=1)
    acc = max(abs(float(np.mean(a)) - float(np.mean(b)))
              for a, b in zip(got["val"], want["val"]))
    gaps = [float(np.max(g)) for g in want["gaps"]] or [0.0]
    return {"change": float(np.max(np.abs(n_got - n_want) / scale)),
            "panel": float(np.max(diff / scale)),
            "client": float(np.max(np.linalg.norm(gap, axis=1)
                                   / np.maximum(moved, np.median(moved)))),
            "acc": acc,
            "best": float(np.max(np.abs(np.asarray(got["best"], np.float64)
                                        - np.max(want["val"], axis=0)))),
            "decision": max(gaps)}


def verdict(values: dict, limits) -> tuple:
    """(correct, checks): every number that has a limit is within it (the
    comm count exactly); ``checks`` maps each number to [value, limit]
    (limit None where the number is read but not compared). Without a
    limits table, nothing is correct."""
    checks = {k: [values[k], (limits or {}).get(k)] for k in NUMBERS
              if k in values}
    ok = limits is not None and all(
        v <= lim for v, lim in checks.values() if lim is not None)
    return ok, checks
