"""Collaboration-graph construction: GGC (Alg. 2) and BGGC (Alg. 3).

The randomized double-greedy of Fourati et al. adapted to DPFL: for each
candidate j (in seeded shuffled order) compute the marginal gains of
*adding* j to the grow-set X and *removing* j from the shrink-set Y, where
rewards are R(S) = -F_k^V(weighted_avg_{i in S} w_i); accept with
probability a/(a+b) (p=1 when a=b=0 per the paper), until |C_k| = B_c.

TPU adaptation (DESIGN.md §3): the sequential loop is a seeded `lax.scan`
carrying (mask_X, mask_Y, w^X, w^Y, p_X, p_Y); the four reward probes per
step are one vmapped forward. The running sums are exactly BGGC's trick, so
GGC, BGGC and the heterogeneous-budget variant share ONE decision kernel
(`greedy_decision_step`) and Theorem 1 holds by construction — and is
*tested* against a literal recompute-from-scratch reference (`ggc_naive`)
plus a batched BGGC (`bggc`) that never holds more than B_c client models.

Coin flips use fold_in(key, candidate_id), making the random stream
independent of batching order — the seeded-randomness premise of Thm 1.

All set-average / aggregation matmuls route through the dispatching
`kernels.ops.graph_mix` (Pallas on TPU, pure-jnp fp32 reference elsewhere);
pass ``mix_impl`` to pin an implementation (DESIGN.md §4).

Every builder exists in two graph representations (DESIGN.md §12): the
dense entry points emit (N, N) bool masks, the ``*_sparse`` ones emit
(N, B) int32 neighbor lists (ascending peer ids, -1 pads, self edge
implicit) whose greedy scans probe only the <= B candidates — same
seeded decisions bit for bit, O(N·B) instead of O(N²) work. The dense
GGC scans those lists too when its caller bounds the candidate rows
(`all_clients_graph`'s ``width``), taking and returning masks.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..analysis.counters import count as _count
from ..analysis.registry import exchange_site
from ..kernels import ops as _kops


# ------------------------------------------------------------------ mixing


def eq4_weights_unnormalized(adj, p, active=None):
    """The Eq.-4 member weights BEFORE row normalization: (N, N) fp32
    with entry ``p_i`` where k receives from i (diagonal forced on,
    participation-masked), 0 elsewhere. `mixing_matrix` is exactly this
    divided by its row sums; the robust rules (`repro.fl.robust`) need
    the unnormalized form because trimming changes which members the
    normalization runs over (DESIGN.md §15)."""
    adj = jnp.asarray(adj, jnp.float32)
    n = adj.shape[0]
    if active is not None:
        act = jnp.asarray(active, jnp.float32)
        adj = adj * act[:, None] * act[None, :]
    adj = jnp.maximum(adj, jnp.eye(n, dtype=adj.dtype))
    return adj * p[None, :]


def mixing_matrix(adj, p, active=None):
    """adj: (N, N) bool/float, adj[k, i]=1 iff k receives from i (diagonal
    forced on: every client 'collaborates' with itself). p: (N,) weights.
    Returns row-stochastic A with A[k, i] = p_i adj[k, i] / sum_j p_j adj[k, j].

    ``active`` ((N,) bool, optional) restricts the round to the available
    clients (DESIGN.md §9): rows AND columns of absent clients zero out
    before the forced diagonal, so an absent client's row is e_k (it holds
    its params) and an available client renormalizes its Eq.-4 weights
    over only its available peers. ``active=None`` (and an all-ones mask —
    multiplying by 1.0 is exact) reproduces the full-participation matrix
    bitwise.
    """
    w = eq4_weights_unnormalized(adj, p, active=active)
    return w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-12)


@exchange_site(charges="caller")
def mix_pytree(A, stacked_params):
    """w_k <- sum_i A[k,i] w_i on a client-stacked pytree (Eq. 4)."""
    return jax.tree.map(
        lambda w: jnp.einsum("ij,j...->i...", A.astype(jnp.float32),
                             w.astype(jnp.float32)).astype(w.dtype),
        stacked_params)


@exchange_site(charges="caller")
def mix_flat(A, flat_w, mix_fn=None, *, impl: Optional[str] = None,
             mesh=None, client_axes=None):
    """(N, P) client-stacked flattened params through the Eq.-4 mixing
    matmul. Dispatches to `kernels.ops.graph_mix` (Pallas on TPU, fp32
    reference elsewhere); ``impl`` pins an implementation, ``mix_fn``
    overrides the whole op (legacy hook). ``mesh``/``client_axes`` select
    the shard_map row-block path (each client shard gathers the peer
    panels it mixes with — DESIGN.md §8)."""
    if mix_fn is not None:
        return mix_fn(A, flat_w)
    return _kops.graph_mix(A, flat_w, impl=impl, mesh=mesh,
                           client_axes=client_axes)


@exchange_site(charges="caller")
def weighted_sum(mask_p, flat_w, *, impl: Optional[str] = None):
    """sum_n mask_p[n] * flat_w[n] — the set-average numerator used by the
    greedy probes, routed through the same graph_mix kernel as Eq. 4
    ((1, N) @ (N, P) row-matmul in fp32)."""
    out = _kops.graph_mix(mask_p.astype(jnp.float32)[None, :],
                          flat_w.astype(jnp.float32), impl=impl)
    return out[0]


# ----------------------------------------------------------- GGC decisions


class GreedyCarry(NamedTuple):
    """Running double-greedy state: grow/shrink masks, their weighted
    parameter sums and total weights, and the selection count."""
    maskX: jax.Array    # (N,) bool — grow set X (incl. client k)
    maskY: jax.Array    # (N,) bool — shrink set Y
    wX: jax.Array       # (P,) — sum_{i in X} p_i w_i
    wY: jax.Array       # (P,) — sum_{i in Y} p_i w_i
    pX: jax.Array       # () — sum_{i in X} p_i
    pY: jax.Array       # () — sum_{i in Y} p_i
    nsel: jax.Array     # () int32 — |C_k| so far


def greedy_decision_step(reward_fn: Callable):
    """THE single copy of the seeded double-greedy decision body.

    Returns ``step(carry, j, w_j, *, key, k_idx, cand_mask, p, budget)``
    processing candidate ``j`` (model ``w_j``): four reward probes batched
    into one vmapped forward, the a/(a+b) coin flip on the
    ``fold_in(key, j+1)`` stream, and the running-sum accept/reject update.
    ``budget`` is a *traced* int32 scalar, so one compiled kernel serves
    static (Alg. 2), batched (Alg. 3) and per-client heterogeneous budgets
    alike — Theorem-1 equivalence across the three entry points holds by
    construction (tested against `make_ggc_naive`).
    """

    def step(carry: GreedyCarry, j, w_j, *, key, k_idx, cand_mask, p,
             budget, slot=None, is_cand=None, p_j=None) -> GreedyCarry:
        maskX, maskY, wX, wY, pX, pY, nsel = carry
        # ``slot`` is the carry-mask position of candidate ``j``: the
        # dense scans index their (N,) masks by the global id, the sparse
        # scan (make_ggc_sparse) by the (B,) neighbor-list slot — the
        # PRNG stream and the probes always use the global id, so both
        # layouts draw identical coin flips for identical candidates
        slot = j if slot is None else slot
        is_cand = cand_mask[j] if is_cand is None else is_cand
        p_j = p[j] if p_j is None else p_j
        # four reward probes, batched into one vmapped forward; barriers
        # pin the probe/reward fusion boundary so the decision stream does
        # not additionally depend on what surrounds the kernel (compiled
        # round vs host loop vs shard_map block) — fp noise here feeds the
        # a/(a+b) coin flips, which near-zero gains amplify (DESIGN.md §8)
        probes = jax.lax.optimization_barrier(jnp.stack([
            wX / pX,
            (wX + p_j * w_j) / (pX + p_j),
            wY / pY,
            (wY - p_j * w_j) / jnp.maximum(pY - p_j, 1e-12),
        ]))
        r = jax.lax.optimization_barrier(
            jax.vmap(lambda fw: reward_fn(fw, k_idx))(probes))
        a = jnp.maximum(r[1] - r[0], 0.0)
        b = jnp.maximum(r[3] - r[2], 0.0)
        prob = jnp.where(a + b > 0, a / (a + b), 1.0)
        u = jax.random.uniform(jax.random.fold_in(key, j + 1))
        add = (u < prob) & is_cand & (nsel < budget)
        rem = (~(u < prob)) & is_cand
        return GreedyCarry(
            maskX=maskX.at[slot].set(maskX[slot] | add),
            maskY=maskY.at[slot].set(maskY[slot] & ~rem),
            wX=jnp.where(add, wX + p_j * w_j, wX),
            wY=jnp.where(rem, wY - p_j * w_j, wY),
            pX=jnp.where(add, pX + p_j, pX),
            pY=jnp.where(rem, pY - p_j, pY),
            nsel=nsel + add.astype(jnp.int32))

    return step


def _greedy_init(k_idx, cand_mask, flat_w, p, *, mix_impl=None):
    """Shared GGC initialization: X = {k}, Y = Omega_k ∪ {k}, running sums
    via the graph_mix row-matmul."""
    N = flat_w.shape[0]
    maskX = jnp.zeros(N, bool).at[k_idx].set(True)
    maskY = cand_mask | maskX
    return GreedyCarry(
        maskX=maskX, maskY=maskY,
        wX=p[k_idx] * flat_w[k_idx],
        wY=weighted_sum(maskY * p, flat_w, impl=mix_impl),
        pX=p[k_idx], pY=jnp.sum(maskY * p),
        nsel=jnp.int32(0))


def make_ggc(reward_fn: Callable, budget: int, *,
             mix_impl: Optional[str] = None):
    """Build the jittable GGC kernel (Algorithm 2).

    reward_fn(flat_params (P,), client_idx) -> scalar reward (higher =
    better), i.e. -validation loss for that client.

    Returns ggc(key, k_idx, cand_mask (N,), flat_w (N,P), p (N,),
    budget_k=None) -> mask_X (N,) bool of selected collaborators INCLUDING
    k itself. ``budget_k`` optionally overrides the static budget with a
    traced per-client scalar (the heterogeneous variant).
    """
    step = greedy_decision_step(reward_fn)

    def ggc(key, k_idx, cand_mask, flat_w, p, budget_k=None):
        N = flat_w.shape[0]
        b = jnp.int32(budget) if budget_k is None else \
            jnp.asarray(budget_k, jnp.int32)
        cand_mask = cand_mask & (jnp.arange(N) != k_idx)
        carry = _greedy_init(k_idx, cand_mask, flat_w, p, mix_impl=mix_impl)
        order = jax.random.permutation(jax.random.fold_in(key, 0), N)

        def body(carry, j):
            return step(carry, j, flat_w[j], key=key, k_idx=k_idx,
                        cand_mask=cand_mask, p=p, budget=b), None

        carry, _ = jax.lax.scan(body, carry, order)
        return carry.maskX

    return ggc


@exchange_site(charges="preprocess")
def make_ggc_naive(reward_fn: Callable, budget: int):
    """Literal Algorithm 2: recompute set averages from scratch each step
    (no running sums). Oracle for the Theorem-1 equivalence tests."""

    def avg(mask, flat_w, p):
        w = jnp.einsum("n,np->p", mask * p, flat_w)
        return w / jnp.maximum(jnp.sum(mask * p), 1e-12)

    def ggc(key, k_idx, cand_mask, flat_w, p):
        N = flat_w.shape[0]
        cand_mask = cand_mask & (jnp.arange(N) != k_idx)
        maskX = jnp.zeros(N, bool).at[k_idx].set(True)
        maskY = cand_mask | maskX
        order = jax.random.permutation(jax.random.fold_in(key, 0), N)

        def body(carry, j):
            maskX, maskY, nsel = carry
            is_cand = cand_mask[j]
            p_ = p.astype(jnp.float32)
            RX = reward_fn(avg(maskX.astype(jnp.float32), flat_w, p_), k_idx)
            RXj = reward_fn(
                avg(maskX.at[j].set(True).astype(jnp.float32), flat_w, p_),
                k_idx)
            RY = reward_fn(avg(maskY.astype(jnp.float32), flat_w, p_), k_idx)
            RYj = reward_fn(
                avg(maskY.at[j].set(False).astype(jnp.float32), flat_w, p_),
                k_idx)
            a = jnp.maximum(RXj - RX, 0.0)
            b = jnp.maximum(RYj - RY, 0.0)
            prob = jnp.where(a + b > 0, a / (a + b), 1.0)
            u = jax.random.uniform(jax.random.fold_in(key, j + 1))
            add = (u < prob) & is_cand & (nsel < budget)
            rem = (~(u < prob)) & is_cand
            maskX = maskX.at[j].set(maskX[j] | add)
            maskY = maskY.at[j].set(maskY[j] & ~rem)
            return (maskX, maskY, nsel + add.astype(jnp.int32)), None

        init = (maskX, maskY, jnp.int32(0))
        (maskX, _, _), _ = jax.lax.scan(body, init, order)
        return maskX

    return ggc


def make_bggc(reward_fn: Callable, budget: int, *,
              mix_impl: Optional[str] = None):
    """Batched GGC (Algorithm 3): the preprocessing-phase variant that
    receives models in batches of <= budget and keeps only the streaming
    sums w^X / w^Y — never more than O(B_c) model storage.

    The python loop over batches mirrors the two communication phases of
    Algorithm 3; decisions are the shared `greedy_decision_step`, so the
    output equals GGC's (Theorem 1; tested).
    """
    step = greedy_decision_step(reward_fn)

    def bggc(key, k_idx, cand_mask, flat_w, p):
        N, P = flat_w.shape
        b = jnp.int32(budget)
        cand_mask = jnp.asarray(cand_mask) & (jnp.arange(N) != k_idx)
        # --- phase 1: stream batches to accumulate w^Y (Alg. 3 lines 2-7)
        maskY0 = cand_mask | jnp.zeros(N, bool).at[k_idx].set(True)
        wY = p[k_idx] * flat_w[k_idx]
        pY = p[k_idx]
        B = max(int(budget), 1)
        for s in range(0, N, B):
            batch = jnp.arange(s, min(s + B, N))
            m = maskY0[batch] & (batch != k_idx)
            wY = wY + weighted_sum(m * p[batch], flat_w[batch],
                                   impl=mix_impl)
            pY = pY + jnp.sum(m * p[batch])
        # --- phase 2: batched decisions in the SAME shuffled order
        maskX = jnp.zeros(N, bool).at[k_idx].set(True)
        carry = GreedyCarry(maskX=maskX, maskY=maskY0,
                            wX=p[k_idx] * flat_w[k_idx], wY=wY,
                            pX=p[k_idx], pY=pY, nsel=jnp.int32(0))
        order = jax.random.permutation(jax.random.fold_in(key, 0), N)

        def body(carry, jw):
            j, w_j = jw  # the batch transmits model w_j with its index
            return step(carry, j, w_j, key=key, k_idx=k_idx,
                        cand_mask=cand_mask, p=p, budget=b), None

        for s in range(0, N, B):  # each iteration receives <= B_c models
            idx = order[s:min(s + B, N)]
            batch_w = flat_w[idx]  # the only model storage: <= B_c rows
            carry, _ = jax.lax.scan(body, carry, (idx, batch_w))
        return carry.maskX

    return bggc


def make_ggc_heterogeneous(reward_fn: Callable, max_budget: int, *,
                           mix_impl: Optional[str] = None):
    """Beyond-paper extension (the paper's §Limitations, implemented):
    per-client budgets B_c^k — the budget enters as a traced scalar so
    one compiled kernel serves every client. Thin wrapper over the unified
    `make_ggc` kernel (``max_budget`` kept for API compatibility; the
    traced budget is what constrains selection).

    Returns ggc(key, k_idx, cand_mask, flat_w, p, budget_k) -> mask_X."""
    base = make_ggc(reward_fn, int(max_budget), mix_impl=mix_impl)

    def ggc(key, k_idx, cand_mask, flat_w, p, budget_k):
        return base(key, k_idx, cand_mask, flat_w, p, budget_k=budget_k)

    return ggc


@exchange_site(charges="preprocess")
def _shard_clients_graph(per_client, mesh, client_axes, keys, ks,
                         cand_masks, flat_w, p, extra=()):
    """shard_map a vmapped per-client graph builder over the client mesh
    axes: each shard all-gathers the peer parameter panels once, then
    vmaps ``per_client`` over only its shard-local k rows — the GGC
    reward probes and greedy decisions stay shard-local (DESIGN.md §8).

    ``cand_masks`` is any per-client (N, C) row table — dense (N, N) bool
    candidate masks or sparse (N, B) int32 neighbor lists. ``extra`` are
    replicated trailing arguments passed whole to every ``per_client``
    call (e.g. the (N,) availability mask of a participation round)."""
    from jax.sharding import PartitionSpec as P

    ca = tuple(client_axes)

    def block(keys_blk, k_blk, cand_blk, w_blk, p_full, *extra_full):
        # materialize the gathered peer panels before the probes so the
        # gather cannot fuse into the reward matmuls (keeps the per-shard
        # probe numerics as close to the single-device build as XLA
        # allows — see DESIGN.md §8 on greedy-decision fp sensitivity)
        w_full = jax.lax.optimization_barrier(
            jax.lax.all_gather(w_blk, ca, axis=0, tiled=True))
        return jax.vmap(
            per_client,
            in_axes=(0, 0, 0, None, None) + (None,) * len(extra_full))(
                keys_blk, k_blk, cand_blk, w_full, p_full, *extra_full)

    # check_vma=False: the probes may dispatch to the Pallas graph_mix
    # kernel, which has no shard_map replication rule
    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(ca, None), P(ca), P(ca, None), P(ca, None), P(None))
        + (P(None),) * len(extra),
        out_specs=P(ca, None), check_vma=False)(keys, ks, cand_masks,
                                                flat_w, p, *extra)


def _count_probes(n_clients: int, scan_len: int) -> None:
    """Count, at trace time, the reward probes one call of an all-clients
    builder executes: four per scan step (`greedy_decision_step`), a scan
    of ``scan_len`` candidates for each of ``n_clients`` clients — N for
    the full scans and BGGC, the list width for the neighbor-list scan —
    however few of them are candidates (`repro.analysis.counters`)."""
    _count("ggc.probes", 4 * n_clients * scan_len)


def all_clients_graph(key, flat_w, p, cand_masks, reward_fn, budget,
                      impl: str = "ggc", mix_impl: Optional[str] = None,
                      mesh=None, client_axes=None,
                      width: Optional[int] = None):
    """Run graph construction for every client (vmap over k).

    cand_masks: (N, N) bool, row k = Omega_k. Returns adjacency (N, N) bool
    with adj[k, i]=1 iff i selected for k (diag True). With
    ``mesh``/``client_axes`` the vmap covers only the shard-local k rows
    inside a shard_map (adjacency rows come back client-sharded).

    ``width`` is a static bound on the off-diagonal candidates of every
    row. Given it, the ``"ggc"`` scan visits each Omega_k as a list of
    ``width`` slots (`make_ggc_sparse`) instead of all N clients: the
    same selections bit for bit (the skipped non-candidates are no-ops of
    the full scan), 4·N·width reward probes instead of 4·N². A row with
    more candidates than ``width`` would lose the surplus, so pass it
    only where the masks are known to respect it. The naive oracle always
    scans all N."""
    N = flat_w.shape[0]
    if width is not None and impl == "ggc":
        # counts its own 4·N·width probes
        nbr = all_clients_graph_sparse(
            key, flat_w, p, neighbors_from_adjacency(cand_masks, width),
            reward_fn, budget, mix_impl=mix_impl, mesh=mesh,
            client_axes=client_axes)
        return adjacency_from_neighbors(nbr, N)
    _count_probes(N, N)
    if impl == "naive":
        ggc = make_ggc_naive(reward_fn, budget)
    else:
        ggc = make_ggc(reward_fn, budget, mix_impl=mix_impl)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(N))
    if mesh is not None:
        return _shard_clients_graph(ggc, mesh, client_axes, keys,
                                    jnp.arange(N), cand_masks, flat_w, p)
    return jax.vmap(ggc, in_axes=(0, 0, 0, None, None))(
        keys, jnp.arange(N), cand_masks, flat_w, p)


def all_clients_bggc(key, flat_w, p, cand_masks, reward_fn, budget,
                     mix_impl: Optional[str] = None,
                     mesh=None, client_axes=None):
    """Batched-GGC preprocessing for every client as ONE traced program
    (vmap over k; the Algorithm-3 batch phases unroll at trace time), in
    place of N eager per-client `bggc` calls — jit the result once and
    every run reuses the compile. Selections are bitwise-identical to the
    sequential loop (same fold_in(key, k) streams; tested). With
    ``mesh``/``client_axes``, the vmap covers only shard-local k rows."""
    N = flat_w.shape[0]
    _count_probes(N, N)
    bggc = make_bggc(reward_fn, budget, mix_impl=mix_impl)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(N))
    if mesh is not None:
        return _shard_clients_graph(bggc, mesh, client_axes, keys,
                                    jnp.arange(N), cand_masks, flat_w, p)
    return jax.vmap(bggc, in_axes=(0, 0, 0, None, None))(
        keys, jnp.arange(N), cand_masks, flat_w, p)


# ------------------------------------------------- sparse neighbor lists
#
# Budget-sparse representation (DESIGN.md §12): the constrained greedy
# keeps |C_k| <= B, so the collaboration graph is stored as (N, B) int32
# neighbor-index lists (ascending global client ids, -1 = empty slot,
# self excluded — the Eq.-4 self term is implicit and always present)
# instead of (N, N) masks. Decisions, realized-download counts and wire
# bytes are identical integers in both layouts; only fp summation order
# differs in the mixing (§12 numerics).


def mask_to_neighbors(mask, k_idx, budget: int):
    """One client's (N,) bool selection mask -> (budget,) int32 neighbor
    list: the indices of the selected OFF-DIAGONAL peers in ascending
    order, -1 padding the unused slots. Lossless for selections of size
    <= budget — exactly what the budget-constrained greedy guarantees."""
    N = mask.shape[0]
    ar = jnp.arange(N)
    off = mask & (ar != k_idx)
    score = jnp.where(off, N - ar, 0)           # >0 iff selected, desc = asc ids
    vals, pos = jax.lax.top_k(score, min(budget, N))
    idx = jnp.where(vals > 0, pos, -1).astype(jnp.int32)
    if budget > N:
        idx = jnp.pad(idx, (0, budget - N), constant_values=-1)
    return idx


def neighbors_from_adjacency(adj, budget: int):
    """(N, N) bool adjacency -> (N, budget) int32 neighbor lists (row k =
    ascending off-diagonal peers of k, -1 pads). Inverse of
    `adjacency_from_neighbors` whenever every row has <= budget peers."""
    N = adj.shape[0]
    return jax.vmap(lambda row, k: mask_to_neighbors(row, k, budget))(
        jnp.asarray(adj, bool), jnp.arange(N))


def adjacency_from_neighbors(idx, n: int):
    """(N, B) int32 neighbor lists -> (N, n) bool adjacency with the
    diagonal forced True (every client collaborates with itself)."""
    N = idx.shape[0]
    rows = jnp.arange(N)[:, None]
    adj = jnp.zeros((N, n), bool).at[rows, jnp.clip(idx, 0, n - 1)].max(
        idx >= 0)
    return adj | jnp.eye(N, n, dtype=bool)


def count_neighbor_downloads(idx, active=None):
    """Realized model downloads encoded by neighbor lists ``idx`` (N, B):
    one download per non-sentinel slot, restricted (DESIGN.md §9) to
    available downloader/peer pairs when ``active`` ((N,) bool) is given.
    Integer-exact: equals the off-diagonal edge count of the equivalent
    dense adjacency, so dense and sparse comm accounting cannot drift."""
    N = idx.shape[0]
    valid = idx >= 0
    if active is not None:
        act = jnp.asarray(active, bool)
        valid = valid & act[:, None] & act[jnp.clip(idx, 0, N - 1)]
    return jnp.sum(valid)


def sparse_mixing_weights(idx, p, active=None):
    """Eq.-4 row weights in neighbor-list form. idx: (N, B) int32 lists
    (-1 = empty); p: (N,) fp32 client weights. Returns ``(self_w, nbr_w)``
    — (N,) and (N, B) fp32 with row k satisfying
    ``self_w[k] + sum_b nbr_w[k, b] = 1``: exactly the nonzero entries of
    `mixing_matrix`'s row k (diagonal forced on, p-weighted, normalized).

    ``active`` ((N,) bool) restricts to available downloader/peer pairs
    and renormalizes (DESIGN.md §9): an absent client's row is e_k. As in
    the dense path, ``active=None`` and an all-ones mask are bitwise
    identical (multiplying by 1.0 is exact)."""
    p, w = sparse_eq4_unnormalized(idx, p, active=active)
    denom = jnp.maximum(p + w.sum(axis=1), 1e-12)
    return p / denom, w / denom[:, None]


def sparse_eq4_unnormalized(idx, p, active=None):
    """Neighbor-list counterpart of `eq4_weights_unnormalized`: the
    Eq.-4 member weights before row normalization. Returns ``(p, w)`` —
    (N,) fp32 self weights and (N, B) fp32 peer weights (0 at empty or
    participation-masked slots); `sparse_mixing_weights` is exactly this
    pair divided by ``max(p + w.sum(1), 1e-12)``."""
    N, _ = idx.shape
    p = jnp.asarray(p, jnp.float32)
    w = (idx >= 0).astype(jnp.float32)
    safe = jnp.clip(idx, 0, N - 1)
    if active is not None:
        act = jnp.asarray(active, jnp.float32)
        w = w * act[:, None] * act[safe]
    w = w * p[safe]
    return p, w


@exchange_site(charges="caller")
def mix_flat_sparse(self_w, nbr_w, idx, flat_w, peers=None, *,
                    impl: Optional[str] = None, mesh=None,
                    client_axes=None):
    """Eq.-4 mix in neighbor-list form: gathers only the <= B selected
    peer rows per client instead of the dense (N, N) @ (N, P) matmul —
    O(N·B·P) work. ``peers`` (default ``flat_w``) is the peer-visible
    model table — the decoded payloads under compression, while the self
    term always reads the exact local row of ``flat_w`` (DESIGN.md §11).
    Dispatches through `kernels.ops.sparse_graph_mix`; the mesh path
    rotates peer panels shard-to-shard and keeps only requested rows
    rather than all-gathering the full (N, P) panel (DESIGN.md §12)."""
    return _kops.sparse_graph_mix(
        self_w, nbr_w, idx, flat_w,
        (flat_w if peers is None else peers,),
        impl=impl, mesh=mesh, client_axes=client_axes)


def make_ggc_sparse(reward_fn: Callable, budget: int, *,
                    mix_impl: Optional[str] = None):
    """GGC emitting a neighbor LIST: the scan visits only the <= B
    candidate slots (in the same seeded-permutation order as the dense
    scan) instead of all N clients — O(B) reward probes per client.

    Returns ``ggc(key, k_idx, cand_idx, flat_w, p, active=None)`` with
    cand_idx (B,) int32 = Omega_k as a neighbor list; the result is the
    selected C_k as a (B,) int32 ascending list (-1 pads). Because the
    coin-flip stream is keyed by the candidate's GLOBAL id and skipped
    non-candidates are exact no-ops of the dense scan, the selections are
    BITWISE identical to `make_ggc` on the equivalent mask (tested)."""
    step = greedy_decision_step(reward_fn)

    def ggc(key, k_idx, cand_idx, flat_w, p, active=None):
        N = flat_w.shape[0]
        B = cand_idx.shape[0]
        safe = jnp.clip(cand_idx, 0, N - 1)
        valid = (cand_idx >= 0) & (safe != k_idx)
        if active is not None:
            valid = valid & active[safe] & active[k_idx]
        # init running sums with the SAME masked row-matmul as the dense
        # path (the (N,) scatter is a per-client transient — the stacked
        # (N, B) output is what rides in state), so probes start bitwise
        # aligned with `make_ggc`
        cand_mask = jnp.zeros(N, bool).at[safe].max(valid)
        carry_full = _greedy_init(k_idx, cand_mask, flat_w, p,
                                  mix_impl=mix_impl)
        carry = GreedyCarry(
            maskX=jnp.zeros(B, bool), maskY=valid,
            wX=carry_full.wX, wY=carry_full.wY,
            pX=carry_full.pX, pY=carry_full.pY, nsel=jnp.int32(0))
        # visit candidate slots in dense-permutation order: position of
        # each global id in permutation(fold_in(key, 0), N)
        inv = jnp.argsort(jax.random.permutation(
            jax.random.fold_in(key, 0), N))
        visit = jnp.argsort(jnp.where(valid, inv[safe], N + safe))
        cand_w = flat_w[safe]                     # (B, P) gather
        p_c = p[safe]

        def body(carry, slot):
            j = safe[slot]
            return step(carry, j, cand_w[slot], key=key, k_idx=k_idx,
                        cand_mask=None, p=None, budget=jnp.int32(budget),
                        slot=slot, is_cand=valid[slot], p_j=p_c[slot]), None

        carry, _ = jax.lax.scan(body, carry, visit)
        # canonical output order: ascending global id, -1 slots last
        sel = jnp.where(carry.maskX, safe, N + safe)
        sel = jnp.sort(sel)
        return jnp.where(sel < N, sel, -1).astype(jnp.int32)

    return ggc


def all_clients_graph_sparse(key, flat_w, p, cand_idx, reward_fn,
                             budget: int, mix_impl: Optional[str] = None,
                             mesh=None, client_axes=None, active=None):
    """Sparse-repr graph construction for every client: candidates and
    selections are (N, B) neighbor lists, the (N, N) adjacency never
    materializes, and each client's greedy scan probes only its <= B
    candidates. Selections are bitwise-identical to `all_clients_graph`
    on the equivalent dense masks (tested). ``active`` restricts the
    candidate pool to available peers (absent-client handling — keeping
    the previous C_k — is the caller's, as in the dense path)."""
    N = flat_w.shape[0]
    _count_probes(N, cand_idx.shape[1])
    ggc = make_ggc_sparse(reward_fn, budget, mix_impl=mix_impl)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(N))
    extra = () if active is None else (active,)
    per_client = (ggc if active is None else
                  (lambda k_, ki, ci, w, pp, act: ggc(k_, ki, ci, w, pp,
                                                      active=act)))
    if mesh is not None:
        return _shard_clients_graph(per_client, mesh, client_axes, keys,
                                    jnp.arange(N), cand_idx, flat_w, p,
                                    extra=extra)
    return jax.vmap(per_client,
                    in_axes=(0, 0, 0, None, None) + (None,) * len(extra))(
                        keys, jnp.arange(N), cand_idx, flat_w, p, *extra)


def all_clients_bggc_sparse(key, flat_w, p, reward_fn, budget: int,
                            mix_impl: Optional[str] = None,
                            mesh=None, client_axes=None):
    """Batched-GGC preprocessing emitting (N, B) neighbor lists. The
    Algorithm-3 stream necessarily visits every peer (full candidacy),
    but the full-ones (N, N) candidate table of the dense entry point is
    replaced by a per-client transient, and the stacked output is the
    (N, budget) Omega list. Selections equal `all_clients_bggc` with a
    full candidate mask, bitwise (tested)."""
    N = flat_w.shape[0]
    _count_probes(N, N)
    bggc = make_bggc(reward_fn, budget, mix_impl=mix_impl)
    # list width: a client can select at most min(budget, N-1) peers, and
    # the round engine sizes every (N, B) buffer with the same clamp —
    # budget >= N must not widen the emitted lists past N-1
    width = max(1, min(budget, N - 1))

    def per_client(key_k, k_idx, _cand, w_full, p_full):
        mask = bggc(key_k, k_idx, jnp.arange(N) != k_idx, w_full, p_full)
        return mask_to_neighbors(mask, k_idx, width)

    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(N))
    dummy = jnp.zeros((N, 1), jnp.int32)    # unused candidate column
    if mesh is not None:
        return _shard_clients_graph(per_client, mesh, client_axes, keys,
                                    jnp.arange(N), dummy, flat_w, p)
    return jax.vmap(per_client, in_axes=(0, 0, 0, None, None))(
        keys, jnp.arange(N), dummy, flat_w, p)


def all_clients_graph_heterogeneous(key, flat_w, p, cand_masks, reward_fn,
                                    budgets, reachability=None,
                                    mix_impl: Optional[str] = None):
    """Per-client budgets + optional communicability restriction (both
    from the paper's §Limitations). budgets: (N,) int32; reachability:
    (N, N) bool — client k may only ever talk to reachable peers."""
    N = flat_w.shape[0]
    _count_probes(N, N)
    if reachability is not None:
        cand_masks = cand_masks & reachability
    ggc = make_ggc_heterogeneous(reward_fn, int(jnp.max(budgets)),
                                 mix_impl=mix_impl)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(N))
    return jax.vmap(ggc, in_axes=(0, 0, 0, None, None, 0))(
        keys, jnp.arange(N), cand_masks, flat_w, p,
        jnp.asarray(budgets, jnp.int32))
