"""Plain reference of what a DPFL round computes, in jax.numpy alone.

It imports nothing of the program. It follows the published algorithm
(DPFL, arXiv 2406.06520, Alg. 1-2, and the paper CNN of App. F.3.2) and
the seeded streams that make a run repeatable: the same seed gives the
same initial model, minibatch order, candidate order and coin flips as
the system under test, so the two can be compared step for step.

- `init_panel`: every client starts from one model drawn from the seed;
- `train`: tau epochs of minibatch SGD (momentum 0.9, weight decay) per
  client, a fresh optimizer state per call;
- `mix`: Eq. 4, the p-weighted average over C_k u {k};
- `evaluate`: validation accuracy of each client's model;
- `ggc`: the double-greedy graph refresh (Alg. 2) over each client's
  candidate list. With ``forced`` it replays decisions made elsewhere and
  returns how far each one lies from the coin flip that the reference's
  own rewards call for.

Graphs are (N, B) int32 neighbor lists: ascending peer ids, -1 pads, the
client itself implicit. Every function takes ``dt`` (parameter and
activation type) and ``prec`` (the precision of the model's matmuls and
convolutions): float32 at the precision the configuration states
(``matmul_precision``) is the reference, and bfloat16 at the default
precision is the control, the step below that float32. Averages of
models (the mix, the refresh's probes) are exact float32 sums at any
``prec``, as the algorithm states them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT
# a configuration's ``matmul_precision`` -> the precision of every dot
PRECISIONS = {"default": DEFAULT, "high": jax.lax.Precision.HIGH,
              "highest": HIGHEST}
# images whose activations the evaluation and the refresh hold at once
IMAGES_AT_ONCE = 4000


# ----------------------------------------------------------------- model


def param_shapes(model: dict) -> dict:
    """The paper CNN's parameter shapes, sorted by name (the order in
    which a flattened parameter vector lays them out)."""
    c_in, c1, c2 = model["in_channels"], model["c1"], model["c2"]
    s = ((model["image_size"] - 4) // 2 - 4) // 2
    shapes = {
        "conv1_w": (5, 5, c_in, c1), "conv1_b": (c1,),
        "conv2_w": (5, 5, c1, c2), "conv2_b": (c2,),
        "fc1_w": (s * s * c2, model["fc1"]), "fc1_b": (model["fc1"],),
        "fc2_w": (model["fc1"], model["fc2"]), "fc2_b": (model["fc2"],),
        "out_w": (model["fc2"], model["n_classes"]),
        "out_b": (model["n_classes"],),
    }
    return dict(sorted(shapes.items()))


def leaf_slices(model: dict) -> dict:
    """name -> slice of that leaf in a flattened parameter vector."""
    out, at = {}, 0
    for name, shape in param_shapes(model).items():
        n = math.prod(shape)
        out[name] = slice(at, at + n)
        at += n
    return out


def unflatten(model: dict, flat):
    """(..., P) -> dict of (..., *shape)."""
    lead = flat.shape[:-1]
    return {name: flat[..., sl].reshape(lead + param_shapes(model)[name])
            for name, sl in leaf_slices(model).items()}


def flatten(model: dict, params):
    lead = params["conv1_b"].shape[:-1]
    return jnp.concatenate([params[k].reshape(lead + (-1,))
                            for k in param_shapes(model)], axis=-1)


def init_model(model: dict, key):
    """One model from ``key``: the five weight matrices are N(0, 1)
    draws from split(key, 5), scaled by 0.1 for the convolutions and by
    1/sqrt(fan_in) for the dense layers; biases start at zero."""
    ks = jax.random.split(key, 5)
    shapes = param_shapes(model)
    params = {}
    for i, name in enumerate(("conv1", "conv2", "fc1", "fc2", "out")):
        shape = shapes[name + "_w"]
        scale = 0.1 if name.startswith("conv") else 1.0 / math.sqrt(shape[0])
        params[name + "_w"] = jax.random.normal(ks[i], shape) * scale
        params[name + "_b"] = jnp.zeros(shapes[name + "_b"], jnp.float32)
    return params


def conv5(h, w, b, prec):
    """VALID 5x5 convolution, stride 1, of ``h`` (B, H, W, C) with ``w``
    (5, 5, C, O), at ``prec``."""
    return jax.lax.conv_general_dilated(
        h, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=prec) + b


def logits(params, x, prec):
    def pool(h):
        return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    h = pool(jax.nn.relu(conv5(x, params["conv1_w"], params["conv1_b"],
                               prec)))
    h = pool(jax.nn.relu(conv5(h, params["conv2_w"], params["conv2_b"],
                               prec)))
    h = h.reshape(h.shape[0], -1)
    for name in ("fc1", "fc2"):
        h = jax.nn.relu(jnp.dot(h, params[name + "_w"], precision=prec)
                        + params[name + "_b"])
    return jnp.dot(h, params["out_w"], precision=prec) + params["out_b"]


def loss(params, x, y, prec):
    """Mean cross-entropy."""
    logp = jax.nn.log_softmax(logits(params, x, prec))
    return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0].mean()


# ------------------------------------------------------------- algorithm


def run_keys(seed: int):
    """(k_init, k_pre, k_graph, k_train): the run's four streams."""
    return jax.random.split(jax.random.PRNGKey(seed), 4)


def init_panel(model: dict, key, n_clients: int, dt):
    """(N, P): every client holds the same model drawn from ``key``."""
    flat = flatten(model, init_model(model, key)).astype(dt)
    return jnp.broadcast_to(flat, (n_clients,) + flat.shape)


def train(model: dict, opt: dict, panel, x, y, key, epochs: int, dt,
          prec):
    """``epochs`` of minibatch SGD for every client. Client c draws
    split(key, N)[c]; epoch e of it draws split(that, epochs)[e] and
    visits the first floor(n/bs)*bs rows of a permutation of its data."""
    bs = opt["batch_size"]
    lr, mom, wd = (jnp.asarray(opt[k], dt)
                   for k in ("lr", "momentum", "weight_decay"))
    n = x.shape[1]
    nb = n // bs

    def one_client(flat, xc, yc, kc):
        def step(carry, b):
            p, mu = carry
            g = jax.grad(lambda q: loss(unflatten(model, q),
                                        b[0], b[1], prec))(p)
            g = g + wd * p
            mu = mom * mu + g
            return (p - lr * mu, mu), None

        def epoch(carry, ek):
            perm = jax.random.permutation(ek, n)[:nb * bs].reshape(nb, bs)

            def batch(carry, idx):
                return step(carry, (xc[idx].astype(dt), yc[idx]))

            return jax.lax.scan(batch, carry, perm)[0], None

        (flat, _), _ = jax.lax.scan(epoch, (flat, jnp.zeros_like(flat)),
                                    jax.random.split(kc, epochs))
        return flat

    keys = jax.random.split(key, panel.shape[0])
    return jax.vmap(one_client)(panel, x, y, keys)


def _rows(graph, n: int):
    """(N, B) lists -> (N, B+1) member rows [k, peers...] (pads -> k) and
    a (N, B+1) membership mask."""
    k = jnp.arange(n)[:, None]
    rows = jnp.concatenate([k, jnp.where(graph >= 0, graph, k)], axis=1)
    member = jnp.concatenate([jnp.ones_like(k, bool), graph >= 0], axis=1)
    return rows, member


def mix(panel, graph, p, dt):
    """Eq. 4 over (N, B) lists: out_k = sum_{i in C_k u {k}} p_i w_i /
    sum p_i, as a per-member sum (no (N, N) operator)."""
    rows, member = _rows(graph, panel.shape[0])
    w = jnp.where(member, p[rows], 0.0).astype(dt)
    w = w / w.sum(1, keepdims=True)
    out = jnp.zeros_like(panel)
    for b in range(rows.shape[1]):
        out = out + w[:, b, None] * panel[rows[:, b]]
    return out


def evaluate(model: dict, panel, vx, vy, dt, prec):
    """(N,) validation accuracy of each client's own model."""
    def one(args):
        flat, xc, yc = args
        lg = logits(unflatten(model, flat), xc.astype(dt), prec)
        return (jnp.argmax(lg, -1) == yc).mean()

    return jax.lax.map(one, (panel, vx, vy),
                       batch_size=max(1, IMAGES_AT_ONCE // vx.shape[1]))


def ggc(model: dict, panel, omega, p, vx, vy, key, budget: int, dt, prec,
        forced=None):
    """The GGC refresh of every client over its candidate list ``omega``.

    Client k draws key_k = fold_in(key, k); it visits its candidates in
    the order of permutation(fold_in(key_k, 0), N), and candidate j's coin
    is uniform(fold_in(key_k, j + 1)). At each candidate the four rewards
    -loss_k(avg X), -loss_k(avg X+j), -loss_k(avg Y), -loss_k(avg Y-j)
    give a = max(R(X+j) - R(X), 0), b = max(R(Y-j) - R(Y), 0) and
    prob = a / (a + b) (1 when both are 0): j joins X when coin < prob
    and |X \\ {k}| < budget, and leaves Y when coin >= prob.

    Without ``forced``, returns the selected (N, B) lists. With
    ``forced`` ((N, B) lists selected elsewhere), each visited candidate
    before the budget fills takes the forced decision (joined X iff it is
    in the forced list), and returns ((gain_x, gain_y, coin, joined,
    decided), stray): per visited slot the reference's R(X+j) - R(X) and
    R(Y-j) - R(Y), the coin, the forced decision and whether the budget
    left it open; and per forced peer whether it is not a candidate."""
    n, width = omega.shape
    rows, member = _rows(omega, n)
    valid = member[:, 1:] & (omega != jnp.arange(n)[:, None])
    pr = p[rows].astype(jnp.float32)

    def one(args):
        key_k, k, rows_k, valid_k, pr_k, xk, yk, forced_k = args
        order = jnp.argsort(jax.random.permutation(
            jax.random.fold_in(key_k, 0), n))
        cand = rows_k[1:]
        visit = jnp.argsort(jnp.where(valid_k, order[cand], n + cand))
        coins = jax.vmap(lambda j: jax.random.uniform(
            jax.random.fold_in(key_k, j + 1)))(cand)
        if forced_k is not None:
            want = (cand[:, None] == forced_k[None, :]).any(1) & valid_k
        members = panel[rows_k]                       # (B+1, P)

        def reward(weights):
            w = (weights / weights.sum()).astype(dt)
            probe = jnp.einsum("r,rp->p", w, members, precision=HIGHEST)
            return -loss(unflatten(model, probe), xk.astype(dt), yk, prec)

        def step(carry, slot):
            in_x, in_y, nsel = carry
            e = jnp.zeros(width + 1, bool).at[slot + 1].set(True)
            wx = jnp.where(in_x, pr_k, 0.0)
            wy = jnp.where(in_y, pr_k, 0.0)
            r = jax.vmap(reward)(jnp.stack([
                wx, jnp.where(in_x | e, pr_k, 0.0),
                wy, jnp.where(in_y & ~e, pr_k, 0.0)]))
            a = jnp.maximum(r[1] - r[0], 0.0)
            b = jnp.maximum(r[3] - r[2], 0.0)
            prob = jnp.where(a + b > 0, a / (a + b), 1.0).astype(jnp.float32)
            u = coins[slot]
            ok = valid_k[slot]
            open_ = nsel < budget
            if forced_k is None:
                add = (u < prob) & ok & open_
                rem = ~(u < prob) & ok
                joined = add
            else:
                joined = want[slot]
                add = joined & open_
                rem = ~joined & ok & (open_ | ~(u < prob))
            return (in_x | (e & add), in_y & ~(e & rem),
                    nsel + add.astype(jnp.int32)), (
                r[1] - r[0], r[3] - r[2], u, joined, ok & open_)

        init = (jnp.zeros(width + 1, bool).at[0].set(True),
                jnp.concatenate([jnp.ones(1, bool), valid_k]), jnp.int32(0))
        (in_x, _, _), decisions = jax.lax.scan(step, init, visit)
        if forced_k is not None:
            # a forced peer that is not a candidate of k is never allowed
            stray = (forced_k >= 0) & ~(forced_k[:, None]
                                        == jnp.where(valid_k, cand, -2)
                                        [None, :]).any(1)
            return decisions, stray
        sel = jnp.where(in_x[1:], cand, n + cand)
        sel = jnp.sort(sel)
        return jnp.where(sel < n, sel, -1).astype(jnp.int32)

    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    # clients in blocks, so that one block's probe activations are held
    return jax.lax.map(
        one, (keys, jnp.arange(n), rows, valid, pr, vx, vy, forced),
        batch_size=max(1, IMAGES_AT_ONCE // (4 * vx.shape[1])))


def _prob(x, y):
    a, b = np.maximum(x, 0.0), np.maximum(y, 0.0)
    with np.errstate(invalid="ignore", divide="ignore"):
        return np.where(a + b > 0, a / (a + b), 1.0)


def reward_gap(gain_x, gain_y, coin, joined, iters: int = 60):
    """Per decision, the least delta such that rewards each moved by at
    most delta (so each gain by at most 2 delta) make the double greedy
    take the decision ``joined`` under ``coin``: 0 where the reference's
    own rewards take it. It reads in the rewards' unit (nats of
    validation loss), so a decision whose gains are near 0, where the
    coin's threshold a/(a+b) swings on rounding, reads near 0."""
    x, y = (np.asarray(v, np.float64) for v in (gain_x, gain_y))
    u = np.asarray(coin, np.float64)
    j = np.asarray(joined, bool)

    def agrees(d):
        add = u < _prob(x + 2 * d, y - 2 * d)
        rem = u >= _prob(x - 2 * d, y + 2 * d)
        return np.where(j, add, rem)

    lo = np.zeros_like(x)
    hi = np.abs(x) + np.abs(y) + 1.0
    done = agrees(lo)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        ok = agrees(mid)
        hi = np.where(ok, mid, hi)
        lo = np.where(ok, lo, mid)
    return np.where(done, 0.0, hi)


def follow(model: dict, opt: dict, data: dict, p, seed: int, omega,
           graphs, rounds: int, budget: int, tau_init: int, tau: int,
           prec, dt=jnp.float32, period: int = 1):
    """The run from ``seed``: the initial model, ``tau_init`` epochs,
    the mix over ``omega``, then ``rounds`` rounds of ``tau`` epochs,
    refresh, mix and evaluation. ``data`` holds device arrays "train_x",
    "train_y", "val_x", "val_y"; graphs are (N, B) lists.

    With ``graphs`` (one per round), each round's decisions are replayed
    from them and their gaps returned; with None, the refreshes decide
    themselves and the graphs they chose are returned. Returns a dict of
    numpy arrays: "S0" (the panel round 1 starts from), "S" and "val" per
    round, "graphs" per round, "best" (each client's best accuracy) and,
    when replaying, "gaps" per round."""
    k_init, k_pre, k_graph, k_train = run_keys(seed)
    tx, ty, vx, vy = (data[k] for k in ("train_x", "train_y", "val_x",
                                        "val_y"))
    n = tx.shape[0]
    p = jnp.asarray(p, jnp.float32)
    fit = jax.jit(lambda pan, x, y, k, epochs: train(
        model, opt, pan, x, y, k, epochs, dt, prec),
        static_argnums=(4,))
    blend = jax.jit(lambda pan, g: mix(pan, g, p, dt))
    score = jax.jit(lambda pan, x, y: evaluate(model, pan, x, y, dt, prec))
    refresh = jax.jit(lambda pan, om, x, y, k, f: ggc(
        model, pan, om, p, x, y, k, budget, dt, prec, f))
    omega = jnp.asarray(omega)
    panel = init_panel(model, k_init, n, dt)
    panel = blend(fit(panel, tx, ty, k_pre, tau_init), omega)
    out = {"S0": np.asarray(panel, np.float32), "S": [], "val": [],
           "graphs": [], "gaps": []}
    prev = omega
    for t in range(rounds):
        trained = fit(panel, tx, ty, jax.random.fold_in(k_train, t), tau)
        key = jax.random.fold_in(k_graph, 1000 + t)
        if graphs is None:
            g = refresh(trained, omega, vx, vy, key, None) \
                if t % period == 0 else prev
        else:
            g = jnp.asarray(graphs[t])
            if t % period == 0:
                (gx, gy, u, joined, decided), stray = jax.device_get(
                    refresh(trained, omega, vx, vy, key, g))
                gaps = reward_gap(gx, gy, u, joined)[decided]
                out["gaps"].append(np.concatenate(
                    [gaps, np.where(stray[stray], np.inf, 0.0)]))
            else:
                # no refresh: the graph must stay as it was
                same = bool(jnp.all(g == prev))
                out["gaps"].append(np.array([0.0 if same else np.inf]))
        prev = g
        panel = blend(trained, g)
        out["S"].append(np.asarray(panel, np.float32))
        out["val"].append(np.asarray(score(panel, vx, vy), np.float32))
        out["graphs"].append(np.asarray(g))
    out["best"] = np.max(out["val"], axis=0)
    return out
