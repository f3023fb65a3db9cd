"""Stacked-client federated simulation engine.

All N client models live in one pytree with leading client axis; local
training is vmapped; aggregation is a mixing-matrix einsum (optionally the
Pallas graph_mix kernel on flattened params). This is the TPU-native
reformulation of the paper's sequential single-GPU client loop (DESIGN.md
§3) — `shard_clients` commits the client axis to mesh axes (production:
('pod', 'data')), after which local training and evaluation compile
shard-local and only the graph ops communicate (DESIGN.md §8).
"""
from __future__ import annotations

import contextlib
import inspect
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..analysis.counters import collecting
from ..models.classifier import accuracy as _acc
from ..models.classifier import xent_loss as _xent
from ..optim import Optimizer, sgd


class FLEngine:
    def __init__(self, model, data, lr: float = 0.05, momentum: float = 0.9,
                 weight_decay: float = 1e-3, batch_size: int = 16,
                 loss_fn: Optional[Callable] = None,
                 acc_fn: Optional[Callable] = None,
                 mesh=None, client_axes=None):
        self.model = model
        self.data = data
        self.batch_size = min(batch_size, data.train_x.shape[1])
        self.opt: Optimizer = sgd(lr, momentum=momentum,
                                  weight_decay=weight_decay)
        self.loss_fn = loss_fn or (lambda p, b: _xent(model, p, b))
        self.acc_fn = acc_fn or (lambda p, b: _acc(model, p, b))
        self.p = jnp.asarray(data.p, jnp.float32)
        # flatten/unflatten for graph ops
        example = model.init(jax.random.PRNGKey(0))
        flat, self._unravel = ravel_pytree(example)
        self.n_params = flat.shape[0]
        self.mesh = None
        self.client_axes = None
        if mesh is not None:
            self.shard_clients(mesh, client_axes)
        else:
            self._build()

    # ----------------------------------------------------------- sharding
    def shard_clients(self, mesh, client_axes=None):
        """Commit the client axis to ``client_axes`` of ``mesh`` (default:
        whichever of ('pod', 'data') the mesh has). Re-places the client
        data on the mesh and rebuilds the traced fns, which then train and
        evaluate inside a client `shard_map` (`map_clients`). N must
        divide the product of the client axis sizes."""
        if client_axes is None:
            client_axes = tuple(a for a in ("pod", "data")
                                if a in mesh.axis_names)
        from ..sharding.compat import mesh_axis_sizes
        self.mesh = mesh
        self.client_axes = tuple(client_axes)
        n_shards = 1
        for a in self.client_axes:
            n_shards *= mesh_axis_sizes(mesh)[a]
        if self.data.n_clients % n_shards:
            raise ValueError(
                f"n_clients={self.data.n_clients} not divisible by the "
                f"{n_shards} client shards of axes {self.client_axes}")
        self._build()
        return self

    def client_spec(self, ndim: int = 2) -> P:
        """PartitionSpec sharding axis 0 over the client mesh axes."""
        ca = self.client_axes if self.client_axes else ("pod", "data")
        return P(ca, *((None,) * (ndim - 1)))

    def map_clients(self, fn, *args):
        """``jax.vmap(fn)(*args)`` over the leading client axis of every
        leaf of ``args``. On a mesh the vmap runs inside a `jax.shard_map`
        over the client axes, so each device trains and evaluates only
        its own clients: left to the SPMD partitioner, a vmapped
        convolution becomes a grouped one that is partitioned by
        all-gathering every client's activations."""
        vf = jax.vmap(fn)
        if self.mesh is None:
            return vf(*args)

        def specs(tree):
            return jax.tree.map(lambda a: self.client_spec(a.ndim), tree)

        # check_vma=False: the body has no collectives, and its scans
        # start from per-client constants (optimizer state) that the
        # varying-axes check would have to pcast one by one
        return jax.shard_map(
            vf, mesh=self.mesh, in_specs=specs(args),
            out_specs=specs(jax.eval_shape(vf, *args)),
            check_vma=False)(*args)

    # ------------------------------------------------------------ plumbing
    def init_clients(self, key):
        """Same init for all clients (paper Alg. 1: every local model starts
        from w)."""
        params = self.model.init(key)
        N = self.data.n_clients
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (N,) + a.shape).copy(),
            params)

    def flatten(self, stacked):
        """Client-stacked pytree (leaves (N, ...)) -> (N, P) fp32 rows
        (P = `n_params`), the layout every graph op mixes in."""
        return jax.vmap(lambda t: ravel_pytree(t)[0])(stacked)

    def unflatten(self, flat):
        """(N, P) flattened rows -> client-stacked pytree; exact inverse
        of `flatten` (ravel_pytree round trip, dtypes restored)."""
        return jax.vmap(self._unravel)(flat)

    def _device_data(self, arr, replicated: bool = False):
        """Upload a client-stacked data array ONCE: device-resident, and
        placed on the client mesh when the engine is sharded — split over
        the client axes, or whole on every device with ``replicated``."""
        a = jnp.asarray(arr)
        if self.mesh is None:
            return a
        spec = P() if replicated else self.client_spec(a.ndim)
        return jax.device_put(a, NamedSharding(self.mesh, spec))

    def client_data(self):
        """The client-stacked arrays the traceable fns read, as
        ``{"train": (x, y), "val": (x, y)}``. Inside a function compiled
        by `jit` these are that function's arguments; elsewhere they are
        the device-resident arrays. Traceable code reads them here at
        trace time and never closes over them, so the dataset is an input
        of every compiled program and not a constant folded into it.

        Raises when read under a trace that `jit` did not start (a plain
        ``jax.jit``, ``vmap`` or ``scan`` over an engine fn): there the
        arrays would be folded into the traced program as constants."""
        if self._bound is not None:
            return self._bound
        if not jax.core.trace_ctx.is_top_level():
            raise RuntimeError(
                "FLEngine.client_data() read under a trace that "
                "FLEngine.jit did not start: the dataset would be compiled "
                "into the program as constants. Trace the function with "
                "engine.jit instead of jax.jit.")
        return self._data

    @contextlib.contextmanager
    def _bind(self, data):
        prev, self._bound = self._bound, data
        try:
            yield
        finally:
            self._bound = prev

    def jit(self, fn, *, donate_argnums=(), in_shardings=None,
            out_shardings=None, static_argnames=()):
        """`jax.jit` of ``fn`` that takes the client data (`client_data`)
        as an extra, never-donated argument: see `DataJit`."""
        return DataJit(self, fn, donate_argnums=donate_argnums,
                       in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       static_argnames=static_argnames)

    def _build(self):
        """Builds the raw traceable fns (`train_fn`, `eval_split_fn`,
        `eval_val_fn` — composed into the compiled round engine, DESIGN.md
        §5) and their standalone jitted wrappers (`local_train`,
        `_eval_split`), plus the device-resident (mesh-placed) train/val/
        test arrays — hoisted here so no per-call ``jnp.asarray`` ever
        re-uploads them at dispatch time. Validation is whole on every
        device of a mesh: the GGC reward probes read it from inside the
        client `shard_map`, where a client-split array would be
        all-gathered on every refresh."""
        model, opt = self.model, self.opt
        bs = self.batch_size
        loss_fn = self.loss_fn

        def sgd_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
            return params, opt_state, loss

        def one_client_epochs(params, x, y, key, epochs):
            n = x.shape[0]
            nb = n // bs
            opt_state = opt.init(params)

            def epoch(carry, ekey):
                params, opt_state = carry
                perm = jax.random.permutation(ekey, n)
                xb = x[perm[: nb * bs]].reshape((nb, bs) + x.shape[1:])
                yb = y[perm[: nb * bs]].reshape((nb, bs) + y.shape[1:])

                def step(c, b):
                    p, o = c
                    p, o, l = sgd_step(p, o, {"x": b[0], "y": b[1]})
                    return (p, o), l

                (params, opt_state), losses = jax.lax.scan(
                    step, (params, opt_state), (xb, yb))
                return (params, opt_state), losses.mean()

            (params, _), losses = jax.lax.scan(
                epoch, (params, opt_state), jax.random.split(key, epochs))
            return params, losses.mean()

        self.train_data = (self._device_data(self.data.train_x),
                           self._device_data(self.data.train_y))
        self.val_data = (self._device_data(self.data.val_x, True),
                         self._device_data(self.data.val_y, True))
        self.test_data = (self._device_data(self.data.test_x),
                          self._device_data(self.data.test_y))
        self._data = {"train": self.train_data, "val": self.val_data}
        self._bound = None

        def train_fn_with_labels(stacked, key, epochs, ys):
            keys = jax.random.split(key, self.data.n_clients)
            return self.map_clients(
                lambda p, x, y, k: one_client_epochs(p, x, y, k, epochs),
                stacked, self.client_data()["train"][0], ys, keys)

        # label-parameterized variant for data-level attacks (DESIGN.md
        # §15): same trace, with the (N, n_train) label table an argument
        self.train_fn_with_labels = train_fn_with_labels

        def train_fn(stacked, key, epochs):
            return train_fn_with_labels(stacked, key, epochs,
                                        self.client_data()["train"][1])

        self.train_fn = train_fn
        # local_train(stacked, key, epochs) -> (stacked', (N,) mean loss):
        # `epochs` seeded epochs of minibatch SGD vmapped over clients
        # (stacked leaves (N, ...); per-client streams fold_in by row)
        self.local_train = self.jit(train_fn, static_argnames=("epochs",))
        self.local_train_with_labels = self.jit(
            train_fn_with_labels, static_argnames=("epochs",))

        def eval_split_fn(stacked, xs, ys):
            def one(p, x, y):
                batch = {"x": x, "y": y}
                return self.acc_fn(p, batch), loss_fn(p, batch)

            return self.map_clients(one, stacked, xs, ys)

        self.eval_split_fn = eval_split_fn
        self._eval_split = jax.jit(eval_split_fn)

        def eval_val_fn(stacked):
            return eval_split_fn(stacked, *self.client_data()["val"])

        self.eval_val_fn = eval_val_fn

    # ------------------------------------------------------------- metrics
    def eval_val(self, stacked):
        """Per-client validation metrics of a stacked pytree: returns
        ``(acc (N,) fp32, loss (N,) fp32)`` — each client evaluated on
        its own (device-resident) validation split."""
        return self._eval_split(stacked, *self.val_data)

    def eval_test(self, stacked):
        """Per-client test metrics, same contract as `eval_val`."""
        return self._eval_split(stacked, *self.test_data)

    def make_reward_fn(self):
        """reward(flat_params, k) = -validation loss of client k (Eq. 7)."""
        unravel = self._unravel
        loss_fn = self.loss_fn

        def reward(flat, k):
            val_x, val_y = self.client_data()["val"]
            return -loss_fn(unravel(flat), {"x": val_x[k], "y": val_y[k]})

        return reward


class DataJit:
    """``jax.jit(fn)`` whose compiled program takes the engine's client
    data (`FLEngine.client_data`) as a leading argument that is never
    donated, bound for the duration of ``fn``'s trace. Called like ``fn``
    (``__call__``, ``lower`` and ``eval_shape`` pass the data themselves),
    so a ``round_step`` built on it keeps the ``round_step(state)``
    contract, and the dataset is neither re-uploaded per dispatch nor
    compiled into the program as constants. ``donate_argnums`` and
    ``in_shardings`` count ``fn``'s own positional arguments; on a mesh
    the data keeps the
    shardings `FLEngine` placed it with.

    The compiled program takes ``fn``'s name (``jit_round_step``,
    ``jit_train_fn``, ...), which names its module in a profiler trace.
    ``counts`` holds the work its latest trace counted
    (`repro.analysis.counters`), e.g. ``counts["ggc.probes"]``: the GGC
    reward probes one call executes. The counts are static (from shapes,
    at trace time), and only the latest trace's are kept: a program
    traced at two signatures reports the one traced last."""

    def __init__(self, engine, fn, *, donate_argnums=(), in_shardings=None,
                 out_shardings=None, static_argnames=()):
        self.engine = engine
        self.counts = {}

        def with_data(data, *args, **kwargs):
            counts = {}
            with engine._bind(data), collecting(counts):
                out = fn(*args, **kwargs)
            self.counts = counts
            return out

        with_data.__name__ = with_data.__qualname__ = getattr(
            fn, "__name__", "with_data")
        # fn's own signature behind the data argument, so static
        # arguments resolve by name and position as they would for fn
        sig = inspect.signature(fn)
        with_data.__signature__ = sig.replace(parameters=[
            inspect.Parameter("data", inspect.Parameter.POSITIONAL_ONLY),
            *sig.parameters.values()])
        kw = {"donate_argnums": tuple(i + 1 for i in donate_argnums),
              "static_argnames": static_argnames}
        if in_shardings is not None:
            data_sh = jax.tree.map(lambda a: a.sharding, engine._data)
            kw["in_shardings"] = (data_sh,) + tuple(in_shardings)
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        self._jit = jax.jit(with_data, **kw)

    def __call__(self, *args, **kwargs):
        return self._jit(self.engine.client_data(), *args, **kwargs)

    def lower(self, *args, **kwargs):
        return self._jit.lower(self.engine.client_data(), *args, **kwargs)

    def eval_shape(self, *args, **kwargs):
        return self._jit.eval_shape(self.engine.client_data(), *args,
                                    **kwargs)

    def _cache_size(self) -> int:
        return self._jit._cache_size()
