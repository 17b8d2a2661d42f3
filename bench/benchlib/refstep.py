"""The plain reference of the timed training path, and the readings that
decide ``correct``.

One D-SGD step of n nodes, written from its definition:

    m_i  <- beta * m_i + grad_i(theta_i)          (float32)
    x_i  <- bf16(theta_i - lr * m_i)              (the configuration's dtype)
    theta_i <- bf16(sum_j W_ij x_j)               (no mixing when n == 1)

with each node's gradient from the family's float32 reference model on that
node's rows.  Parameters live on the device in float32 holding bfloat16
values, so the gradient is a float32 gradient; momentum waits on the host
between steps, leaf by leaf, so that parameters, gradients and momentum
never share one chip's memory at once.

Readings, the same for the program and the reference, per node:
  losses        each step's loss
  grad_norms    per leaf, the norm of the first step's gradient
  change_norms  per leaf, the norm of theta after the steps minus theta_0
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from benchlib import data
from benchlib.precision import EINSUMS

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def round_to(x, dtype):
    """float32 ``x`` rounded to nearest (ties to even) at ``dtype``'s
    precision, kept in float32.  Done on the bits: a compiler may drop a
    convert pair, or a reduce-precision, as excess precision."""
    if jnp.finfo(dtype).nmant >= jnp.finfo(jnp.float32).nmant:
        return x
    if dtype != jnp.bfloat16:
        raise ValueError(f"no rounding to {dtype}")
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def mixing_matrix(topology: str, n: int) -> np.ndarray:
    """W of the named static topology.  ``d_ring``: each node averages itself
    and its two ring neighbours with weight 1/3 (Metropolis weights)."""
    if n == 1:
        return np.ones((1, 1))
    if topology != "d_ring":
        raise ValueError(f"no reference mixing matrix for topology {topology!r}")
    w = np.zeros((n, n))
    for i in range(n):
        for j in {i, (i + 1) % n, (i - 1) % n}:
            w[i, j] = 1.0 / len({i, (i + 1) % n, (i - 1) % n})
    return w


def degree(cell: dict) -> int:
    """Neighbours per node of the cell's topology."""
    w = mixing_matrix(cell["topology"], cell["mesh"][0])
    return int(np.count_nonzero(w[0])) - 1


def lr_of(cell: dict) -> float:
    """The step size as the training CLI derives it from ``--lr`` and
    ``--lr-scaling``: the base rate times sqrt (or the first power) of one
    plus the graph degree, with the base batch equal to the global batch."""
    s = degree(cell) + 1
    return cell["lr"] * {"none": 1.0, "sqrt": s ** 0.5, "linear": s}[cell["lr_scaling"]]


def leaf_norms(tree, stacked: bool):
    """(nodes, leaves) float32 norms of each leaf over its non-node axes."""
    def one(x):
        x = x.astype(jnp.float32)
        if not stacked:
            x = x[None]
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))

    return jnp.stack([one(x) for x in jax.tree.leaves(tree)], axis=1)


def change_norms(params, theta0, stacked: bool):
    return leaf_norms(
        jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                     params, theta0), stacked)


class Reference:
    """The family's reference model run as the cell's n-node D-SGD job."""

    def __init__(self, family_mod, cfg: dict, traffic: dict, cell: dict, *,
                 devices, precision: str = "f32", fault: str | None = None):
        """``fault`` plants one of the faults the check must catch, in the
        reference put in the program's place: ``half_batch`` (half of each
        node's rows, or of its positions when it has one row, left out of
        the mean), ``no_exchange`` (no mixing between nodes),
        ``leaf_dropped`` (the largest leaf never updated)."""
        self.fault = fault
        self.mod = family_mod
        self.cfg = cfg
        self.traffic = traffic
        self.n = cell["mesh"][0]
        self.lr = lr_of(cell)
        self.beta = cell["momentum"]
        self.dtype = DTYPES[cfg["dtype"]]
        self.w = mixing_matrix(cell["topology"], self.n)
        if fault == "no_exchange":
            self.w = np.eye(self.n)
        self.ein = EINSUMS[precision]
        self.mesh = Mesh(np.asarray(devices[: self.n]), ("node",))
        self.node = NamedSharding(self.mesh, P("node"))

    def init(self, seed: int):
        """theta_0 of every node, (n, ...) in the configuration's dtype."""
        n, mod, cfg, dt = self.n, self.mod, self.cfg, self.dtype

        def make(key):
            p = mod.init(cfg, key, dt)
            return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), p)

        return jax.jit(make, out_shardings=self.node)(seed_key(seed))

    def batch(self, step: int, seed: int):
        rows = data.stacked(self.traffic, self.cfg["vocab_size"], self.n, step, seed)
        if self.fault == "half_batch":
            t = rows["targets"]
            if t.shape[1] > 1:
                t[:, t.shape[1] // 2:] = -1
            else:
                t[..., t.shape[2] // 2:] = -1
        return jax.device_put(rows, self.node)

    def run(self, seed: int, steps: int = 3) -> dict:
        """Readings of the first ``steps`` steps from ``seed``."""
        mod, cfg, ein = self.mod, self.cfg, self.ein
        grad_fn = jax.jit(jax.vmap(jax.value_and_grad(
            lambda p, b: mod.loss(p, b, cfg, ein))))
        dt = self.dtype
        to_dtype = jax.jit(lambda x: x.astype(jnp.float32))
        lr, beta = jnp.float32(self.lr), jnp.float32(self.beta)

        @jax.jit
        def update(p, g, m):
            m = beta * m + g
            return round_to(p - lr * m, dt), m

        @jax.jit
        def first_update(p, g):     # momentum starts at zero
            return round_to(p - lr * g, dt), g

        coefs = [(s, np.asarray([self.w[i, (i + s) % self.n] for i in range(self.n)],
                                np.float32))
                 for s in range(self.n)]
        coefs = [(s, c) for s, c in coefs if c.any()]

        @jax.jit
        def mix(x):
            acc = 0.0
            for s, c in coefs:
                y = x if s == 0 else jnp.roll(x, -s, axis=0)
                acc = acc + c.reshape((-1,) + (1,) * (x.ndim - 1)) * y
            return round_to(acc, dt)

        params = jax.tree.map(to_dtype, self.init(seed))
        leaves, treedef = jax.tree.flatten(params)
        del params
        moms = [None] * len(leaves)
        frozen = (int(np.argmax([x.size for x in leaves]))
                  if self.fault == "leaf_dropped" else -1)
        losses, grad_norms = [], None
        with jax.default_matmul_precision("highest"):
            for t in range(steps):
                loss, grads = grad_fn(jax.tree.unflatten(treedef, leaves),
                                      self.batch(t, seed))
                losses.append(np.asarray(loss))
                if t == 0:
                    grad_norms = np.asarray(leaf_norms(grads, True))
                g_leaves = jax.tree.leaves(grads)
                del grads
                for i in range(len(leaves)):
                    if i == frozen:
                        g_leaves[i] = None
                        continue
                    if moms[i] is None:
                        x, m = first_update(leaves[i], g_leaves[i])
                    else:
                        x, m = update(leaves[i], g_leaves[i],
                                      jax.device_put(moms[i], self.node))
                    g_leaves[i] = None
                    leaves[i] = mix(x) if self.n > 1 else x
                    if t + 1 < steps:
                        moms[i] = np.asarray(m)
                    del x, m
            theta0 = self.init(seed)
            change = np.asarray(jax.jit(lambda a, b: change_norms(a, b, True))(
                jax.tree.unflatten(treedef, leaves), theta0))
        return {"losses": np.stack(losses), "grad_norms": grad_norms,
                "change_norms": change}
