"""Plain reference of one CHOCO-SGD step.

Written from the configuration's sizes and the published algorithms, in
float32 at ``Precision.HIGHEST``, importing nothing of the program:

* the model and its mean next-token cross-entropy, from the module of the
  configuration's family (``bench/families/<family>.py``), differentiated
  by ``jax.grad``;
* heavy-ball momentum (beta 0.9) for the local half-step;
* CHOCO-GOSSIP (Koloskova et al. 2019, Algorithm 2) with top-k of the
  error-feedback delta per bucket of ``counts.bucket_plan``, each message
  added into x_hat and s where its values were taken, and the Theorem-2
  consensus stepsize per bucket.

The same code, with every matmul's operands and cotangents rounded to
float8 (e4m3, one scale per tensor), is the control: what a step one
precision below the configuration's bfloat16 would give.

Weights are made here, from the seed, by :func:`init_node` with the
family's rule; the harness places the same weights in the program's
state, so nothing of the program's own making reaches the reference.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, counts, families

HIGHEST = jax.lax.Precision.HIGHEST
MOMENTUM = 0.9
FAULTS = ("half_batch", "altered", "no_exchange")


# -- weights -----------------------------------------------------------------

def _unflatten(model: Dict, leaves: Sequence):
    shapes = counts.param_shapes(model)
    it = iter(leaves)

    def build(node):
        return {k: build(node[k]) if isinstance(node[k], dict) else next(it)
                for k in sorted(node)}
    return build(shapes)


def node_key(key, node: int, leaf: int):
    return jax.random.fold_in(jax.random.fold_in(key, leaf), node)


def init_node(model: Dict, key, node) -> Dict:
    """Node ``node``'s initial weights from ``key`` (nested dict, f32)."""
    init_leaf = families.load(model["family"]).init_leaf
    leaves = [init_leaf(path, shape, node_key(key, node, j))
              for j, (path, shape) in enumerate(counts.leaf_shapes(model))]
    return _unflatten(model, leaves)


def init_nodes(model: Dict, key, n_nodes: int) -> Dict:
    """All nodes' weights, stacked on a leading node axis."""
    return jax.vmap(lambda i: init_node(model, key, i))(jnp.arange(n_nodes))


# -- loss --------------------------------------------------------------------

def _q8(a):
    """Round to float8 e4m3 with one scale per tensor."""
    amax = jnp.max(jnp.abs(a))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _round_cotangent(y):
    return y


def _rc_fwd(y):
    return y, None


def _rc_bwd(_, g):
    return (_q8(g),)


_round_cotangent.defvjp(_rc_fwd, _rc_bwd)


def _matmul(spec: str, a, b, control: bool):
    if not control:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    # operands rounded going forward (straight through going back), the
    # cotangent rounded where it enters this matmul's backward pass
    ra = a + jax.lax.stop_gradient(_q8(a) - a)
    rb = b + jax.lax.stop_gradient(_q8(b) - b)
    return _round_cotangent(jnp.einsum(spec, ra, rb, precision=HIGHEST))


def node_loss(params: Dict, tokens, labels, model: Dict,
              control: bool = False, valid=None):
    """Mean next-token cross-entropy of one node's batch (B, S), by the
    model's family, every matmul at ``HIGHEST`` or as the control."""
    mm = functools.partial(_matmul, control=control)
    return families.load(model["family"]).loss(params, tokens, labels,
                                               model, mm, valid)


# -- CHOCO -------------------------------------------------------------------

def ring_weights(n: int) -> np.ndarray:
    """Mixing matrix of the ring: 1/3 to self and each neighbour (n >= 3)."""
    if n == 1:
        return np.ones((1, 1))
    if n == 2:
        return np.full((2, 2), 0.5)
    W = np.zeros((n, n))
    for i in range(n):
        W[i, i] = W[i, (i + 1) % n] = W[i, (i - 1) % n] = 1.0 / 3.0
    return W


def theorem2_gamma(W: np.ndarray, omega: float) -> float:
    """Consensus stepsize of Theorem 2 (eq. 20) for mixing matrix W."""
    eig = np.sort(np.abs(np.linalg.eigvalsh(W)))[::-1]
    delta = 1.0 - (eig[1] if len(eig) > 1 else 0.0)
    beta = float(np.linalg.norm(np.eye(len(W)) - W, ord=2))
    den = (16 * delta + delta ** 2 + 4 * beta ** 2 + 2 * delta * beta ** 2
           - 8 * delta * omega)
    return float(delta * delta * omega / den)


def _row_width(b: Dict) -> int:
    return b["size"] if b["rows"] == 1 else counts.MAX_BUCKET


def _bucket_buffer(flat: List, b: Dict):
    """A bucket's leaves, each padded to 128 elements, then the whole
    padded to ``rows`` rows."""
    parts = [jnp.pad(flat[i], (0, -(-size // counts.LANES) * counts.LANES
                                   - size))
             for i, _, size in b["slots"]]
    buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return jnp.pad(buf, (0, b["rows"] * _row_width(b) - buf.size))


def compress(deltas: List, plan: List[Dict]):
    """Top-k of each bucket's delta, per row: [(values, indices) (rows, k)]."""
    out = []
    for b in plan:
        rows = _bucket_buffer(deltas, b).reshape(b["rows"], -1)
        _, idx = jax.lax.top_k(jnp.abs(rows), b["k"])
        out.append((jnp.take_along_axis(rows, idx, axis=1), idx))
    return out


def scatter_add(leaves: List, payload, plan: List[Dict], weight) -> List:
    """``leaves`` plus ``weight`` times the payloads' kept values, added in
    place where they were taken (no full-size copy of a message is made)."""
    leaves = list(leaves)
    for (vals, idx), b in zip(payload, plan):
        flat = (jnp.arange(b["rows"])[:, None] * _row_width(b) + idx)
        flat, v = flat.reshape(-1), weight * vals.reshape(-1)
        if len(b["slots"]) == 1:
            i = b["slots"][0][0]
            leaves[i] = leaves[i].at[flat].add(v, mode="drop")
            continue
        buf = jnp.zeros(b["size"], jnp.float32).at[flat].add(v)
        for i, off, size in b["slots"]:
            leaves[i] = leaves[i] + buf[off:off + size]
    return leaves


class Reference:
    """The reference run of one cell.  Every array carries a leading node
    axis, sharded one node per device, and every step is a function of one
    node mapped over that axis, so each compiles once for all chips; the
    neighbours' messages come by rolling the node axis of the ring.
    ``run(..., control=True)`` is the control; ``fault=`` plants one of
    :data:`FAULTS` (through arguments, so every run shares one compile)."""

    def __init__(self, model: Dict, traffic: Dict, lr: float, devices):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        families.load(model["family"]).check(model)
        self.model = model
        self.lr = np.float32(lr)
        self.n = n = traffic["nodes"]
        self.shapes = [s for _, s in counts.leaf_shapes(model)]
        self.sizes = [math.prod(s) for s in self.shapes]
        self.plan = counts.bucket_plan(self.sizes, traffic["fraction"])
        W = ring_weights(n)
        self.w_self = float(W[0, 0])
        #: ring neighbours as shifts of the node axis, with their weight
        self.shifts = sorted({1 % n, -1 % n} - {0})
        self.w_nbr = float(W[0, self.shifts[0]]) if self.shifts else 0.0
        gammas = [theorem2_gamma(W, b["budget"] / b["logical"])
                  for b in self.plan]
        self.leaf_gamma = [0.0] * len(self.sizes)
        for b, g in zip(self.plan, gammas):
            for i, _, _ in b["slots"]:
                self.leaf_gamma[i] = g
        mesh = Mesh(np.asarray(devices[:n]), ("node",))
        self.nodes = NamedSharding(mesh, P("node"))
        self.replicated = NamedSharding(mesh, P())
        on = lambda fn: jax.vmap(fn)
        self._grad = {c: jax.jit(on(functools.partial(self._grad_fn, c)),
                                 donate_argnums=(0, 1))
                      for c in (False, True)}
        self._compress = jax.jit(self._compress_fn)
        self._mix = jax.jit(self._mix_fn, donate_argnums=(0, 1, 2))
        self._init = jax.jit(lambda key: [
            a.reshape(n, -1) for a in jax.tree.leaves(init_nodes(model, key, n))],
            out_shardings=self.nodes)
        self._zeros = jax.jit(lambda: [jnp.zeros((n, sz), jnp.float32)
                                       for sz in self.sizes],
                              out_shardings=self.nodes)
        self._norms = jax.jit(lambda a: [jnp.linalg.norm(x, axis=1)
                                         for x in a])
        self._change = jax.jit(lambda a, b: (
            [jnp.linalg.norm(x - y, axis=1) for x, y in zip(a, b)],
            [jax.vmap(compare.projections)(x - y) for x, y in zip(a, b)]))

    def _grad_fn(self, control, x, mu, tokens, labels, valid, lr):
        """One node: loss, first-gradient norms, the momentum half-step."""
        params = _unflatten(self.model, [a.reshape(s) for a, s
                                         in zip(x, self.shapes)])
        loss, g = jax.value_and_grad(node_loss)(params, tokens, labels,
                                                self.model, control, valid)
        g = [a.reshape(-1) for a in jax.tree.leaves(g)]
        gnorm = [jnp.linalg.norm(a) for a in g]
        gproj = [compare.projections(a) for a in g]
        mu = [MOMENTUM * m + a for m, a in zip(mu, g)]
        x_half = [a - lr * m for a, m in zip(x, mu)]
        return loss, gnorm, gproj, x_half, mu

    def _compress_fn(self, x_half, x_hat, sign):
        one = lambda xh, h: compress([a - b for a, b in zip(xh, h)],
                                     self.plan)
        return [(sign * v, i) for v, i in jax.vmap(one)(x_half, x_hat)]

    def _mix_fn(self, x_half, x_hat, s, payload, w_nbr):
        nbrs = [jax.tree.map(lambda a: jnp.roll(a, -k, axis=0), payload)
                for k in self.shifts]

        def one(x_half, x_hat, s, own, nbrs):
            x_hat = scatter_add(x_hat, own, self.plan, 1.0)
            s = scatter_add(s, own, self.plan, self.w_self)
            for p in nbrs:
                s = scatter_add(s, p, self.plan, w_nbr)
            x = [xh + g * (sv - h) for xh, g, sv, h
                 in zip(x_half, self.leaf_gamma, s, x_hat)]
            return x, x_hat, s
        return jax.vmap(one)(x_half, x_hat, s, payload, nbrs)

    def run(self, key, batches: Sequence[Dict], control: bool = False,
            fault: Optional[str] = None) -> Dict:
        """Three (or ``len(batches)``) steps; returns the readings:
        ``loss`` per step (mean over nodes), and per node and leaf the
        norms of the first gradient (``grad``), of the parameters' change
        (``step``), of x_hat (``hat``) and of s (``s``) after the last."""
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
        key = jax.device_put(key, self.replicated)
        x, mu, hat, s = (self._init(key), self._zeros(), self._zeros(),
                         self._zeros())
        sign = np.float32(-1.0 if fault == "altered" else 1.0)
        w_nbr = np.float32(0.0 if fault == "no_exchange" else self.w_nbr)
        losses, grad = [], None
        for t, batch in enumerate(batches):
            valid = np.ones(batch["tokens"].shape, np.float32)
            if fault == "half_batch":
                valid[..., valid.shape[-1] // 2:] = 0.0
            put = lambda a: jax.device_put(a, self.nodes)
            loss, gnorm, gproj, x, mu = self._grad[control](
                x, mu, put(batch["tokens"]), put(batch["labels"]),
                put(valid), np.full(self.n, self.lr, np.float32))
            losses.append(float(np.mean(np.asarray(loss))))
            if t == 0:
                grad, grad_proj = _by_node(gnorm), _by_node(gproj)
            payload = self._compress(x, hat, sign)
            x, hat, s = self._mix(x, hat, s, payload, w_nbr)
        x0 = self._init(key)
        step, step_proj = self._change(x, x0)
        del x0
        return {"loss": losses, "grad": grad, "grad_proj": grad_proj,
                "step": _by_node(step), "step_proj": _by_node(step_proj),
                "hat": _by_node(self._norms(hat)), "s": _by_node(self._norms(s))}


def _by_node(leaves):
    """Per-leaf arrays (n, ...) -> one array (n, leaves, ...)."""
    return np.stack([np.asarray(v) for v in leaves], axis=1)
