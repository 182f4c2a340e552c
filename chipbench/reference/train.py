"""Plain reference of data-parallel LQ-SGD training steps.

Follows the paper's Algorithm 1 with the PowerSGD conventions it builds on
(Vogels et al., 2019), written from those descriptions and independent of
``repro``. Each of W workers holds its own error feedback E_w; all hold the
same warm-start factor Q. Per low-rank matrix instance (each layer of a
stacked leaf is one):

    G'_w = G_w + E_w
    P    = avg( G'_w Q )             log-quantised, see below
    P̂    = Gram-Schmidt(P)           column by column, col / (|col| + 1e-8)
    Q    = avg( G'_w^T P̂ )           log-quantised; also the next warm start
    Ĝ    = P̂ Q^T ;  E_w = G'_w - Ĝ

avg(X_w): one scale s = max_w max|X_w| over the instance; codes
c_w = round(sign(x) log(1 + α|x|/s) / log(1 + α) · L), L = 2^(b-1) - 1
(paper Eq. 5); the codes are averaged over workers and expanded by
Eq. 6, sign(c) ((1 + α)^(|c|/L) - 1) / α · s. A leaf that is not a matrix
(norm gains, biases, the SSM's per-head vectors) is quantised whole with one
scale and averaged after expanding. The synced gradient is rounded to the
parameter's type, and SGD sets w <- w - lr·Ĝ, rounded to that type.

The gradients are float32 and come from ``lm``'s model one layer at a time:
the forward keeps each layer's input, the backward takes each layer's
vector-Jacobian product in turn and syncs and updates that layer's matrices
as soon as they are known, so the whole model's float32 gradient is never
held at once.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import leaf_route
from chipbench.weights import q_init

__all__ = ["reference_steps", "leaf_norms"]

HI = jax.lax.Precision.HIGHEST
SG = jax.tree_util.SequenceKey
DK = jax.tree_util.DictKey


def _quant(x, bits, alpha):
    lv = (1 << (bits - 1)) - 1
    c = jnp.sign(x) * jnp.log1p(alpha * jnp.abs(x)) * (lv / math.log1p(alpha))
    return jnp.clip(jnp.round(c), -lv, lv)


def _expand(c, bits, alpha):
    lv = (1 << (bits - 1)) - 1
    return jnp.sign(c) * (jnp.exp(jnp.abs(c) * (math.log1p(alpha) / lv)) - 1) / alpha


def _scale(x):
    s = jnp.max(jnp.abs(x))
    return jnp.where(s > 0, s, 1.0)


def _avg_codes(x, bits, alpha):
    """x (W, ...) -> the dequantised mean of the workers' codes (...)."""
    s = _scale(x)
    return _expand(jnp.mean(_quant(x / s, bits, alpha), axis=0), bits, alpha) * s


def _gram_schmidt(p):
    cols = []
    for i in range(p.shape[1]):
        col = p[:, i]
        for prev in cols:
            col = col - jnp.dot(prev, col, precision=HI) * prev
        cols.append(col / (jnp.linalg.norm(col) + 1e-8))
    return jnp.stack(cols, axis=1)


def _lowrank(g, err, q, *, bits, alpha):
    """g, err (W, n, m); q (m, r) -> (Ĝ (n, m), E (W, n, m), Q (m, r))."""
    gef = g + err
    p = _avg_codes(jnp.einsum("wnm,mr->wnr", gef, q, precision=HI), bits, alpha)
    ph = _gram_schmidt(p)
    qn = _avg_codes(jnp.einsum("wnm,nr->wmr", gef, ph, precision=HI), bits, alpha)
    ghat = jnp.einsum("nr,mr->nm", ph, qn, precision=HI)
    return ghat, gef - ghat[None], qn


def _whole(g, *, bits, alpha):
    s = _scale(g)
    return jnp.mean(_expand(_quant(g / s, bits, alpha), bits, alpha), axis=0) * s


def _sgd(w, g, lr):
    g = g.astype(w.dtype).astype(jnp.float32)
    return (w.astype(jnp.float32) - lr * g).astype(w.dtype)


def leaf_norms(a_tree, b_tree) -> list[float]:
    """Per leaf ‖a - b‖ in float32; the leaves may be host or device arrays."""
    diff = jax.jit(lambda a, b: jnp.linalg.norm((a.astype(jnp.float32) - b).ravel()))
    out = []
    for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)):
        out.append(float(diff(jnp.asarray(a), jnp.asarray(b).astype(jnp.float32))))
    return out


class _Layout:
    """Where each flat leaf of the program's parameter tree sits."""

    def __init__(self, params, model: dict, rank: int, min_numel: int):
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(params)
        self.paths = [p for p, _ in flat]
        self.shapes = [tuple(a.shape) for _, a in flat]
        self.stacked = [p[0] == DK("scan") for p in self.paths]
        self.routes = [
            leaf_route(s, st, rank, min_numel)
            for s, st in zip(self.shapes, self.stacked)
        ]
        self.layers = []  # (spec, group key, repeat index or None)
        for i, spec in enumerate(model.get("lead", [])):
            self.layers.append((spec, ("lead", i), None))
        for r in range(model["repeats"]):
            for pos, spec in enumerate(model["pattern"]):
                self.layers.append((spec, ("scan", pos), r))
        for i, spec in enumerate(model.get("tail", [])):
            self.layers.append((spec, ("tail", i), None))
        self.groups = {}
        for key in {g for _, g, _ in self.layers}:
            sub = params[key[0]][key[1]]
            prefix = (DK(key[0]), SG(key[1]))
            idx = [i for i, p in enumerate(self.paths) if p[:2] == prefix]
            self.groups[key] = (jax.tree_util.tree_structure(sub), idx)
        top = [i for i, p in enumerate(self.paths) if len(p) == 1]
        self.head = {str(self.paths[i][0].key): i for i in top}


def reference_steps(
    lm,
    model: dict,
    params0,
    *,
    seed: int,
    batches: list[np.ndarray],
    workers: int,
    traffic: dict,
    low=None,
    on_step=None,
):
    """Train ``len(batches)`` steps from ``params0`` (the program's layout).

    Returns the program-style loss of each step (the mean of the workers'
    mean losses). After each step, ``on_step(t, tree)`` may call ``tree()``
    for the parameters as a tree in the program's layout.
    """
    rank, bits, alpha, lr = (traffic[k] for k in ("rank", "bits", "alpha", "lr"))
    lay = _Layout(params0, model, rank, traffic["min_compress_numel"])
    n_leaves = len(lay.paths)
    leaves = jax.tree.leaves(params0)
    w_leaves: list = []  # per leaf: array, or list over repeats if stacked
    err: list = [None] * n_leaves
    q: list = [None] * n_leaves
    for i in range(n_leaves):
        a, route, st = leaves[i], lay.routes[i], lay.stacked[i]
        w_leaves.append([a[r] for r in range(a.shape[0])] if st else a)
        if route is None:
            continue
        n, m, rk = route
        if st:
            qs = q_init(seed, i, (a.shape[0], m, rk))
            q[i] = [qs[r] for r in range(a.shape[0])]
            err[i] = [jnp.zeros((workers, n, m)) for _ in range(a.shape[0])]
        else:
            q[i] = q_init(seed, i, (m, rk))
            err[i] = jnp.zeros((workers, n, m))

    def fwd_fn(spec):
        def rows(p, x):  # x (W, b, S, D): the layer acts on each row alone
            y = lm.layer(p, x.reshape((-1,) + x.shape[2:]), spec, model, low)
            return y.reshape(x.shape)

        return jax.jit(rows)

    def vjp_fn(spec):
        def one(p, x, dy):
            return jax.vjp(lambda p_, x_: lm.layer(p_, x_, spec, model, low), p, x)[
                1
            ](dy)

        return jax.jit(jax.vmap(one, in_axes=(None, 0, 0)))

    fwd = {g: fwd_fn(spec) for spec, g, _ in lay.layers}
    vjp = {g: vjp_fn(spec) for spec, g, _ in lay.layers}
    head_vg = jax.jit(
        jax.vmap(
            jax.value_and_grad(
                lambda p, x, t: lm.head_loss(p, x, t, model, low), argnums=(0, 1)
            ),
            in_axes=(None, 0, 0),
        )
    )
    lowrank = jax.jit(lambda g, e, qq: _lowrank(g, e, qq, bits=bits, alpha=alpha))
    whole = jax.jit(lambda g: _whole(g, bits=bits, alpha=alpha))
    sgd = jax.jit(lambda w, g: _sgd(w, g, lr))
    lookup = jax.jit(jax.vmap(lm.embed_lookup, in_axes=(None, 0)))
    vocab = lay.shapes[lay.head["embed"]][0]

    @jax.jit
    def lookup_grad(dx, tok):
        d = dx.shape[-1]
        z = jnp.zeros((vocab, d), jnp.float32)
        return jax.vmap(lambda dxw, tw: z.at[tw.reshape(-1)].add(dxw.reshape(-1, d)))(
            dx, tok
        )

    def layer_params(group, r):
        treedef, idx = lay.groups[group]
        return jax.tree_util.tree_unflatten(
            treedef, [w_leaves[i][r] if r is not None else w_leaves[i] for i in idx]
        )

    def sync_matrix(i, g, r):
        """Sync one low-rank instance of leaf i and apply SGD to it."""
        n, m, _ = lay.routes[i]
        gw = g.reshape(workers, n, m)
        if r is None:
            ghat, err[i], q[i] = lowrank(gw, err[i], q[i])
            w_leaves[i] = sgd(w_leaves[i], ghat.reshape(lay.shapes[i]))
        else:
            ghat, err[i][r], q[i][r] = lowrank(gw, err[i][r], q[i][r])
            w_leaves[i][r] = sgd(w_leaves[i][r], ghat.reshape(lay.shapes[i][1:]))

    def current_tree():
        flat = [jnp.stack(w) if isinstance(w, list) else w for w in w_leaves]
        return jax.tree_util.tree_unflatten(lay.treedef, flat)

    losses = []
    hi = lay.head
    for t, batch in enumerate(batches):
        seq = batch["tokens"].shape[1]
        tok = jnp.asarray(batch["tokens"]).reshape(workers, -1, seq)
        w_e = w_leaves[hi["embed"]]
        x = lookup(w_e, tok)
        acts = []
        for spec, group, r in lay.layers:
            acts.append(x)
            x = fwd[group](layer_params(group, r), x)
        head_p = {k: w_leaves[i] for k, i in hi.items() if k != "embed"}
        head_p["embed"] = w_e
        loss_w, (dhead, dx) = head_vg(head_p, x, tok)
        losses.append(float(jnp.mean(loss_w)))
        whole_grads: dict[int, list] = {}
        for spec, group, r in reversed(lay.layers):
            p_l = layer_params(group, r)
            dp, dx = vjp[group](p_l, acts.pop(), dx)
            _, idx = lay.groups[group]
            for i, g in zip(idx, jax.tree.leaves(dp)):
                if lay.routes[i] is not None:
                    sync_matrix(i, g, r)
                else:
                    whole_grads.setdefault(i, {})[r] = g
        grads = {i: dhead[k] for k, i in hi.items()}
        grads[hi["embed"]] = grads[hi["embed"]] + lookup_grad(dx, tok)
        for i, g in grads.items():
            if lay.routes[i] is not None:
                sync_matrix(i, g, None)
            else:
                w_leaves[i] = sgd(w_leaves[i], whole(g))
        for i, per_r in whole_grads.items():
            if lay.stacked[i]:
                reps = sorted(per_r)
                synced = whole(jnp.stack([per_r[r] for r in reps], axis=1))
                for j, r in enumerate(reps):
                    w_leaves[i][r] = sgd(w_leaves[i][r], synced[j])
            else:
                w_leaves[i] = sgd(w_leaves[i], whole(per_r[None]))
        if on_step is not None:
            on_step(t, current_tree)
    return losses
