"""Initial training state made by the benchmark from ``--seed``.

The weights and the compressor's warm-start factors come from the seed by
the rules below, on the device, in one jitted call, in the type the state
holds. The reference regenerates them from the same seed by the same call,
so it never takes an array that the program made. Only the layout of the
state (its tree of leaves and their shapes) is read from the program.

Leaf rules, by the leaf's own key in the tree (the published inits of the
two families): RMS-norm gains and biases 0 (the norms scale by 1 + w);
Mamba-2's ``A_log`` = log U(1, 16), ``dt_bias`` = softplus⁻¹ of a
log-uniform step in [1e-3, 0.1], ``D`` = 1; embeddings N(0, 0.02²); every
other matrix N(0, 1/fan_in) with fan_in its second-to-last dimension. The
warm-start factors ``q`` are N(0, 1), the same on every worker; error
feedback and optimizer state start at 0.

A configuration file may give an ``init`` map, ``{leaf name: rule}``, for
leaves these rules do not name; a rule is one of ``RULES``: ``zeros``,
``ones``, ``fan_in`` (N(0, 1/fan_in)) and ``embed`` (N(0, 0.02²)). A
vector whose name neither the rules nor the map know is an error.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["RULES", "seed_key", "init_params", "make_state", "q_init"]

_ZERO = {"ln1", "ln2", "final_norm", "norm", "q_norm", "k_norm", "conv_b"}
_ZERO |= {"bq", "bk", "bv", "norm_h", "norm_e"}


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one stream of one seed; any non-negative int seed."""
    words = np.random.SeedSequence([seed, stream]).generate_state(1, np.uint32)
    return jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)


def _last_key(path) -> str:
    for k in reversed(path):
        if isinstance(k, jax.tree_util.DictKey):
            return str(k.key)
    raise ValueError(f"leaf without a dict key: {path}")


def _fan_in(key, shape):
    if len(shape) < 2:
        raise ValueError(f"N(0, 1/fan_in) needs a matrix, not shape {shape}")
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])


RULES = {
    "zeros": lambda key, shape: jnp.zeros(shape, jnp.float32),
    "ones": lambda key, shape: jnp.ones(shape, jnp.float32),
    "fan_in": _fan_in,
    "embed": lambda key, shape: 0.02 * jax.random.normal(key, shape, jnp.float32),
}
_KNOWN = _ZERO | {"A_log", "dt_bias", "D", "embed"}


def _leaf(key, name: str, shape: tuple[int, ...], dtype, init: dict) -> jax.Array:
    if name in _ZERO:
        w = RULES["zeros"](key, shape)
    elif name == "A_log":
        w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        lo, hi = np.log(1e-3), np.log(0.1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        w = dt + jnp.log(-jnp.expm1(-dt))  # softplus⁻¹
    elif name == "D":
        w = RULES["ones"](key, shape)
    elif name == "embed":
        w = RULES["embed"](key, shape)
    elif name in init:
        w = RULES[init[name]](key, shape)
    elif len(shape) >= 2:
        w = RULES["fan_in"](key, shape)
    else:
        raise ValueError(f"no init rule for leaf {name!r} of shape {shape}")
    return w.astype(dtype)


def _check_init(init: dict) -> None:
    for name, rule in init.items():
        if name in _KNOWN:
            raise ValueError(f"leaf {name!r} has its own init rule; the map may not")
        if rule not in RULES:
            raise ValueError(f"init rule {rule!r} of {name!r} not in {sorted(RULES)}")


def _params(key: jax.Array, abstract, init: dict):
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    leaves = [
        _leaf(jax.random.fold_in(key, i), _last_key(p), a.shape, a.dtype, init)
        for i, (p, a) in enumerate(flat)
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def init_params(seed: int, abstract, shardings=None, init: dict | None = None):
    """The seed's parameters, shaped and typed as ``abstract``; ``init`` is
    the configuration file's map of further leaf rules."""
    init = init or {}
    _check_init(init)
    # the key is an argument, not a constant: one program serves every seed
    fn = jax.jit(lambda k: _params(k, abstract, init), out_shardings=shardings)
    return fn(seed_key(seed, 0))


def _state(keys: tuple[jax.Array, jax.Array], abstract: dict, init: dict) -> dict:
    pkey, qkey = keys
    state = {"params": _params(pkey, abstract["params"], init)}
    state["opt"] = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract["opt"])
    comp = {}
    for ns, sub in abstract["comp"].items():
        if ns == "q":
            # per worker, identical: the leading dim is the data-parallel one
            comp[ns] = {
                k: jnp.broadcast_to(_q(qkey, int(k), a.shape[1:]), a.shape)
                for k, a in sub.items()
            }
        elif ns == "err":
            comp[ns] = {k: jnp.zeros(a.shape, a.dtype) for k, a in sub.items()}
        else:
            raise ValueError(f"no init rule for compressor state {ns!r}")
    state["comp"] = comp
    state["step"] = jnp.zeros(abstract["step"].shape, abstract["step"].dtype)
    return state


def make_state(seed: int, abstract: dict, shardings: dict, init: dict | None = None):
    """The whole train state, born on the mesh in one jitted call."""
    init = init or {}
    _check_init(init)
    fn = jax.jit(lambda ks: _state(ks, abstract, init), out_shardings=shardings)
    return fn((seed_key(seed, 0), seed_key(seed, 1)))


def _q(key: jax.Array, index: int, shape: tuple[int, ...]) -> jax.Array:
    return jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)


def q_init(seed: int, index: int, shape: tuple[int, ...]) -> jax.Array:
    """One warm-start factor as ``make_state`` draws it (per worker)."""
    return jax.jit(_q, static_argnums=(1, 2))(seed_key(seed, 1), index, shape)
