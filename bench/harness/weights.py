"""Weights from the seed: one jitted call on the device, in bf16, in the
layout the program serves (``repro.models.model``'s dense parameter
tree).  The reference reads the same arrays; the program gets them
handed in and makes none of its own.

Matrices are N(0, 1/fan_in); the embedding and the untied head are
N(0, 1/hidden) so logits have a spread of about one; every norm gain is
drawn from U(0.8, 1.2), so a norm applied with the wrong gain, or not
at all, moves the logits.  Large leaves are drawn a slice at a time
(``lax.map``), so float32 staging never holds more than one slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16


def seed_key(seed: int):
    """A key for any whole-number seed, beyond 32 bits too."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def shapes(config: dict) -> dict:
    """Leaf shapes of the dense tree for a configuration file."""
    L, d = int(config["num_hidden_layers"]), int(config["hidden_size"])
    hd, V = int(config["head_dim"]), int(config["vocab_size"])
    hq = int(config["num_attention_heads"]) * hd
    hk = int(config["num_key_value_heads"]) * hd
    f = int(config["intermediate_size"])
    tree = {
        "embed": (V, d),
        "final_ln_w": (d,),
        "blocks": {
            "attn": {"ln_w": (L, d), "wq": (L, d, hq), "wk": (L, d, hk),
                     "wv": (L, d, hk), "wo": (L, hq, d),
                     "q_norm": (L, hd), "k_norm": (L, hd)},
            "mlp": {"ln_w": (L, d), "wi_gate": (L, d, f),
                    "wi_up": (L, d, f), "wo": (L, f, d)},
        },
    }
    if not config["tie_word_embeddings"]:
        tree["lm_head"] = (d, V)
    return tree


def _normal(key, shape, std):
    """bf16 N(0, std^2), drawn slice by slice along the first axis."""
    n = shape[0]
    parts = n if len(shape) == 3 else next(p for p in (8, 4, 2, 1)
                                           if n % p == 0)
    keys = jax.random.split(key, parts)
    inner = (n // parts,) + tuple(shape[1:])
    out = jax.lax.map(lambda k: (jax.random.normal(k, inner, jnp.float32)
                                 * std).astype(BF16), keys)
    return out.reshape(shape)


def _gain(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2).astype(BF16)


@functools.partial(jax.jit, static_argnums=0)
def _make(frozen: tuple, key):
    config = dict(frozen)
    tree = shapes(config)
    d = int(config["hidden_size"])
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util
             .tree_flatten_with_path(tree, is_leaf=lambda x:
                                     isinstance(x, tuple))[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for path, shape, k in zip(paths, leaves, keys):
        if "ln_w" in path or "norm" in path:
            out.append(_gain(k, shape))
        elif "embed" in path or "lm_head" in path:
            out.append(_normal(k, shape, d ** -0.5))
        else:       # [L, fan_in, fan_out]
            out.append(_normal(k, shape, shape[-2] ** -0.5))
    return jax.tree.unflatten(treedef, out)


def make(config: dict, seed: int, device=None):
    """The weights for ``config`` from ``seed``, on ``device``."""
    frozen = tuple(sorted((k, v) for k, v in config.items()
                          if k in ("num_hidden_layers", "hidden_size",
                                   "head_dim", "vocab_size",
                                   "num_attention_heads",
                                   "num_key_value_heads",
                                   "intermediate_size",
                                   "tie_word_embeddings")))
    with jax.default_device(device):
        return _make(frozen, seed_key(seed))


def check_layout(params, abstract) -> None:
    """Raise unless ``params`` has the program's tree, shapes and
    dtypes (``abstract`` is ``repro.models.model.abstract(cfg)``)."""
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), abstract)
    if got != want:
        raise ValueError(f"bench weights do not match the program's "
                         f"parameter tree:\n{got}\nvs\n{want}")
