"""The benchmark's own parameters: layout and seeded initialization.

The layout is written here from the configuration's sizes (a dense
decoder: embeddings, a stack of pre-norm attention + MLP layers stored
with a leading layer axis, a final norm and an optional untied head), in
the tree the program's training step takes.  The harness checks that the
program agrees with it.  Both the program's run and the plain reference
start from these weights, made from ``--seed`` in one jitted call on the
device, in float32 (the master weights of mixed-precision training).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["layout", "init_params", "seed_key", "slice_names",
           "slice_norms"]

# path -> (shape, std); std None means zeros
Layout = Dict[Tuple[str, ...], Tuple[Tuple[int, ...], float]]


def layout(m: Dict) -> Layout:
    d, hd, L = m["d_model"], m["head_dim"], m["n_layers"]
    q, kv, f, v = m["n_heads"] * hd, m["n_kv_heads"] * hd, m["d_ff"], \
        m["vocab_size"]
    g = ("stack", "groups", "l00")
    out: Layout = {
        ("embed",): ((v, d), 1 / np.sqrt(d)),
        ("final_norm", "scale"): ((d,), None),
        ("final_norm", "bias"): ((d,), None),
        g + ("mixer_norm", "scale"): ((L, d), None),
        g + ("mixer_norm", "bias"): ((L, d), None),
        g + ("mixer", "wq"): ((L, d, q), 1 / np.sqrt(d)),
        g + ("mixer", "wk"): ((L, d, kv), 1 / np.sqrt(d)),
        g + ("mixer", "wv"): ((L, d, kv), 1 / np.sqrt(d)),
        g + ("mixer", "wo"): ((L, q, d), 1 / np.sqrt(q * L)),
        g + ("ffn_norm", "scale"): ((L, d), None),
        g + ("ffn_norm", "bias"): ((L, d), None),
        g + ("ffn", "w_up"): ((L, d, f), 1 / np.sqrt(d)),
        g + ("ffn", "w_down"): ((L, f, d), 1 / np.sqrt(f * L)),
    }
    if m["pos_emb"] == "learned":
        out[("pos_embed",)] = ((m["max_seq_len"], d), 1 / np.sqrt(d))
    if not m["tie_embeddings"]:
        out[("head",)] = ((d, v), 1 / np.sqrt(d))
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed below 2**64 (two 32-bit words)."""
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words))


def _nest(flat: Dict[Tuple[str, ...], jax.Array]) -> Dict:
    tree: Dict = {}
    for path, val in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = val
    return tree


def init_params(lay: Layout, key: jax.Array) -> Dict:
    """Truncated-normal (at 3 std) matrices and zero norms, one key per
    leaf folded in by the leaf's position in sorted order.  Jit it."""
    flat = {}
    for i, path in enumerate(sorted(lay)):
        shape, std = lay[path]
        if std is None:
            flat[path] = jnp.zeros(shape, jnp.float32)
        else:
            k = jax.random.fold_in(key, i)
            flat[path] = std * jax.random.truncated_normal(
                k, -3.0, 3.0, shape, jnp.float32)
    return _nest(flat)


def _leaves(tree: Dict, lay: Layout):
    for path in sorted(lay):
        node = tree
        for k in path:
            node = node[k]
        yield path, node


def slice_names(lay: Layout) -> List[str]:
    """Names of the compared slices: every leaf, with a stacked layer
    leaf split into its layers."""
    names = []
    for path in sorted(lay):
        shape, _ = lay[path]
        name = "/".join(p for p in path if p not in ("stack", "groups",
                                                     "l00"))
        if path[0] == "stack":
            names += [f"L{i}/{name}" for i in range(shape[0])]
        else:
            names.append(name)
    return names


def slice_norms(tree: Dict, lay: Layout) -> jax.Array:
    """Euclidean norm of each slice of ``tree`` in ``slice_names`` order,
    in float32.  Jit it."""
    out = []
    for path, x in _leaves(tree, lay):
        x = x.astype(jnp.float32)
        if path[0] == "stack":
            out.append(jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(
                range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)
