"""Seeded weights, drawn on the device, the same for the program and the
plain reference.

Every leaf is a pure function of (seed, leaf name, layer, row block) in
the published layout (Hugging Face's head order: query head ``j`` reads
KV head ``j // G``).  The program receives them converted to its own
layout in one jitted call; the reference draws them again, layer by layer,
from this module: it never reads an array the program holds.

Values are uniform in ``[-a, a]`` (bits to floats by exact arithmetic, so
a leaf drawn alone equals the same leaf drawn inside the stacked program),
with the spread of the program's own initialisers: 0.02 for projections
and experts, ``1/sqrt(d)`` for the embedding and the head, norm scales
around 1.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 8192        # rows of an embedding/head table per key
LINEAR_STD = 0.02


def seed_words(seed: int) -> np.ndarray:
    """The seed as the two 32-bit words of a threefry key.  Jitted draws
    take them as an argument, never as a constant, so that one compiled
    program serves every seed and a run's set-up finds it in the cache."""
    s = int(seed) % (1 << 64)
    return np.asarray([s >> 32, s & 0xFFFFFFFF], np.uint32)


def root_key(words):
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _leaf_key(root, name: str):
    return jax.random.fold_in(root, zlib.crc32(name.encode()))


def _draw(key, shape, std, dtype, offset=0.0):
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    return (u * (std * math.sqrt(3.0)) + offset).astype(dtype)


def layer_leaf(root, specs: dict, name: str, layer):
    """Leaf ``name`` of layer ``layer``; ``specs``: the family's
    ``layer_specs``, name -> (shape, dtype, std, offset) in the published
    layout."""
    shape, dtype, std, offset = specs[name]
    key = jax.random.fold_in(_leaf_key(root, "layer/" + name), layer)
    return _draw(key, shape, std, dtype, offset)


def layer_weights(root, specs: dict, layer) -> dict:
    return {n: layer_leaf(root, specs, n, layer) for n in specs}


def table_block(root, m: dict, name: str, block):
    """Rows ``[block * ROW_BLOCK, (block + 1) * ROW_BLOCK)`` of the
    ``embed`` or ``head`` table, bf16 (rows past the vocabulary are drawn
    and dropped, so every block has one shape)."""
    d = m["hidden_size"]
    key = jax.random.fold_in(_leaf_key(root, name), block)
    return _draw(key, (ROW_BLOCK, d), 1.0 / math.sqrt(d), jnp.bfloat16)


def n_table_blocks(m: dict) -> int:
    return -(-m["vocab_size"] // ROW_BLOCK)


def table(root, m: dict, name: str):
    blocks = jax.lax.map(lambda b: table_block(root, m, name, b),
                         jnp.arange(n_table_blocks(m)))
    return blocks.reshape(-1, m["hidden_size"])[: m["vocab_size"]]


def final_norm(root, m: dict):
    return _draw(_leaf_key(root, "final_norm"), (m["hidden_size"],),
                 0.25 / math.sqrt(3.0), jnp.float32, 1.0)


# ---------------------------------------------------------------------------
# The program's tree, filled in the program's layout
# ---------------------------------------------------------------------------

def _path_name(path) -> list[str]:
    return [str(getattr(e, "key", getattr(e, "idx", e))) for e in path]


def _layer_groups(shapes) -> dict:
    """``{tree prefix: (layer indices, stacked)}`` of the program's layer
    trees: ``head_blocks/<i>`` is layer ``i``; ``blocks/<s>`` is stacked
    over periods, period ``q`` of slot ``s`` being layer ``head + q p + s``
    (``p`` slots); ``tail_blocks/<i>`` is the depth remainder after them."""
    head = len(shapes.get("head_blocks", []))
    slots = shapes.get("blocks", ())
    periods = jax.tree_util.tree_leaves(slots[0])[0].shape[0] if slots else 0
    groups = {f"head_blocks/{i}": ([i], False) for i in range(head)}
    groups.update({f"blocks/{s}": ([head + q * len(slots) + s for q in range(periods)], True)
                   for s in range(len(slots))})
    first_tail = head + periods * len(slots)
    groups.update({f"tail_blocks/{i}": ([first_tail + i], False)
                   for i in range(len(shapes.get("tail_blocks", [])))})
    return groups


def program_params(m: dict, seed: int, shapes, kinds, specs_of, to_program):
    """The program's parameter tree (``shapes``: its ``eval_shape`` of
    ``init_params``) filled from the seed in one jitted call.  ``kinds``
    gives each layer's kind (``reference.layer_kinds``), ``specs_of(kind)``
    that kind's ``layer_specs``; ``to_program(name, w, m)`` converts a
    published leaf to the program's layout.  Layer ``l`` is drawn with its
    kind's specs under the same keys wherever the program keeps it (see
    ``_layer_groups``); the layers of one stacked slot share a kind."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = ["/".join(_path_name(p)) for p, _ in flat]
    groups = _layer_groups(shapes)
    n_layers = sum(len(layers) for layers, _ in groups.values())
    if n_layers != len(kinds):
        raise ValueError(f"the program holds {n_layers} layers, the family "
                         f"gives {len(kinds)} kinds")
    for prefix, (layers, _) in groups.items():
        if len({kinds[l] for l in layers}) > 1:
            raise ValueError(f"{prefix} stacks layers of kinds "
                             f"{sorted({str(kinds[l]) for l in layers})}")

    def make(words):
        root = root_key(words)

        def draw(layer, kind):
            specs = specs_of(kind)
            return {n: to_program(n, layer_leaf(root, specs, n, layer), m) for n in specs}

        drawn = {prefix: (jax.lax.map(lambda l, k=kinds[layers[0]]: draw(l, k),
                                      jnp.asarray(layers, jnp.int32))
                          if stacked else draw(layers[0], kinds[layers[0]]))
                 for prefix, (layers, stacked) in groups.items()}
        out = []
        for name, (_, sd) in zip(names, flat):
            group = "/".join(name.split("/")[:2])
            if group in drawn:
                leaf = drawn[group].get(name[len(group) + 1:])
                if leaf is None:
                    raise ValueError(f"no weights for the program's leaf {name!r}")
            elif name in ("embed", "head"):
                leaf = table(root, m, name)
            elif name == "final_norm":
                leaf = final_norm(root, m)
            else:
                raise ValueError(f"no weights for the program's leaf {name!r}")
            if leaf.shape != sd.shape or leaf.dtype != sd.dtype:
                raise ValueError(
                    f"{name}: the benchmark draws {leaf.shape} {leaf.dtype}, the "
                    f"program holds {sd.shape} {sd.dtype}")
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(seed_words(seed))
