"""Plain float32 reference of a configuration's decoder, in ``jax.numpy``.

The layers are the configuration's family's (``reference/<family>.py``,
named by the configuration file's ``family``), one kind per layer
(``reference.layer_kinds``); this module runs them: no KV cache, no
batching, no quantization, the whole sequence recomputed from its tokens,
layer by layer, with each layer's weights drawn again from the seed
(``chipbench.weights``) with its kind's specs, then the final norm and the
head in vocabulary blocks.  One program is compiled per kind.

Departures from the published models are the configuration file's
``reduced`` and ``assumed``; weights are random (uniform, seeded).

``quant="w4a8"`` is the control: the same computation with every product
that the configuration runs in int8 taken one step lower, int4 weights per
output channel over int8 activations per row, and the K/V rows rounded to
int8 per (position, head) as the program's cache stores them.  It is not
run by the benchmark's timed runs, only by ``calibrate.py`` and the tests.

Nothing here imports the program (``repro``).
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from reference import layer_kinds
from reference.common import HI, QUERY_BLOCK, rmsnorm


def family(name: str):
    """The reference family module ``reference/<name>.py``."""
    return importlib.import_module(f"reference.{name}")


@functools.lru_cache(maxsize=None)
def _layer_program(fam: str, m_items: tuple, quant, kind):
    m = dict(m_items)
    layer = family(fam).layer
    return jax.jit(lambda x, w: layer(x, w, m, quant, kind))


def _padded(n: int) -> int:
    """Sequence lengths are padded to a power of two (at least a query
    block), so a run compiles a handful of shapes; causal masking keeps
    the padding out of every real position."""
    p = QUERY_BLOCK
    while p < n:
        p *= 2
    return p


ROW_PAD = 256           # scored rows are padded to a multiple of this


def _pad_to(n: int, k: int) -> int:
    return -(-max(n, 1) // k) * k


@jax.jit
def _embed_rows(table, ids):
    return jnp.take(table, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_rows(x, g, idx, eps):
    return rmsnorm(x[idx], g, eps)


def scored_rows(fam: str, m: dict, seed: int, pairs, quant=None) -> np.ndarray:
    """Final-normed float32 hidden rows at every served position of each
    (prompt, served tokens) pair: the row after prompt token ``-1`` and
    after each served token but the last, in order (P, d).  Each sequence
    runs layer by layer, each layer through the program of its kind; every
    shape is padded (lengths to a power of two, rows to a multiple of
    ``ROW_PAD``) so repeated runs reuse compiled programs."""
    f = family(fam)
    kinds = layer_kinds(f, m)
    m_items = tuple(sorted((k, v) for k, v in m.items() if not isinstance(v, (dict, list))))
    programs = {k: (_layer_program(fam, m_items, quant, k),
                    jax.jit(lambda kw, l, specs=f.layer_specs(m, k):
                            W.layer_weights(W.root_key(kw), specs, l)))
                for k in dict.fromkeys(kinds)}
    words = W.seed_words(seed)
    embed = jax.jit(lambda kw: W.table(W.root_key(kw), m, "embed"))(words)
    seqs = [list(p) + list(s[:-1]) for p, s in pairs]
    xs = []
    for toks in seqs:
        ids = np.zeros((_padded(len(toks)),), np.int32)
        ids[:len(toks)] = toks
        xs.append(_embed_rows(embed, ids))
    del embed
    for layer, kind in enumerate(kinds):
        layer_fn, layer_w = programs[kind]
        w = layer_w(words, layer)
        xs = [layer_fn(x, w) for x in xs]
        del w
    g = jax.jit(lambda kw: W.final_norm(W.root_key(kw), m))(words)
    rows = []
    for x, (p, s) in zip(xs, pairs):
        n = len(s)
        idx = np.zeros((_pad_to(n, ROW_PAD),), np.int32)
        idx[:n] = np.arange(len(p) - 1, len(p) - 1 + n)
        rows.append(np.asarray(_final_rows(x, g, idx, float(m["rms_norm_eps"])))[:n])
    return np.concatenate(rows)


def head_scores(m: dict, seed: int, hidden, served, hidden_ctrl=None):
    """At each scored position: the reference's best logit, its logit of
    the served token, and (given the control's hidden states) its logit
    of the token the control puts first.  ``hidden``: (P, d) float32
    rows, ``served``: (P,) ids.  The head is taken in vocabulary blocks
    drawn from the seed."""
    words = W.seed_words(seed)
    vocab = m["vocab_size"]
    blk = jax.jit(lambda kw, b: W.table_block(W.root_key(kw), m, "head", b))
    n = hidden.shape[0]
    p = _pad_to(n, ROW_PAD)

    def pad(a, fill=0):
        out = np.full((p,) + a.shape[1:], fill, a.dtype)
        out[:n] = a
        return out

    hidden = pad(np.asarray(hidden, np.float32))
    hidden_ctrl = None if hidden_ctrl is None else pad(np.asarray(hidden_ctrl, np.float32))
    served = pad(np.asarray(served, np.int32), -1)
    best = jnp.full((p,), -jnp.inf)
    at_served = jnp.zeros((p,))
    c_best = jnp.full((p,), -jnp.inf)
    at_c = jnp.zeros((p,))

    @jax.jit
    def fold(b, tab, best, at_served, c_best, at_c, hidden, hidden_ctrl, served):
        ids = b * W.ROW_BLOCK + jnp.arange(W.ROW_BLOCK)
        ok = ids < vocab
        lg = jnp.matmul(hidden, tab.astype(jnp.float32).T, precision=HI)
        lg = jnp.where(ok[None, :], lg, -jnp.inf)
        best = jnp.maximum(best, lg.max(axis=1))
        hit = (served[:, None] == ids[None, :])
        at_served = at_served + jnp.where(hit, lg, 0.0).sum(axis=1)
        if hidden_ctrl is not None:
            lc = jnp.matmul(hidden_ctrl, tab.astype(jnp.float32).T, precision=HI)
            lc = jnp.where(ok[None, :], lc, -jnp.inf)
            j = jnp.argmax(lc, axis=1)
            cmax = jnp.take_along_axis(lc, j[:, None], axis=1)[:, 0]
            take = cmax > c_best
            at_c = jnp.where(take, jnp.take_along_axis(lg, j[:, None], axis=1)[:, 0], at_c)
            c_best = jnp.where(take, cmax, c_best)
        return best, at_served, c_best, at_c

    for b in range(W.n_table_blocks(m)):
        best, at_served, c_best, at_c = fold(
            b, blk(words, b), best, at_served, c_best, at_c, hidden, hidden_ctrl, served)
    out = {"best": np.asarray(best)[:n], "served": np.asarray(at_served)[:n]}
    if hidden_ctrl is not None:
        out["control"] = np.asarray(at_c)[:n]
    return out
