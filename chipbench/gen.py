"""A deployment's table, made on the device from the run's seed.

The distributions follow the paper's query shapes: a target
distribution Q over V_X, ``n_close`` candidates planted at controlled l1
distances from Q, every other candidate drawn from a Dirichlet prior
into a band at least ``far_distance`` from Q, and Zipf-skewed candidate
frequencies. Two keys of the configuration choose the query's shape:

  close_rank   "head" (default): the close set is the ``n_close`` most
               frequent candidates; "tail": the ``n_close`` least
               frequent, the paper's rare top-k queries (FLIGHTS-q2/q3)
  target_kind  "peaked" (default): Q drawn from Dirichlet(2);
               "uniform": Q = 1/V_X everywhere, drawing nothing (FLIGHTS-q4)

It is a copy of the construction in the program's ``data/synth.py``,
vectorised, and imports nothing of the program, so that the yardstick
does not move when the program does.

Tuples are drawn i.i.d. from that distribution straight into blocks of
`BLOCK` tuples: each draws its joint key z * V_X + x from Walker's alias
table of the V_Z * V_X joint probabilities (a uniform slot, kept or
swapped for its alias), two small-table lookups a tuple. i.i.d. draws
are already in random order, which is what the paper's shuffled layout
provides, so no shuffle follows. The packed
presence bitmap is built on the device in the program's format (block
row, word ``c // 32``, bit ``c % 32``). The exact counts of a table, or
of any set of its blocks, come from `block_counts`: plain device code
that shares nothing with the program's kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # tuples per block: 4 KiB of (z, x) int32 pairs, as in the paper
GEN_CHUNK_BLOCKS = 4096  # largest chunk the generator draws at once
COUNT_CHUNK_BLOCKS = 1024  # blocks per exact-count call: 2^19 tuples < 2^24


def perturb_distribution(p: np.ndarray, dist: float, rng: np.random.Generator) -> np.ndarray:
    """A distribution at l1 distance about ``dist`` from p (mass moved at random)."""
    v = p.copy()
    d = rng.dirichlet(np.ones_like(p))
    e = rng.dirichlet(np.ones_like(p))
    move = (d - e) * (dist / max(np.abs(d - e).sum(), 1e-12))
    v = np.clip(v + move, 1e-9, None)
    return v / v.sum()


CLOSE_RANKS = ("head", "tail")
TARGET_KINDS = ("peaked", "uniform")


def _choice(cfg: dict, key: str, allowed: tuple) -> str:
    value = cfg.get(key, allowed[0])
    if value not in allowed:
        raise ValueError(f"{key} must be one of {allowed}, got {value!r}")
    return value


def distributions(cfg: dict, rng: np.random.Generator) -> dict:
    """Target, candidate frequencies and per-candidate distributions."""
    v_z, v_x = cfg["v_z"], cfg["v_x"]
    close_rank = _choice(cfg, "close_rank", CLOSE_RANKS)
    if _choice(cfg, "target_kind", TARGET_KINDS) == "uniform":
        q = np.full(v_x, 1.0 / v_x)
    else:
        q = rng.dirichlet(np.full(v_x, 2.0))
        q = q / q.sum()
    freq = np.arange(1, v_z + 1, dtype=np.float64) ** (-cfg["zipf_a"])
    freq /= freq.sum()
    n_close = cfg["n_close"]
    ids = np.arange(v_z)
    close = ids[v_z - n_close:] if close_rank == "tail" else ids[:n_close]
    hists = np.zeros((v_z, v_x))
    spread = np.linspace(0.5, 1.5, num=max(n_close, 1))
    for i, z in enumerate(close):
        hists[z] = perturb_distribution(q, cfg["close_distance"] * spread[i], rng)
    far = np.setdiff1d(ids, close)
    todo = far
    for _ in range(64):  # rejection into the far band
        if todo.size == 0:
            break
        h = rng.dirichlet(np.full(v_x, 0.8), size=todo.size)
        hists[todo] = h
        todo = todo[np.abs(h - q).sum(axis=1) < cfg["far_distance"]]
    for z in todo:  # forced far: move mass away from the target
        hists[z] = perturb_distribution(q, cfg["far_distance"] * 1.5, rng)
    return {"target": q, "freq": freq, "hists": hists, "close_ids": close}


def _largest_divisor(n: int, cap: int) -> int:
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


def alias_table(p: np.ndarray) -> tuple:
    """Walker's alias table of the distribution ``p`` (Vose's method): draw
    a slot i uniformly, keep it with probability ``prob[i]``, else take
    ``alias[i]``; each outcome then comes up with its probability in p."""
    n = p.size
    q = np.asarray(p, np.float64) * (n / p.sum())
    prob, alias = np.ones(n), np.arange(n)
    small = list(np.flatnonzero(q < 1.0))
    large = list(np.flatnonzero(q >= 1.0))
    while small and large:
        s, g = small.pop(), large.pop()
        prob[s], alias[s] = q[s], g
        q[g] -= 1.0 - q[s]
        (small if q[g] < 1.0 else large).append(g)
    return prob, alias


@functools.partial(jax.jit, static_argnames=("num_blocks", "chunk_blocks", "v_z", "v_x"))
def _make_table(key, prob, alias, *, num_blocks, chunk_blocks, v_z, v_x):
    words = -(-v_z // 32)
    word_ids = jnp.arange(words, dtype=jnp.int32)

    def chunk(i):
        kk, ku = jax.random.split(jax.random.fold_in(key, i))
        k = jax.random.randint(kk, (chunk_blocks, BLOCK), 0, v_z * v_x, jnp.int32)
        u = jax.random.uniform(ku, (chunk_blocks, BLOCK), jnp.float32)
        k = jnp.where(u < prob[k], k, alias[k])
        z, x = k // v_x, k % v_x
        bits = jnp.left_shift(jnp.uint32(1), (z & 31).astype(jnp.uint32))
        hit = (z >> 5)[..., None] == word_ids
        bitmap = jax.lax.reduce(
            jnp.where(hit, bits[..., None], jnp.uint32(0)),
            jnp.uint32(0), jax.lax.bitwise_or, (1,),
        )
        return z, x, bitmap

    def body(i, bufs):
        at = i * chunk_blocks
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(buf, part, at, 0)
            for buf, part in zip(bufs, chunk(i))
        )

    init = (
        jnp.zeros((num_blocks, BLOCK), jnp.int32),
        jnp.zeros((num_blocks, BLOCK), jnp.int32),
        jnp.zeros((num_blocks, words), jnp.uint32),
    )
    return jax.lax.fori_loop(0, num_blocks // chunk_blocks, body, init)


def make_table(dist: dict, num_tuples: int, seed_words) -> tuple:
    """(z_blocks, x_blocks, bitmap) device arrays of ``num_tuples`` draws.

    One jitted call; the chunks it draws are sized to sit beside the
    table. ``seed_words`` are two uint32 words derived from the run seed.
    """
    if num_tuples % BLOCK:
        raise ValueError(f"num_tuples must be a multiple of {BLOCK}, got {num_tuples}")
    v_z, v_x = dist["hists"].shape
    num_blocks = num_tuples // BLOCK
    prob, alias = alias_table((dist["freq"][:, None] * dist["hists"]).reshape(-1))
    key = jax.random.fold_in(jax.random.key(int(seed_words[0])), int(seed_words[1]))
    return _make_table(
        key, jnp.asarray(prob, jnp.float32), jnp.asarray(alias, jnp.int32),
        num_blocks=num_blocks, chunk_blocks=_largest_divisor(num_blocks, GEN_CHUNK_BLOCKS),
        v_z=v_z, v_x=v_x,
    )


def _split(bins: int) -> int:
    """Low-digit base for the two one-hots of `block_counts` (a power of two)."""
    return 1 << max(1, (int(np.ceil(np.log2(bins))) + 1) // 2)


@functools.partial(jax.jit, static_argnames=("v_z", "v_x"))
def _chunk_counts(z, x, valid, *, v_z, v_x):
    """int32 (V_Z, V_X) counts of the (P, BLOCK) blocks whose ``valid`` is set.

    The key z * V_X + x is split into a high and a low digit; the count of
    each key is the product of their one-hots contracted over the
    samples, exact in f32 while a chunk holds fewer than 2^24 tuples."""
    bins = v_z * v_x
    lo_n = _split(bins)
    hi_n = -(-bins // lo_n)
    keep = valid[:, None] & (z >= 0) & (x >= 0)
    key = jnp.where(keep, z * v_x + x, -1).reshape(-1)
    hi = jnp.where(key >= 0, key // lo_n, -1)
    lo = jnp.where(key >= 0, key % lo_n, -1)
    oh_hi = (hi[:, None] == jnp.arange(hi_n)).astype(jnp.bfloat16)
    oh_lo = (lo[:, None] == jnp.arange(lo_n)).astype(jnp.bfloat16)
    c = jax.lax.dot_general(
        oh_hi, oh_lo, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return c.astype(jnp.int32).reshape(-1)[:bins].reshape(v_z, v_x)


@functools.partial(jax.jit, static_argnames=("v_z", "v_x"))
def _table_counts(z_blocks, x_blocks, *, v_z, v_x):
    nb = z_blocks.shape[0]
    p = _largest_divisor(nb, COUNT_CHUNK_BLOCKS)
    valid = jnp.ones(p, bool)

    def body(i, acc):
        z = jax.lax.dynamic_slice_in_dim(z_blocks, i * p, p)
        x = jax.lax.dynamic_slice_in_dim(x_blocks, i * p, p)
        return acc + _chunk_counts(z, x, valid, v_z=v_z, v_x=v_x)

    return jax.lax.fori_loop(0, nb // p, body, jnp.zeros((v_z, v_x), jnp.int32))


@functools.partial(jax.jit, static_argnames=("v_z", "v_x"))
def _gathered_counts(acc, z_blocks, x_blocks, idx, valid, *, v_z, v_x):
    return acc + _chunk_counts(z_blocks[idx], x_blocks[idx], valid, v_z=v_z, v_x=v_x)


def table_counts(z_blocks, x_blocks, *, v_z: int, v_x: int) -> np.ndarray:
    """Exact int64 (V_Z, V_X) counts of the whole table (one device call)."""
    return np.asarray(_table_counts(z_blocks, x_blocks, v_z=v_z, v_x=v_x), np.int64)


def block_counts(z_blocks, x_blocks, blocks: np.ndarray, *, v_z: int, v_x: int) -> np.ndarray:
    """Exact int64 (V_Z, V_X) counts over the table rows ``blocks``."""
    blocks = np.asarray(blocks, np.int32)
    p = COUNT_CHUNK_BLOCKS
    acc = jnp.zeros((v_z, v_x), jnp.int32)
    for lo in range(0, blocks.size, p):
        part = blocks[lo : lo + p]
        idx = np.zeros(p, np.int32)
        idx[: part.size] = part
        acc = _gathered_counts(
            acc, z_blocks, x_blocks, jnp.asarray(idx), jnp.asarray(np.arange(p) < part.size),
            v_z=v_z, v_x=v_x,
        )
    return np.asarray(acc, np.int64)
