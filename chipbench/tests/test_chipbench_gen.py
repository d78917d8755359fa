"""The benchmark's device generator and its exact counts, at small sizes."""

import hashlib

import numpy as np
import pytest

from chipbench import gen, harness, spec
from repro.core.bitmap import build_block_bitmap

CFG = dict(v_z=300, v_x=24, n_close=10, close_distance=0.05, far_distance=0.45,
           zipf_a=0.3)


@pytest.fixture(scope="module")
def table():
    dist = gen.distributions(CFG, np.random.default_rng(5))
    z, x, bitmap = gen.make_table(dist, 512 * 96, np.array([11, 2**32 - 1], np.uint64))
    return dist, np.asarray(z), np.asarray(x), np.asarray(bitmap), (z, x)


def test_distributions_plant_the_close_set():
    dist = gen.distributions(CFG, np.random.default_rng(0))
    d = np.abs(dist["hists"] - dist["target"]).sum(axis=1)
    close = dist["close_ids"]
    assert np.array_equal(close, np.arange(10))
    assert d[close].max() < 0.1
    assert np.delete(d, close).min() >= CFG["far_distance"]
    assert np.isclose(dist["freq"].sum(), 1.0) and np.all(np.diff(dist["freq"]) <= 0)


@pytest.mark.parametrize("target_kind", ["peaked", "uniform"])
def test_tail_close_rank_plants_the_least_frequent(target_kind):
    cfg = dict(CFG, close_rank="tail", target_kind=target_kind, zipf_a=1.2)
    dist = gen.distributions(cfg, np.random.default_rng(0))
    d = np.abs(dist["hists"] - dist["target"]).sum(axis=1)
    close = dist["close_ids"]
    assert np.array_equal(close, np.arange(CFG["v_z"] - 10, CFG["v_z"]))
    assert np.all(dist["freq"][close] < dist["freq"][: CFG["v_z"] - 10].min())
    assert d[close].max() < 0.1
    assert d[: CFG["v_z"] - 10].min() >= CFG["far_distance"]


def test_uniform_target_is_exact_and_draws_nothing():
    cfg = dict(CFG, target_kind="uniform")
    dist = gen.distributions(cfg, np.random.default_rng(6))
    assert np.array_equal(dist["target"], np.full(CFG["v_x"], 1.0 / CFG["v_x"]))
    # the first draw of the stream goes to the first close candidate
    first = gen.perturb_distribution(
        dist["target"], CFG["close_distance"] * 0.5, np.random.default_rng(6)
    )
    assert np.array_equal(dist["hists"][0], first)


@pytest.mark.parametrize("key,value", [("close_rank", "middle"), ("close_rank", None),
                                       ("target_kind", "Uniform"), ("target_kind", "zipf")])
def test_unknown_shape_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        gen.distributions(dict(CFG, **{key: value}), np.random.default_rng(0))


# sha256 prefixes of what the default shape (head, peaked) makes from a seed,
# pinned when the shape keys were added: a change to how the default path
# draws from its random streams moves the cells' tables and fails here
PIN_SEED = 4_500_000_001
PINS = {
    "small.0": "d5b7ba3f8d3ef0fc",
    "small.7": "ef4c4622a63b2c93",
    "taxi_q1.dist": "a758adbb6570c75f",
    "taxi_q1.table": "75e62400fc7aec64",
    "taxi_q1.targets": "f78d5719ca37dcdc",
    "flights_q1.dist": "eecba41539c1307a",
    "flights_q1.table": "09ff6712fd0ded3b",
}


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _pinned(name: str) -> str:
    if name.startswith("small."):
        dist = gen.distributions(CFG, np.random.default_rng(int(name.split(".")[1])))
        return _digest(dist["target"], dist["freq"], dist["hists"], dist["close_ids"])
    config, part = name.split(".")
    cfg = spec.load_json(spec.HERE / "configs" / f"{config}.json")
    words = harness.seed_words(PIN_SEED)
    dist = gen.distributions(cfg, np.random.default_rng(words[:2]))
    if part == "dist":
        return _digest(dist["target"], dist["freq"], dist["hists"], dist["close_ids"])
    if part == "table":
        return _digest(*gen.make_table(dist, 512 * 64, words[:2]))
    # per-query targets around the planted one, drawn as `load.Client.submit` draws them
    lo, hi = spec.load_json(spec.HERE / "traffic" / "cold8.json")["target_l1"]
    rng = np.random.default_rng(words[2:])
    return _digest(*(gen.perturb_distribution(dist["target"], float(rng.uniform(lo, hi)), rng)
                     for _ in range(8)))


@pytest.mark.parametrize("name", sorted(PINS))
def test_default_shape_is_pinned(name):
    assert _pinned(name) == PINS[name]


def test_table_shapes_and_ranges(table):
    _, z, x, bitmap, _ = table
    assert z.shape == x.shape == (96, gen.BLOCK)
    assert bitmap.shape == (96, -(-CFG["v_z"] // 32))
    assert z.min() >= 0 and z.max() < CFG["v_z"]
    assert x.min() >= 0 and x.max() < CFG["v_x"]


def test_same_seed_same_table_other_seed_other_table():
    dist = gen.distributions(CFG, np.random.default_rng(1))
    a = np.asarray(gen.make_table(dist, 512 * 8, [3, 4])[0])
    b = np.asarray(gen.make_table(dist, 512 * 8, [3, 4])[0])
    c = np.asarray(gen.make_table(dist, 512 * 8, [3, 5])[0])
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_bitmap_equals_the_programs_build(table):
    _, z, _, bitmap, _ = table
    assert np.array_equal(bitmap, build_block_bitmap(z, CFG["v_z"]))


def test_table_counts_equal_bincount(table):
    _, z, x, _, (zd, xd) = table
    v_z, v_x = CFG["v_z"], CFG["v_x"]
    want = np.bincount((z * v_x + x).ravel(), minlength=v_z * v_x).reshape(v_z, v_x)
    assert np.array_equal(gen.table_counts(zd, xd, v_z=v_z, v_x=v_x), want)


@pytest.mark.parametrize("blocks", [[], [0], [5, 3, 95], list(range(0, 96, 2))])
def test_block_counts_equal_bincount(table, blocks):
    _, z, x, _, (zd, xd) = table
    v_z, v_x = CFG["v_z"], CFG["v_x"]
    rows = np.asarray(blocks, int)
    want = np.bincount(
        (z[rows] * v_x + x[rows]).ravel(), minlength=v_z * v_x
    ).reshape(v_z, v_x)
    assert np.array_equal(gen.block_counts(zd, xd, rows, v_z=v_z, v_x=v_x), want)


def test_alias_table_gives_the_distribution():
    rng = np.random.default_rng(3)
    p = rng.dirichlet(np.full(50, 0.3))
    prob, alias = gen.alias_table(p)
    # slot i keeps prob[i] / n for itself and hands the rest to alias[i]
    got = prob / p.size
    np.add.at(got, alias, (1.0 - prob) / p.size)
    assert np.allclose(got, p, atol=1e-12)


def test_table_draws_follow_the_joint_distribution():
    dist = gen.distributions(CFG, np.random.default_rng(4))
    z, x, _ = gen.make_table(dist, 512 * 512, [9, 10])
    got = np.bincount(np.asarray(z).ravel(), minlength=CFG["v_z"]) / (512 * 512)
    assert np.abs(got - dist["freq"]).sum() < 0.05
    head = np.asarray(x)[np.asarray(z) == 0]
    assert np.abs(np.bincount(head, minlength=CFG["v_x"]) / head.size
                  - dist["hists"][0]).sum() < 0.1


def test_table_size_must_be_whole_blocks():
    dist = gen.distributions(CFG, np.random.default_rng(2))
    with pytest.raises(ValueError, match="multiple of 512"):
        gen.make_table(dist, 1000, [1, 2])
