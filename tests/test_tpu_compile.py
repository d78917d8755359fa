"""The main path's Pallas kernels compile for a TPU v5e.

Interpret-mode tests (test_kernels.py, test_metrics.py, ...) pin what
the kernels compute; they cannot see what the chip's compiler refuses:
block shapes whose layout Mosaic and XLA disagree on, blocks that
overflow the scoped VMEM, kernels left inside an automatically
partitioned program. These tests compile — without running anything —
for a described (not attached) v5e 2x2, at the served shapes:

  * the ingest histogram at the served window (512 blocks x 512 tuples)
    and a small one, for the benchmark's (V_Z, V_X) shapes, as exactly
    one kernel call;
  * the Q-batched distance at Q in {1, 8} for the same shapes, plus
    V_X = 8192 on the two-sweep lane-tiled path;
  * AnyActive at the packed bitmap widths of V_Z = 161 and 7,548, for
    windows of 1 to 5,000 blocks;
  * the device-resident source's window gather at the benchmark's table
    size, which must read the tables in place (no table-sized copy);
  * the whole jitted serving round (`multiquery.fused_round`) at the
    taxi_q1 and flights_q1 shapes, and at flights_q2's read mask over 600M
    tuples, holding each of the three kernels once; the statistics step
    (one distance kernel, every metric) and the exact-completion round
    (one ingest kernel) at the deployments' shapes;
  * the four-chip rounds: the pump on a 4x1 mesh and the single-stream
    candidate-sharded mesh on 1x4.

Keep them in this one file: the topology is described inside a fixture,
so only the worker that runs this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import distributed as dist
from repro.core import multiquery as mq
from repro.core.bitmap import words_for
from repro.io import WindowData
from repro.io.block_source import LANES, gather_window
from repro.kernels import ops

SHAPES = [(161, 24), (7548, 24), (191, 2), (161, 161)]
BLOCK = 512
SERVED_WINDOW = 512 * BLOCK  # lookahead 512 x 512-tuple blocks
KERNELS = {"histogram_with_rowsums", "distance_multi", "anyactive"}


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2, with the persistent compilation cache off:
    a compile for a chip that is not attached cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def pallas_engine(monkeypatch):
    """Steer `ops` to the Pallas engine as it would be on the chip (the
    sandbox backend is the CPU); drop programs traced under it after."""
    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
    yield
    jax.clear_caches()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> set:
    return ops.tpu_kernels_in(compiled.as_text())


def _kernel_calls(compiled):
    """Calls per kernel name: a set of names cannot tell one call from two."""
    return ops.tpu_kernel_calls(compiled.as_text())


@pytest.mark.parametrize(
    "v_z,v_x,n",
    [(vz, vx, SERVED_WINDOW) for vz, vx in SHAPES] + [(161, 24, 16 * BLOCK)],
)
def test_histogram_with_rowsums_compiles(one_chip, v_z, v_x, n):
    ids = _sds((n,), jnp.int32, one_chip)
    compiled = ops.histogram_with_rowsums.lower(
        ids, ids, v_z=v_z, v_x=v_x, impl="pallas"
    ).compile()
    assert _kernels(compiled) == {"histogram_with_rowsums"}
    # One call per ingest, which the ingest roofline's byte count assumes;
    # a set of names cannot tell one call from two.
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("q", [1, 8])
@pytest.mark.parametrize("v_z,v_x", SHAPES + [(256, 8192)])
def test_distance_multi_compiles(one_chip, v_z, v_x, q):
    compiled = ops.distance_multi.lower(
        _sds((v_z, v_x), jnp.float32, one_chip),
        _sds((q, v_x), jnp.float32, one_chip),
        impl="pallas",
    ).compile()
    assert _kernels(compiled) == {"distance_multi"}


@pytest.mark.parametrize("num_blocks", [1, 16, 512, 5000])
@pytest.mark.parametrize("v_z", [161, 7548])
def test_anyactive_compiles(one_chip, v_z, num_blocks):
    w = words_for(v_z)  # 6 and 236 packed words
    compiled = ops.anyactive.lower(
        _sds((num_blocks, w), jnp.uint32, one_chip), _sds((w,), jnp.uint32, one_chip),
        impl="pallas",
    ).compile()
    assert _kernels(compiled) == {"anyactive"}


@pytest.mark.parametrize("v_z", [161, 7548])
def test_window_gather_compiles(one_chip, v_z):
    """A 512-block window from a 600M-tuple table. A bitmap whose word
    axis is not lane-aligned (6 or 236 words) is copied whole into the
    gather's layout on every fetch; the source's lane-padded one is not.
    Each table's rows come in one fused gather, not a loop of per-block
    dynamic slices."""
    num_blocks, window, w = 1_171_875, 512, words_for(v_z)
    table = _sds((num_blocks, BLOCK), jnp.int32, one_chip)
    compiled = gather_window.lower(
        table, table, _sds((num_blocks, -(-w // LANES) * LANES), jnp.uint32, one_chip),
        _sds((window,), jnp.int32, one_chip), _sds((window,), jnp.bool_, one_chip), words=w,
    ).compile()
    hlo = compiled.as_text()
    table_copies = [
        line for line in hlo.splitlines()
        if re.search(r"\bcopy(-start|-done)?\(", line) and f"[{num_blocks}," in line
    ]
    assert not table_copies
    assert not re.search(r"\bwhile\(", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    assert compiled.out_info.bitmap.shape == (window, w)


def _round_args(spec, num_blocks, lookahead, state_sharding, rep):
    state = jax.eval_shape(lambda: mq.init_multi_state(spec))
    state = jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, s), state, state_sharding
    )
    cursor = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, rep),
        jax.eval_shape(lambda: mq.init_cursor(num_blocks)),
    )
    wd = WindowData(
        indices=_sds((lookahead,), jnp.int32, rep),
        z=_sds((lookahead, BLOCK), jnp.int32, rep),
        x=_sds((lookahead, BLOCK), jnp.int32, rep),
        bitmap=_sds((lookahead, words_for(spec.v_z)), jnp.uint32, rep),
        valid=_sds((lookahead,), jnp.bool_, rep),
    )
    return state, cursor, wd


@pytest.mark.parametrize("v_z,v_x,num_blocks",
                         [(7548, 24, 62_500), (161, 24, 11_719), (161, 24, 1_171_875)])
def test_fused_round_compiles(one_chip, pallas_engine, v_z, v_x, num_blocks):
    """The whole served round (taxi_q1 / flights_q1 shapes, max_queries=8,
    lookahead=512) compiles with each of the three kernels called once; the
    last case is a flights_q2 cold session's round, its read mask over 600M
    tuples."""
    spec = mq.MultiQuerySpec(v_z=v_z, v_x=v_x, max_queries=8, k_cap=10)
    state_sharding = jax.tree.map(lambda _: one_chip, mq.init_multi_state(spec))
    args = _round_args(spec, num_blocks, 512, state_sharding, one_chip)
    compiled = mq.fused_round.lower(*args, spec=spec, policy="anyactive").compile()
    assert _kernel_calls(compiled) == dict.fromkeys(KERNELS, 1)


@pytest.mark.parametrize("metric", ["l1", "chi2", "hellinger"])
@pytest.mark.parametrize("v_z,v_x", [(7548, 24), (161, 24)])
def test_stats_step_compiles(one_chip, pallas_engine, v_z, v_x, metric):
    """The statistics step at the taxi and flights shapes: one distance
    kernel scores all eight slots, for every metric."""
    spec = mq.MultiQuerySpec(v_z=v_z, v_x=v_x, max_queries=8, k_cap=10, metric=metric)
    state_sharding = jax.tree.map(lambda _: one_chip, mq.init_multi_state(spec))
    state, _, _ = _round_args(spec, 1, 1, state_sharding, one_chip)
    compiled = mq.stats_step.lower(state, spec=spec).compile()
    assert _kernel_calls(compiled) == {"distance_multi": 1}


@pytest.mark.parametrize("v_z,v_x,num_blocks", [(7548, 24, 62_500), (161, 24, 11_719)])
def test_ingest_round_compiles(one_chip, pallas_engine, v_z, v_x, num_blocks):
    """The exact-completion round (Scan) at the taxi and flights shapes:
    one ingest kernel over the 512-block window, no marking, no stats."""
    spec = mq.MultiQuerySpec(v_z=v_z, v_x=v_x, max_queries=8, k_cap=10)
    state_sharding = jax.tree.map(lambda _: one_chip, mq.init_multi_state(spec))
    args = _round_args(spec, num_blocks, 512, state_sharding, one_chip)
    compiled = mq.ingest_round.lower(*args, spec=spec).compile()
    assert _kernel_calls(compiled) == {"histogram_with_rowsums": 1}


@pytest.mark.parametrize("layout", ["pump_4x1", "stream_1x4"])
def test_four_chip_rounds_compile(topo, pallas_engine, layout):
    """The mesh rounds: every kernel sits in a manual (shard_map) region,
    which the TPU compiler requires of a program spanning four chips."""
    spec = mq.MultiQuerySpec(v_z=7548, v_x=24, max_queries=8, k_cap=10)
    num_blocks, lookahead = 62_500, 512
    devices = np.array(topo.devices[:4])
    shape = (4, 1) if layout == "pump_4x1" else (1, 4)
    mesh = Mesh(devices.reshape(shape), ("data", "model"))
    state_sharding = jax.tree.map(
        lambda p: NamedSharding(mesh, p), dist.multi_state_pspecs()
    )
    rep = NamedSharding(mesh, P())
    if layout == "stream_1x4":
        args = _round_args(spec, num_blocks, lookahead, state_sharding, rep)
        fn = dist.make_stream_rounds(mesh, spec).round
    else:
        per = -(-num_blocks // 4)
        state, _, _ = _round_args(spec, num_blocks, lookahead, state_sharding, rep)
        cursor = jax.tree.map(
            lambda a, p: _sds(a.shape, a.dtype, NamedSharding(mesh, p)),
            jax.eval_shape(lambda: mq.init_cursor(per * 4)), dist.cursor_pspecs(),
        )
        _, _, wd = _round_args(spec, num_blocks, 4 * lookahead, state_sharding, rep)
        wd = jax.tree.map(
            lambda a, p: _sds(a.shape, a.dtype, NamedSharding(mesh, p)),
            wd, dist.window_pspecs(),
        )
        args = (state, cursor, wd)
        fn = dist.make_pump_round(mesh, spec, blocks_per_worker=per)
    compiled = fn.lower(*args).compile()
    assert _kernels(compiled) == KERNELS
    assert "all-reduce" in compiled.as_text()

