"""The Pallas cache kernels and the classification program compile for a
TPU v5e.

Interpret mode (the other kernel tests) checks what the kernels compute;
only the TPU compiler checks that they can run on the chip at all: tiling
of every load and store, the reductions Mosaic lowers, and the VMEM each
grid step holds.  These tests compile ``lru_hits``, ``lru_hits_carry`` and
``fused_levels_pallas`` for a described (not attached) ``v5e:2x2`` chip at
the SCALED and PAPER geometries, one case at 2^18 time steps so the chunked
time axis is what keeps VMEM bounded.  Nothing runs, so nothing here says
anything about results or speed.

The topology is described inside a fixture: only the test worker that is
given this file loads the TPU compiler, and a host where it cannot be
described skips these tests.  The persistent compilation cache is off
around the compiles (an entry written for a described chip cannot be read
back without one).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cache_sim.cache_sim import lru_hits, lru_hits_carry
from repro.kernels.cache_sim.fused_sim import fused_levels_pallas
from repro.memsim.config import PAPER, SCALED

LONG = 1 << 18  # time steps: 256 chunks of the streamed time axis


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def shape(one_chip, no_compile_cache):
    def make(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)

    return make


def _levels(cfg):
    return tuple((c.sets, c.ways) for c in (cfg.l1, cfg.l2, cfg.llc))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "sets,ways,steps",
    [(SCALED.l1.sets, SCALED.l1.ways, LONG), (PAPER.llc.sets, PAPER.llc.ways, 256)],
    ids=["scaled-l1-long", "paper-llc"],
)
def test_lru_hits_compiles(shape, sets, ways, steps):
    run = jax.jit(functools.partial(lru_hits, ways=ways))
    _assert_kernel(run.lower(shape(steps, sets)).compile())


@pytest.mark.parametrize(
    "cfg,level",
    [(cfg, level) for cfg in (SCALED, PAPER) for level in ("l1", "l2", "llc")],
    ids=[f"{c}-{lv}" for c in ("scaled", "paper") for lv in ("l1", "l2", "llc")],
)
def test_lru_hits_carry_compiles(shape, cfg, level):
    geom = getattr(cfg, level)
    state = shape(geom.sets, geom.ways)
    compiled = lru_hits_carry.lower(shape(4096, geom.sets), state, state).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize(
    "cfg,steps", [(SCALED, LONG), (PAPER, LONG)], ids=["scaled", "paper"]
)
def test_fused_levels_compiles(shape, cfg, steps):
    levels = _levels(cfg)
    groups = min(sets for sets, _ in levels)
    state = []
    for sets, ways in levels:
        state += [shape(groups, sets // groups * ways)] * 2
    compiled = fused_levels_pallas.lower(
        shape(steps, groups), levels, *state
    ).compile()
    _assert_kernel(compiled)


def test_classify_program_compiles(one_chip, no_compile_cache):
    """The chain-classification program (one sort, shifted scans, one
    scatter) lowers for the chip; at 2^12 events, since the TPU compiler
    takes tens of seconds for a sort of 2^21."""
    from repro.memsim.classify_device import _classify_program

    def arg(dtype, dims=(1 << 12,)):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = _classify_program.lower(
        arg(jnp.int32),
        arg(jnp.int32),
        arg(jnp.bool_),
        arg(jnp.bool_),
        arg(jnp.int8),
        arg(jnp.int32, ()),
    ).compile()
    text = compiled.as_text()
    assert " sort(" in text and " scatter(" in text
