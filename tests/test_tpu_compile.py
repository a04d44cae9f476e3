"""The decision engine's device programs compile for a described TPU v5e.

Nothing runs: each case lowers one jitted program for a chip that is
described, not attached, and compiles it with the TPU compiler — which
refuses what interpret mode accepts (unaligned blocks, dynamic lane
slices, unsupported primitives).  Shapes are the 10x instance's
(T=500, 100+100 servers, d1=1280).  The topology is described inside a
fixture, never at import, so every worker collects the same tests and only
the one that runs them loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import R

T, T_PAD, H, K, D1 = 500, 512, 100, 100, 1280
LANES = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(sh, t):
    """Engine state view ``(g, v, wcaps, scaps, U1, U2, L1, L2)``."""
    return (_spec(sh, (t, H, R)), _spec(sh, (t, K, R)), _spec(sh, (H, R)),
            _spec(sh, (K, R)), _spec(sh, (R,)), _spec(sh, (R,)),
            _spec(sh, ()), _spec(sh, ()))


@pytest.mark.parametrize("m_pad", [64, 128])
def test_sweep_kernel_compiles(one_chip, m_pad):
    from repro.kernels.minplus.kernel import minplus_sweep_pallas
    rows = _spec(one_chip, (T, m_pad))
    c = jax.jit(lambda r: minplus_sweep_pallas(r, D1 - 1)).lower(
        rows).compile()
    assert "tpu_custom_call" in c.as_text()


def test_single_slot_kernel_compiles(one_chip):
    from repro.kernels.minplus.ops import minplus
    row, prev = _spec(one_chip, (257,)), _spec(one_chip, (4097,))
    c = jax.jit(minplus).lower(row, prev).compile()
    assert "tpu_custom_call" in c.as_text()


def _decide_one_launch(sh):
    """The single-arrival launch on TPU (``use_pallas=True``), compiled."""
    from repro.core.schedule_jax import _decide_one
    m_pad = 128
    jd = (_spec(sh, (2 * R + 2,)), _spec(sh, (2, m_pad), jnp.int32),
          _spec(sh, (T,)), _spec(sh, (3,), jnp.int32))
    return _decide_one.lower(_state(sh, T), jd, d1=D1,
                             use_pallas=True).compile()


def _tiled_launch(sh, m_pad):
    """The burst launch at the TPU lane count, compiled."""
    from repro.core.schedule_jax import _decide_tiled
    sd = _state(sh, T_PAD) + (_spec(sh, (T_PAD, R)), _spec(sh, (T_PAD, H, R)),
                              _spec(sh, (T_PAD, K, R)))
    jd = (_spec(sh, (LANES, 2 * R + 2)),
          _spec(sh, (LANES, 2, m_pad), jnp.int32),
          _spec(sh, (LANES, T_PAD)), _spec(sh, (LANES, T_PAD)),
          _spec(sh, (LANES, 4), jnp.int32), _spec(sh, (LANES,)))
    tabs = (_spec(sh, (1, 1, 1)),) * 6
    rows = _spec(sh, (LANES, T_PAD, m_pad))
    valid = _spec(sh, (LANES, T_PAD // 64), jnp.bool_)
    return _decide_tiled.lower(sd, jd, tabs, rows, valid, T=T, d1=D1,
                               use_cache=True, mono=0,
                               use_tabs=False).compile()


def test_legacy_decide_launch_holds_the_kernel(one_chip):
    """The single-arrival launch on TPU (``use_pallas=True``) carries the
    Mosaic kernel, not the interpreter or a jnp stand-in."""
    assert "tpu_custom_call" in _decide_one_launch(one_chip).as_text()


@pytest.mark.parametrize("m_pad", [64, 128])
def test_tiled_decide_compiles_with_eight_lanes(one_chip, m_pad):
    """The burst launch at the TPU lane count."""
    c = _tiled_launch(one_chip, m_pad)
    assert c.memory_analysis().temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("program", ["_decide_tiled", "_decide_one"])
def test_row_build_lowers_without_search_or_gather(one_chip, program):
    """Lowered for the TPU, the COST-row build (scope ``decide.rows``)
    holds no binary search and no gather: the server sort carries the
    capacities and the greedy-cost lookup is dense masked reductions
    (``schedule_jax._greedy_cost``), the form the chip runs fastest."""
    c = (_tiled_launch(one_chip, 128) if program == "_decide_tiled"
         else _decide_one_launch(one_chip))
    rows = []
    for line in c.as_text().splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and "decide.rows" in m.group(1).split("/"):
            rows.append((line, m.group(1)))
    assert any(" sort(" in line for line, _ in rows)
    assert not [p for _, p in rows if "searchsorted" in p]
    assert not [line for line, _ in rows if " gather(" in line]


def test_accept_launches_compile(one_chip):
    """Backtrack and placement, the two launches an accept adds."""
    from repro.core.schedule_jax import _backtrack, _place_slots
    sh, m_pad, wa = one_chip, 128, 32
    i32 = jnp.int32
    _backtrack.lower(_spec(sh, (T_PAD, m_pad)), _spec(sh, (T_PAD, D1)),
                     _spec(sh, (), i32), _spec(sh, (), i32),
                     _spec(sh, (), i32)).compile()
    _place_slots.lower(_state(sh, T), _spec(sh, (2 * R + 2,)),
                       _spec(sh, (wa,)), _spec(sh, (wa,)),
                       _spec(sh, (wa,), i32), wa=wa).compile()


@pytest.mark.parametrize("op", ["store", "roll"])
def test_donated_price_window_ops_compile(one_chip, op):
    """The price state's donated window store (commit/release) and roll
    (window slide), which are only donated off the CPU."""
    from repro.core.pricing import _window_roll_jit, _window_set_jit
    buf = _spec(one_chip, (64, H, R))
    k = _spec(one_chip, (), jnp.int32)
    if op == "store":
        c = _window_set_jit(True).lower(buf, _spec(one_chip, (8, H, R)),
                                        k).compile()
    else:
        c = _window_roll_jit(True).lower(buf, k).compile()
    # the donated buffer is aliased to the output: updated in place
    assert "input_output_alias={ {}: (0, {}" in c.as_text()
