"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, so what Mosaic or XLA would refuse on the chip (unaligned
lane slices, unsupported shape casts, VMEM overflow, device OOM) fails
here.  The topology is described inside a fixture — never at import —
because only one process at a time may load the TPU library.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.control.policy import (ControlConfig, _decide_step,
                                  _jit_operands, control_init)
from repro.core.monitor import (MonitorConfig, _fleet_dispatch,
                                fleet_monitor_init)
from repro.kernels.monitor.kernel import (N_FSTATE, N_ISTATE,
                                          monitor_fleet_pallas)

FLEET_Q = 200_192          # 2e5 monitored ends, padded to block_q = 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("qp", [256, FLEET_Q])
@pytest.mark.parametrize("chunk_t", [32, 256])
def test_monitor_fleet_kernel_compiles_for_v5e(one_chip, qp, chunk_t):
    cfg = MonitorConfig()
    f32, i32 = jnp.float32, jnp.int32
    args = [_spec(s, dt, one_chip) for s, dt in [
        ((chunk_t, qp), f32), ((1, qp), i32), ((cfg.window, qp), f32),
        ((N_FSTATE, qp), f32), ((N_ISTATE, qp), i32),
        ((cfg.conv_window, qp), f32), ((2, qp), f32),
        ((cfg.conv_window, qp), f32)]]
    compiled = monitor_fleet_pallas.lower(
        cfg, *args, block_q=256, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_monitor_fleet_kernel_rejects_unaligned_block(one_chip):
    cfg = MonitorConfig()
    args = [_spec(s, jnp.float32, one_chip) for s in [
        (32, 512), (1, 512), (cfg.window, 512), (N_FSTATE, 512),
        (N_ISTATE, 512), (cfg.conv_window, 512), (2, 512),
        (cfg.conv_window, 512)]]
    with pytest.raises(ValueError, match="multiple of 128"):
        monitor_fleet_pallas.lower(cfg, *args, block_q=64,
                                   interpret=False)


def test_control_decision_compiles_for_v5e(one_chip):
    cfg = ControlConfig()
    q = 200_000
    z = np.zeros(q, np.float32)
    ops = dict(lam=z, mu=z, ready=np.zeros(q, bool), replicas=1,
               rep_basis=1, caps=64, cv2=1.0, occupancy=0.0,
               saturated=False, scalable=True, stale=False, faulty=False,
               leg_rep=True, leg_buf=True, leg_adm=False, headroom=1.2,
               max_reps=8, occ_hi=0.9, occ_lo=0.5, pressure=0.0,
               slo_target=np.nan, over_frac=np.nan)
    state, operands = _jit_operands(cfg, control_init(cfg, q), q, 0.0, ops)
    shapes = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, one_chip), (state, operands))
    compiled = _decide_step(cfg, True).lower(shapes[0],
                                             **shapes[1]).compile()
    assert compiled.memory_analysis() is not None


def test_fleet_step_compiles_for_v5e_without_sort_scatter_or_gather(
        one_chip):
    """The benchmark's fleet step (rounds, state mode, 256-period tiles,
    2e5 ends) compiles with no sort, scatter or gather: the v5e runs
    per-row gathers element by element and lowers a per-row scatter to a
    sort of every destination key."""
    cfg = MonitorConfig()
    state = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: fleet_monitor_init(cfg, FLEET_Q)))
    step = _fleet_dispatch(cfg, "rounds", "state", False, 256, True)
    tc = _spec((FLEET_Q, 256), jnp.float32, one_chip)
    blocked = _spec((FLEET_Q, 256), jnp.bool_, one_chip)
    compiled = step.lower(state, tc, blocked).compile()
    hlo = compiled.as_text()
    for op in ("sort", "scatter", "gather"):
        assert not re.search(rf"\b{op}\(", hlo), op
