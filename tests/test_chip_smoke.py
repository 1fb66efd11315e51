"""CPU rehearsal of ``chip_smoke.py`` and the backend/cache helpers.

The smoke's phase functions run here at tiny sizes (steered from the
test through their size arguments); ``main()`` must refuse a CPU
backend.  The selector tests pin the one host-or-device decision, and
the cache tests pin where the persistent compile cache lives.
"""

import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.control.policy import resolve_impl
from repro.core import backend

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the backend probe see a TPU (nothing is compiled)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# -- the one host-or-device selector ----------------------------------------

def test_interpret_selector_follows_backend():
    assert backend.on_host()
    assert backend.resolve_interpret(None) is True
    assert backend.resolve_interpret(False) is False
    assert backend.resolve_interpret(True) is True


def test_interpret_selector_compiles_on_tpu(on_tpu):
    assert not backend.on_host()
    assert backend.resolve_interpret(None) is False
    assert backend.resolve_interpret(True) is True


@pytest.mark.parametrize("tpu,expect", [(False, "numpy"), (True, "jit")])
def test_control_impl_uses_the_same_probe(monkeypatch, tpu, expect):
    if tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_impl("auto") == expect
    assert resolve_impl("numpy") == "numpy"
    assert resolve_impl("jit") == "jit"
    with pytest.raises(ValueError):
        resolve_impl("gpu")


# -- compile cache location --------------------------------------------------

def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.compile_cache_dir() == str(tmp_path)


def test_compile_cache_fallback_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = Path(backend.compile_cache_dir())
    assert path == ROOT / ".jax_cache"
    assert path.parent == backend.CHECKOUT == ROOT
    assert backend.compile_cache_dir() == str(path)   # same every call


# -- chip_smoke.py -----------------------------------------------------------

def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main() == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err


def test_phase_device_refuses_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.phase_device()


def test_phase_fleet_tiny(smoke, capsys):
    res = smoke.phase_fleet(n_ends=300, chunk_t=32, n_chunks=8,
                            block_q=128)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "fleet"
    assert res["interpret"] is True and res["mosaic_kernel"] is False
    for pair in res["parity_vs_scan"].values():   # 300 ends: no split
        assert pair["split_ends"] == 0 and pair["max_epoch_diff"] == 0
        assert pair["max_rel_diff_agreeing"] < 1e-4
    for band in res["fig13_band"].values():
        assert band["converged_frac"] >= 0.99
        assert band["in_band_frac"] == 1.0
    assert res["periods_per_end"] == 256


def test_parity_counts_and_replays_split_ends(smoke):
    """An end whose final state differs from the oracle counts as split
    and is replayed from the dispatch where the states part; a planted
    split with no decision near the Eq. 4 threshold there is unexplained,
    and the check fails on it."""
    from repro.core.monitor import MonitorConfig
    cfg = MonitorConfig()
    _, tc, blocked = smoke.fleet_streams(40, 32, 4, seed=1)
    ref, rounds = (smoke.run_by_dispatch(cfg, tc, blocked, impl, 32, 128)
                   for impl in ("scan", "rounds"))
    bad = [st._replace(
        epoch=st.epoch.at[3].add(int(k >= 2)),
        last_qbar=st.last_qbar.at[7].multiply(1.01 if k >= 1 else 1.0),
        mean=st.mean.at[7].multiply(1.01 if k >= 1 else 1.0))
        for k, st in enumerate(rounds)]
    res = smoke._parity(cfg, tc, blocked, bad, ref, "rounds", 32, 128)
    assert res["split_ends"] == 2 and res["replayed"] == 2
    assert res["max_epoch_diff"] == 1
    assert res["unexplained"] == res["causes"]["no_near_decision"] == 2
    with pytest.raises(smoke.SmokeFailure, match="without a near-thresh"):
        smoke.check_parity("rounds", res)
    clean = smoke._parity(cfg, tc, blocked, rounds, ref, "rounds", 32, 128)
    assert clean["unexplained"] == 0
    smoke.check_parity("rounds", clean)
    with pytest.raises(smoke.SmokeFailure, match="limit"):
        smoke.check_parity("pallas", {**clean, "split_frac": 1e-3})


def test_eq4_margins_reproduce_the_oracle_and_flag_a_wrong_one(smoke):
    """The float64 Eq. 4 recomputation agrees with every decision the
    scan oracle takes, and disowns a convergence whose sigma trace was
    planted off by far more than rounding."""
    from repro.core.monitor import (MonitorConfig, fleet_monitor_init,
                                    run_monitor_fleet)
    from repro.kernels.monitor.ref import fleet_static_params
    cfg = MonitorConfig()
    P = fleet_static_params(cfg)
    _, tc, blocked = smoke.fleet_streams(24, 256, 4, seed=2)
    _, out = run_monitor_fleet(cfg, tc, blocked, chunk_t=256, impl="scan",
                               mode="full", block_q=128)
    out = jax.tree_util.tree_map(np.asarray, out)
    init = jax.tree_util.tree_map(np.asarray, fleet_monitor_init(cfg, 1))
    st = jax.tree_util.tree_map(lambda x: x[0], init)
    valid = ~np.asarray(blocked)
    ready = valid & (np.cumsum(valid, axis=1) >= cfg.window)
    n_conv = 0
    for i in range(out.converged.shape[0]):
        one = smoke.MonitorOutput(*(x[i] for x in out))
        margin = smoke.eq4_margins(P, st, one, ready[i])
        assert np.all((one.converged == (margin > 0))
                      | (np.abs(margin) <= smoke.DECISION_SLACK))
        n_conv += int(np.sum(margin > 0))
    assert n_conv == out.epoch[:, -1].sum() > 0
    i, t = np.argwhere(out.converged)[0]
    bad = smoke.MonitorOutput(*(x[i] for x in out))
    bad = bad._replace(sigma_qbar=bad.sigma_qbar * 1e3)
    margin = smoke.eq4_margins(P, st, bad, ready[i])
    assert margin[t] < -smoke.DECISION_SLACK          # disowns step t


def test_phase_pipelines_tiny(smoke):
    # Rabin-Karp's Python stages starve the sampler of the interpreter
    # lock: give it seconds of work so a loaded host still samples it
    res = smoke.phase_pipelines(matmul_n=2048, rk_reps=1_000_000,
                                loop_items=3000)
    assert res["fig17_rabin_karp"]["matches"] == 1_000_000
    loop = res["closed_loop"]
    assert loop["impl"] == "numpy" and not loop["impl_degraded"]
    for app in res.values():
        assert app["dispatches"] > 0 and app["periods_sampled"] > 0
    assert max(loop["head_epochs"], loop["tail_epochs"]) >= 1
    assert max(loop["service_rate"], loop["arrival_rate"]) > 0


def test_phase_serve_tiny(smoke):
    res = smoke.phase_serve(arch_cfg=get_smoke_config("internlm2-1.8b"),
                            n_requests=8, prompt_len=8, max_new=4)
    assert res["answered"] == res["match_reference"] == 8
    assert res["crashes"] == 0
