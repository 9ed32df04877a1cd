"""chip_smoke.py's phases at a tiny size on the CPU.

The phases run here as they are: off the TPU the engine picks interpret
mode and the sharded round the jnp reference, so these tests check the
wiring (spec, data, fit, parity arithmetic), not the chip. The platform
check and the ``tpu_custom_call`` count belong to ``main()``.
"""
import chip_smoke as cs
import numpy as np
import pytest

TINY_SIM = cs.SimSize(levels=(2, 2), image_shape=(8, 8, 1), batch=4,
                      samples=400, group_rounds=1, local_steps=2, rounds=2)
TINY_LM = cs.LMSize(seq=16, batch=1, rounds=2)


@pytest.mark.parametrize("name", sorted(cs.SIM_PHASES))
def test_sim_phase_runs_fused_and_reference(name):
    out = cs.sim_phase(name, TINY_SIM)
    phase = cs.SIM_PHASES[name]
    for run in out["runs"].values():
        assert run.kernels == 0            # interpret mode lowers no Mosaic
        assert run.losses.shape == (TINY_SIM.rounds,)
        assert np.isfinite(run.losses).all()
        assert [x.shape[:2] for x in run.x] == [TINY_SIM.levels]
    assert out["parity"]["loss_rel"] <= phase.loss_rtol
    assert out["parity"]["disp_rel"] <= phase.disp_rtol


def test_sharded_phase_runs():
    out = cs.sharded_phase(TINY_LM)
    run = out["run"]
    assert out["leaves"] == len(run.x)     # tree layout: one buffer per leaf
    assert np.isfinite(run.losses).all() and run.losses.shape == (2,)


def test_parity_measures_displacement():
    x0 = [np.zeros((2, 2, 3), np.float32)]
    ref = cs.Run(0, 0.0, 0.0, 0.0, np.array([2.0]), x0,
                 [np.full((2, 2, 3), 1.0, np.float32)])
    off = cs.Run(0, 0.0, 0.0, 0.0, np.array([2.2]), x0,
                 [np.full((2, 2, 3), 1.5, np.float32)])
    par = cs.parity(off, ref)
    assert par["loss_rel"] == pytest.approx(0.1)
    assert par["disp_rel"] == pytest.approx(0.5)


def test_count_kernels_reads_optimized_hlo():
    hlo = ('%a = f32[8] custom-call(%x), custom_call_target="tpu_custom_call"\n'
           '%b = f32[8] custom-call(%a), custom_call_target="tpu_custom_call"\n'
           '%c = f32[8] custom-call(%b), custom_call_target="Sharding"\n')
    assert cs.count_kernels(hlo) == 2


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert '"ok"' not in captured.out
