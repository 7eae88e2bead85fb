"""chip_smoke.py's phases and checks, rehearsed on the CPU at a tiny size.

The smoke itself refuses to run without a TPU; these tests drive its
phase functions directly.  The single-chip run routes ``run_cycle``
down its TPU branch with the dense Pallas kernel in interpret mode, so
the ``path == "pallas"`` check sees what the chip would report.
"""

import jax
import pytest

import chip_smoke


def test_single_chip_phases_pass_on_interpreted_kernel(tmp_path,
                                                       monkeypatch):
    from koordinator_tpu.solver import pallas_dense

    dense = pallas_dense.greedy_assign_dense
    monkeypatch.setattr(
        pallas_dense, "greedy_assign_dense",
        lambda *a, **k: dense(*a, interpret=True, **k),
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    failures = chip_smoke.run_single(
        seed=3, pods=96, nodes=24, workdir=str(tmp_path / "smoke")
    )
    assert failures == []


def test_mesh_phase_passes_on_virtual_devices(tmp_path):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    failures = chip_smoke.run_mesh(
        4, seed=5, pods=96, nodes=32, workdir=str(tmp_path / "smoke")
    )
    assert failures == []


def test_smoke_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out
