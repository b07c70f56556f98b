"""Fixtures of the benchmark's own tests (helpers in ``bench_helpers``)."""
import pytest

from bench_helpers import make_checkout


@pytest.fixture
def cpu_run(monkeypatch, tmp_path):
    """``run.main`` steered to the CPU at the tiny size: the device gate
    accepts the CPU, whose peaks are test values, and the persistent
    compilation cache stays off.  ``root`` runs another checkout."""
    import run
    from benchlib import device
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(run, "compile_cache", lambda: None)
    monkeypatch.setitem(device.PEAKS, "cpu",
                        device.Peaks(1e12, 1e11, 8e9, "test values"))
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    root = make_checkout(tmp_path / "checkout")

    def go(workload, *extra, seed=3000000017, seconds=2.0, root=root):
        return run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), *extra], root=root)
    return go
