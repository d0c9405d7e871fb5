"""``benchmarks/run.py`` must not report a failed bench module as a pass."""
import pytest


def test_failed_module_stops_the_run(monkeypatch, tmp_path):
    from benchmarks import bench_kernels, run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def boom(rows):
        raise RuntimeError("bench module failed")

    monkeypatch.setattr(bench_kernels, "run", boom)
    with pytest.raises(RuntimeError, match="bench module failed"):
        run.main()
