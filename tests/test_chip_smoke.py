"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The phase functions are the ones the chip run calls; here they run at a few
thousand rows, with the Pallas kernels in interpret mode, so a wrong path,
argument or control flow shows up before any chip time is spent.
"""
import json

import jax
import pytest

import chip_smoke


@pytest.fixture(scope="module")
def data():
    return chip_smoke.make_data(4096, 1024)


@pytest.fixture(scope="module")
def trained(data):
    xtr, ytr, xte, yte = data
    return chip_smoke.phase_train(xtr, ytr, xte, yte, leaf=64, rank=16,
                                  min_acc=0.85)


def test_phase_train_reports_and_trains(trained, capsys):
    assert [c for c, _, _ in trained["results"]] == list(chip_smoke.C_GRID)
    rep = trained["engine"].report
    assert rep.kernel_evals > 0 and rep.compression_s > 0


def test_phase_correct_against_dense(data):
    xtr, ytr, xte, yte = data
    out = chip_smoke.phase_correct(xtr, ytr, xte, yte, n=1024, leaf=64,
                                   rank=16)
    assert out["gap"] <= chip_smoke.DECISION_GAP_TOL


def test_phase_serve_matches_predict(trained, data):
    xte = data[2]
    out = chip_smoke.phase_serve(trained["results"][0][1], xte,
                                 n_requests=3, batch=64)
    assert out["qps"] > 0


def test_phase_pallas_interpret(data):
    out = chip_smoke.phase_pallas(data[0], n=1024, leaf=64, rank=16,
                                  impl="pallas_interpret")
    assert set(out) == {"gaussian", "laplacian"}


def test_check_failure_raises():
    with pytest.raises(chip_smoke.SmokeCheckFailed):
        chip_smoke.check(False, "boom")


@pytest.mark.parametrize("argv", [[], ["--four-chips"],
                                  ["--four-chips", "--rows", "4096"]])
def test_main_refuses_a_non_tpu_platform(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(argv)
    assert exc.value.code not in (0, None)
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defers_to_the_environment(monkeypatch, tmp_path,
                                                 restore_cache_dir):
    from repro.launch import cache

    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_falls_back_to_one_fixed_checkout_path(
        monkeypatch, restore_cache_dir):
    import pathlib

    from repro.launch import cache

    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    path = cache.use_compile_cache()
    assert path == cache.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    root = pathlib.Path(chip_smoke.__file__).resolve().parent
    assert pathlib.Path(path) == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
