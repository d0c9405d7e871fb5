"""The harness: refusal off the chip, the peak table, discovery by name,
the result line, and the roofline's count of the scorer's work."""
import json
import shutil

import pytest

from bench import roofline
from bench import run as bench_run


def test_run_refuses_a_platform_that_is_not_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--workload", "susy.train", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert "TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_require_chips_counts_the_cell_chips():
    class Dev:
        platform = "tpu"
    bench_run.require_chips([Dev()], 1)
    with pytest.raises(SystemExit):
        bench_run.require_chips([Dev()], 4)


def test_unknown_device_kind_is_refused():
    assert bench_run.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        bench_run.load_peaks("TPU v99 imaginary")


def _copy_benchmark(tmp_path):
    shutil.copy(bench_run.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def test_new_config_cell_mix_and_metric_are_found_by_name(tmp_path):
    root = _copy_benchmark(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "susy.json").read_text())
    cfg.update(name="susy_small", rows=4096)
    (b / "configs" / "susy_small.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "train.json").read_text())
    mix["holdout_rows"] = 1024
    (b / "traffic" / "train_small.json").write_text(json.dumps(mix))
    (b / "metrics" / "models.small.py").write_text(
        "def read(rec):\n    return float(len(rec['models']))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "susy_small.train",
                               "config": "susy_small",
                               "traffic": "train_small", "chips": 1,
                               "why": "added by a data file"})
    bench["per_layer"].append({"name": "models.small", "unit": "models",
                               "better": "higher",
                               "source": "program_counter", "layer": "x",
                               "moves": "train_s",
                               "workloads": ["susy_small.train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = bench_run.find_cell("susy_small.train", root=root)
    assert cell.config["rows"] == 4096
    assert cell.traffic["holdout_rows"] == 1024
    assert cell.driver().__name__ == "bench_train_models"
    assert cell.data().N_FEATURES == 18
    assert [m["name"] for m in cell.per_layer] == ["models.small"]
    assert cell.metric_reader("models.small").read({"models": [1, 2]}) == 2.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]


def test_every_named_piece_exists():
    bench = json.loads((bench_run.REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = bench_run.find_cell(w["name"])
        assert cell.driver() and cell.data()
        assert cell.limits, w["name"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.metric_reader(m["name"]).read)
    for c in bench["configs"]:
        assert (bench_run.REPO / c["file"]).is_file()


def test_score_work_matches_a_hand_count():
    # 3 rows in 2 launches against 5 support rows of 2 features, 1 column:
    # per kernel entry 2*2 (cross) + 4 (distance, exp) + 2*1 (coefficient)
    flops, nbytes = roofline.score_work(3, 2, 5, 2, 1)
    assert flops == 3 * 5 * 10
    # support rows and coefficients once per launch; queries in, scores out
    assert nbytes == 4 * (2 * 5 * 3 + 3 * 3)
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 1000.0}
    assert roofline.least_time(flops, nbytes, peaks) == (1.5, "compute")
    assert roofline.least_time(1.0, nbytes, peaks)[1] == "memory"


def test_result_line_has_the_contract_keys(tiny_cell, measure_cpu):
    res = measure_cpu(tiny_cell("susy.train"))
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_s", "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    json.loads(json.dumps(res, allow_nan=False))
