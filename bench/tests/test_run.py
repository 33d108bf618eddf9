"""The command refuses to run without a TPU and prints no result."""
import importlib.util
import os
import shutil

from conftest import ROOT


def _load_run(root):
    spec = importlib.util.spec_from_file_location(
        "_bench_run_cmd", os.path.join(root, "bench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_tpu_exits_nonzero_without_result(capsys):
    run = _load_run(ROOT)
    rc = run.main(["--workload", "sf1.scan_agg", "--seed", "12",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "no TPU" in err


def test_unknown_workload_exits_nonzero(capsys):
    run = _load_run(ROOT)
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path, capsys):
    """A directory with only BENCHMARK.json and bench/: no result."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    run = _load_run(str(tmp_path))
    rc = run.main(["--workload", "sf1.scan_agg", "--seed", "3",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
