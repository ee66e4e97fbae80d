"""A run without a TPU fails and prints no result line; so does a run in a
directory that holds only the benchmark."""
import importlib.util
import os
import shutil
import sys

import pytest

from perfbench import cells


def _load_run(path):
    spec = importlib.util.spec_from_file_location("perfbench_run_copy", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def saved_env():
    env, path = dict(os.environ), list(sys.path)
    yield
    os.environ.clear()
    os.environ.update(env)
    sys.path[:] = path


def test_no_tpu_fails_without_a_line(saved_env, capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    run = _load_run(cells.HERE / "run.py")
    rc = run.main(["--workload", "oproj4096.prefill", "--seed", "1",
                   "--seconds", "1", "--trace", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_benchmark_alone_fails_without_a_line(saved_env, tmp_path, capsys):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(cells.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    run = _load_run(tmp_path / "perfbench" / "run.py")
    rc = run.main(["--workload", "oproj4096.prefill", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
