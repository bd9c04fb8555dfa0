"""The command's contract: no chip, no result; the result line's shape;
and a directory holding only the benchmark's files cannot run."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import common

ROOT = common.ROOT


def _run(cwd, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "train-300m-b512-4x1", "--seed", str(2 ** 31 + 3), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=timeout)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            return True
    return False


def test_without_a_chip_it_exits_nonzero_and_prints_no_result(tmp_path):
    p = _run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_cannot_run(tmp_path):
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in man["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_result_line_shape():
    line = common.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 123},
        breakdown={"device_ops": [["fusion", 0.5]],
                   "idle_gaps": [["chipbench.loader_next", 0.01]]},
        checks={"loss_gap": {"value": 1e-5, "limit": 1e-4}})
    obj = json.loads(line)
    assert list(obj)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(obj)
    assert obj["metrics"]["setup_s"] == {"value": 1.5, "unit": "s"}
    assert set(obj["breakdown"]) == {"device_ops", "idle_gaps"}


def test_checks_pass_and_print(capsys):
    ok = {"a": common.check_entry(0.1, 0.2)}
    bad = {"a": common.check_entry(0.3, 0.2)}
    nan = {"a": common.check_entry(float("nan"), 0.2)}
    assert common.checks_pass(ok)
    assert not common.checks_pass(bad) and not common.checks_pass(nan)
    common.print_checks(bad)
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check a = 0.3 limit 0.2")

