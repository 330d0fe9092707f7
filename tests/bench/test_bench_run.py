"""The entry point: the last line's keys, and no result without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import core

ROOT = str(core.ROOT)


def test_result_line_has_the_contract_keys_checks_last():
    run = core.Run(cell="c", workload={}, config={}, traffic={}, seed=1,
                   seconds=1.0, trace=False, chips=1)
    run.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                  "memory_peak_bytes": 1}
    run.checks = [("loss_gap", 1e-4, 1e-3), ("grad_norm_gap", 0.5, 0.1)]
    out = json.loads(core.result_line(
        run, {"setup_s": {"value": 3.0, "unit": "s"}}, None))
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is False
    assert out["checks"]["loss_gap"] == {"value": 1e-4, "limit": 1e-3}
    traced = json.loads(core.result_line(
        run, {}, {"device_ops": [["fusion.1", 0.5]], "idle_gaps": []}))
    assert list(traced)[-2:] == ["breakdown", "checks"]
    run.checks = [("loss_gap", 1e-4, 1e-3)]
    assert run.correct
    run.checks = [("loss_gap", float("nan"), 1e-3)]
    assert not run.correct
    run.checks = []
    assert not run.correct


def test_unknown_device_kind_is_an_error():
    assert core.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        core.load_peaks("TPU v9 imaginary")


def _run(cwd, cell="serve.phi4-mini.chat"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in core.load_manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), cell="train1.qwen3-32b.stage")
    assert p.returncode != 0 and p.stdout.strip() == ""
