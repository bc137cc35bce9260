"""The command's refusals and the result line's keys."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CMD = ["bench/run.py", "--workload", "logistic-rwmh.map.c4", "--seed",
       str(2**31 + 11), "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_refuses_off_a_tpu():
    p = subprocess.run([sys.executable, *CMD], cwd=ROOT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, *CMD], cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_result_keys_and_checks_last(tiny_logistic):
    res = tiny_logistic
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert list(res)[-1] == "checks"
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = "logistic-rwmh.map.c4"
    assert set(res["metrics"]) == {
        m["name"] for m in spec["end_to_end"]
        if cell in m.get("workloads", [cell])}
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert res["window"]["compilations"] == 0
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


def test_window_counts_the_drivers_host_work(tiny_logistic):
    """The window's counters on a run with the profiler off, and the host
    share a traced run would read from them."""
    from types import SimpleNamespace

    from bench import harness

    res = tiny_logistic
    driver = res["window"]["driver"]
    assert driver["chunks"] >= 1 and driver["reruns"] == 0
    assert driver["profiler_s"] == 0.0
    assert 0 < driver["longest_boundary_s"] <= res["window"]["seconds"]
    share = harness._module("metrics", "driver.host_share").read(
        SimpleNamespace(window=res["window"]))
    assert 0 < share < 100
    assert "breakdown" not in res


@pytest.fixture(scope="module")
def tiny_logistic():
    from bench import harness

    return harness.run("logistic-rwmh.map.c4", 5, 2.0, cfg_over=TINY,
                       traffic_over=TINY_TRAFFIC)


TINY = {"n": 2000, "map_steps": 300, "reference_draws": 1000}
TINY_TRAFFIC = {"warmup": 200, "chunk": 100}


def test_window_buffer_is_a_power_of_two_of_chunks():
    from bench import harness

    # 38.3 iterations/s per chain for 30 s, chunks of 40: 1.5 x 1,149 is
    # 43.1 chunks, plus the call's first, rounded up to 64 chunks.
    assert harness.window_buffer(38.3, 30, 40) == 64 * 40
    # a rate a little off gives the same buffer, so the fold is shared
    assert harness.window_buffer(36.0, 30, 40) == 64 * 40


def test_strata_draw_one_index_per_stretch():
    import numpy as np

    from bench import harness

    at = harness.strata(1000, 16, np.random.default_rng(3))
    assert len(at) == 16
    assert all(62 * i <= a < 63 * (i + 1) for i, a in enumerate(at))
    assert list(harness.strata(5, 16, np.random.default_rng(3))) == [
        0, 1, 2, 3, 4]


def test_unknown_bound_or_start_is_refused():
    from bench import harness

    with pytest.raises(ValueError, match="bound='untuned'"):
        harness._choice({"bound": "untuned"}, "bound", harness.BOUNDS)
    with pytest.raises(ValueError, match="start='default'"):
        harness._choice({"start": "default"}, "start", harness.STARTS)
