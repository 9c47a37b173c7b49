"""CPU rehearsal of ``run.py`` for every job kind at a tiny size, with
the Pallas kernels interpreted (``--tiny-cpu``, the sandbox-only
argument). A rehearsal shows control flow, file look-up and the shape of
the result line; it must never print a number under a metric's name.
Also: without the argument a missing TPU is a failure, a checkout that
holds only the benchmark is a failure, and a new cell, configuration,
traffic mix and per-layer metric need new files and new entries only.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(root, *argv, devices=1, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("APEX_TPU_FORCE_INTERPRET", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *argv],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,devices", [
    ("gpt2m_train", 1), ("gpt2l_train_tp2dp2", 4),
    ("gpt2m_chat", 1), ("gpt2m_score_offline", 1)])
def test_rehearsal_of_every_job_kind(cell, devices, trace):
    line = last_line(run(REPO, "--workload", cell, "--seed", "3",
                         "--seconds", "3", "--trace", str(trace),
                         "--tiny-cpu", devices=devices))
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # a CPU run is never written under the name of a device metric
    assert line["metrics"] == {} and "breakdown" not in line
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert "busy_s" not in line["device"]
    want = "train_step_ms_p50" if "train" in cell else (
        "engine_step_ms_p50" if cell == "gpt2m_chat"
        else "offline_engine_step_ms_p50")
    assert (want in line["rehearsal"]) == bool(trace)


def test_no_tpu_is_a_failure_not_a_fallback():
    proc = run(REPO, "--workload", "gpt2m_train", "--seed", "0",
               "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_too_few_chips_is_a_failure():
    proc = run(REPO, "--workload", "gpt2l_train_tp2dp2", "--seed", "0",
               "--seconds", "1", "--trace", "0", "--tiny-cpu", devices=2)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


@pytest.fixture
def copy(tmp_path):
    """BENCHMARK.json and benchmark/ alone, as the driver's bare
    directory has them."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_benchmark_alone_is_a_failure(copy):
    proc = run(str(copy), "--workload", "gpt2m_train", "--seed", "0",
               "--seconds", "1", "--trace", "0", "--tiny-cpu")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_new_cell_config_mix_and_metric_are_files_and_entries(copy):
    """One of each, added to a copy without editing a file that was
    there (BENCHMARK.json gains entries, as any later PR's does)."""
    os.symlink(os.path.join(REPO, "apex_tpu"), copy / "apex_tpu")
    bdir = copy / "benchmark"
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}

    cfg = json.loads((bdir / "configs" / "gpt2-medium.json").read_text())
    cfg.update(name="gpt2", n_embd=768, n_layer=12, n_head=12,
               source="https://huggingface.co/openai-community/gpt2/"
               "blob/main/config.json")
    (bdir / "configs" / "gpt2.json").write_text(json.dumps(cfg))
    mix = json.loads((bdir / "traffic" / "chat_steady.json").read_text())
    mix["arrivals"] = {"process": "gamma", "rate_per_s": 4.0, "cv": 3.0}
    (bdir / "traffic" / "chat_bursty.json").write_text(json.dumps(mix))
    shutil.copy(bdir / "cells" / "gpt2m_chat.json",
                bdir / "cells" / "gpt2s_chat_bursty.json")
    (bdir / "layer_metrics" / "ttft_p99_ms.json").write_text(json.dumps({
        "layer": "service", "unit": "ms", "better": "lower",
        "source": "host_clock", "moves": "ttft_p90_ms",
        "job_kinds": ["serve_open"], "reader": "host:percentile_ms",
        "params": {"key": "ttft_s", "q": 99}}))

    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "gpt2", "source": cfg["source"],
        "file": "benchmark/configs/gpt2.json", "reduced": cfg["reduced"],
        "why": "test"})
    bench["workloads"].append({
        "name": "gpt2s_chat_bursty", "config": "gpt2",
        "traffic": "chat_bursty", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "ttft_p99_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "service",
        "moves": "ttft_p90_ms"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    line = last_line(run(str(copy), "--workload", "gpt2s_chat_bursty",
                         "--seed", "5", "--seconds", "3", "--trace", "1",
                         "--tiny-cpu"))
    assert line["correct"] is True and line["attempted"] > 0
    # the new metric is read, and the new cell inherits its kind's
    assert {"ttft_p99_ms", "engine_step_ms_p50"} <= set(line["rehearsal"])
    assert all(p.read_bytes() == data for p, data in before.items())
