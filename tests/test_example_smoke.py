"""End-to-end example smoke: the GPT trainer script with the native data
loader, .atck checkpointing, and metrics logging on a tp=2 x dp=4 mesh —
the reference's L1 'main_amp.py actually runs' leg (SURVEY.md §4), in
subprocess form so the script's own entry path is what's tested."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest


def test_gpt_train_example_end_to_end(tmp_path):
    data = str(tmp_path / "toks.bin")
    rng = np.random.default_rng(0)
    from apex_tpu import data as atdata
    atdata.write_token_file(data, rng.integers(0, 1024, 200_000,
                                               dtype=np.int64).astype(np.int32),
                            seq_len=128)
    ckpt = str(tmp_path / "ck")
    metrics = str(tmp_path / "m.jsonl")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.join(repo, "examples", "gpt_train.py"),
           "--preset", "tiny", "--tp", "2", "--steps", "2",
           "--clip-grad-norm", "1.0",
           "--data", data, "--ckpt", ckpt, "--metrics", metrics]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "saved" in r.stdout
    lines = [json.loads(l) for l in open(metrics)]
    assert len(lines) == 2 and np.isfinite(lines[-1]["loss"])
    assert lines[-1]["grad_norm"] > 0  # clip flag flows through the step

    # resume leg: picks up the saved step counter
    cmd2 = list(cmd)
    cmd2[cmd2.index("--steps") + 1] = "1"
    r2 = subprocess.run(cmd2, env=env, capture_output=True, text=True,
                        timeout=900)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed" in r2.stdout and "at step 2" in r2.stdout


def test_retinanet_example_smoke(tmp_path):
    """BASELINE config #3: SyncBN + FusedSGD + focal loss detection slice
    runs end-to-end on the simulated mesh with a decreasing loss."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable,
           os.path.join(repo, "examples", "retinanet_detect.py"),
           "--steps", "2", "--batch", "1", "--image", "32",
           "--classes", "4", "--depth", "26"]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    losses = [float(l.split("loss ")[1].split(" ")[0])
              for l in r.stdout.splitlines() if l.startswith("step ")]
    assert len(losses) == 2 and losses[1] < losses[0]


def test_imagenet_example_smoke(tmp_path):
    """BASELINE config #1: ResNet + bf16-policy + DP grad pmean +
    FusedSGD runs end-to-end on the simulated mesh."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.join(repo, "examples", "imagenet_amp.py"),
           "--steps", "2", "--batch", "8", "--image", "32", "--depth", "26"]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    losses = [float(l.rsplit(" ", 1)[1])
              for l in r.stdout.splitlines() if l.startswith("step ")]
    assert len(losses) == 2 and losses[1] < losses[0]


@pytest.mark.slow
def test_imagenet_example_native_loader(tmp_path):
    """Config #1 with the native ImageLoader path: packed uint8 records →
    prefetch thread → on-device normalization (different batches per step,
    so only completion is asserted).

    Marked ``slow`` by the tier-1 marker audit (conftest): ~58 s solo
    on the CPU mesh, over the ~60 s per-test budget under full-suite
    load. The cheaper ``test_imagenet_example_smoke`` keeps the
    e2e path in tier-1; this native-loader variant runs in the soak
    tier."""
    from apex_tpu import data as atdata

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + env.get("PYTHONPATH", ""))
    rng = np.random.default_rng(3)
    img_file = str(tmp_path / "train.bin")
    atdata.write_image_file(
        img_file, rng.integers(0, 256, (24, 32, 32, 3), dtype=np.uint8),
        rng.integers(0, 1000, 24))
    ck = str(tmp_path / "rn.atck")
    cmd = [sys.executable, os.path.join(repo, "examples", "imagenet_amp.py"),
           "--steps", "2", "--batch", "8", "--image", "32", "--depth", "26",
           "--data", img_file, "--val-data", img_file, "--val-batches", "2",
           "--ckpt", ck]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "images/s" in r.stdout
    assert "prec@1" in r.stdout and "over 16 images" in r.stdout
    assert "saved" in r.stdout

    r2 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                        timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed" in r2.stdout and "at step 2" in r2.stdout
    assert "step 3 loss" in r2.stdout  # counter continues past the resume


def test_simple_distributed_example_smoke(tmp_path):
    """The reference's examples/simple/distributed demo (U): amp O2
    fp16 + dynamic scaler + DDP grad reduce, smallest-possible loop;
    loss must fall and the dynamic scale must be reported."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable,
           os.path.join(repo, "examples", "simple_distributed.py"),
           "--steps", "3", "--batch", "16", "--dim", "64", "--fp16"]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    steps = [l for l in r.stdout.splitlines() if l.startswith("step ")]
    losses = [float(l.split("loss ")[1].split(" ")[0]) for l in steps]
    assert len(losses) == 3 and losses[-1] < losses[0]
    assert all("scale 65536" in l for l in steps)  # fp16 dynamic scaler on


def test_gpt_train_moe_example_smoke(tmp_path):
    """--experts/--ep flag plumbing: MoE-GPT over ep=2 x tp=2 trains with
    a falling loss through the flagship example."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.join(repo, "examples", "gpt_train.py"),
           "--preset", "tiny", "--experts", "4", "--ep", "2", "--tp", "2",
           "--steps", "2", "--batch", "8"]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    losses = [float(l.rsplit(" ", 1)[1])
              for l in r.stdout.splitlines() if l.startswith("step ")]
    assert len(losses) == 2 and losses[1] < losses[0]


def test_serve_gpt_example_smoke(tmp_path):
    """Offline batch serving: a JSONL request file (greedy, sampled, and
    an eos-terminal prompt) flows through the continuous-batching engine
    over tp=2; one line per request plus a summary JSON line."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + env.get("PYTHONPATH", ""))
    reqfile = str(tmp_path / "requests.jsonl")
    with open(reqfile, "w") as f:
        for d in ({"id": "greedy", "prompt": [3, 1, 4, 1, 5],
                   "max_tokens": 4},
                  {"id": "sampled", "prompt": [2, 7, 1, 8],
                   "max_tokens": 5, "temperature": 0.9, "top_k": 11,
                   "seed": 9},
                  {"id": "instant", "prompt": [6, 2, 9],
                   "max_tokens": 6, "eos_token_id": 9}):
            f.write(json.dumps(d) + "\n")
    cmd = [sys.executable, os.path.join(repo, "examples", "serve_gpt.py"),
           "--tp", "2", "--slots", "2", "--requests", reqfile]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = {l.split()[1]: l for l in r.stdout.splitlines()
             if l.startswith("request ")}
    assert set(lines) == {"greedy", "sampled", "instant"}
    assert "[length]" in lines["greedy"]
    # the eos-terminal prompt completes at submit with zero tokens
    assert "[eos]" in lines["instant"] and "-> []" in lines["instant"]
    served = [l for l in r.stdout.splitlines() if l.startswith("served ")]
    summary = json.loads(served[0][len("served "):])
    assert summary["requests_completed"] == 3
    assert summary["tokens_emitted"] == 9  # 4 + 5 + 0


def test_generate_example_smoke(tmp_path):
    """Decode demo runs greedy over tp=2 and prints a continuation per
    batch row."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.join(repo, "examples", "generate.py"),
           "--tp", "2", "--n-new", "4", "--batch", "2"]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("prompt ")]
    assert len(lines) == 2 and all("->" in l for l in lines)


def _run_chip_smoke(*args):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("APEX_TPU_FORCE_INTERPRET", None)
    return subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=900)


def test_chip_smoke_refuses_without_tpu():
    """The device gate: with no TPU visible the chip smoke names the
    device, exits non-zero before any phase, and prints no result."""
    r = _run_chip_smoke()
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout
    assert "train[" not in r.stdout and '"ok"' not in r.stdout


@pytest.mark.slow
def test_chip_smoke_tiny_cpu_run():
    """The sandbox argument runs every phase of the chip smoke at a
    tiny size on the CPU (kernels interpreted) and ends in the result
    line."""
    r = _run_chip_smoke("--tiny-cpu")
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["ok"] is True and result["device"]["platform"] == "cpu"
    for phase in ("train[1 chip]: PASS", "serve: PASS",
                  "decode-kernel sweep: PASS",
                  "standalone kernel sweep: PASS"):
        assert phase in r.stdout
