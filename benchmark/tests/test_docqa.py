"""The ``serve_docqa`` kind and DeepSeek-V3.2's work counts: arithmetic
of ``harness/docqa_work.py`` against the issue's own table, the
configuration file against the catalog row, and a CPU rehearsal of the
cell (``--tiny-cpu``) end to end, traced and untraced."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.families import deepseek_v32 as fam
from benchmark.harness import docqa_work as work
from benchmark.harness import recipe

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def shape():
    return fam.shape(recipe.load_json("configs", "deepseek-v3.2-ep16.json"))


def test_parameter_counts_are_the_published_ones():
    s = shape()
    # MLA 187.1 M and the indexer 14.0 M a layer (ISSUE 31's table)
    assert work.attn_matrix_params(s) == (
        7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256
        + 16384 * 7168 + 1536 * 64 * 128 + 7168 * 128 + 7168 * 64)
    assert round(work.attn_matrix_params(s) / 1e6, 1) == 201.1
    assert work.expert_params(s) == 3 * 7168 * 2048 == 44_040_192
    assert work.dense_ffn_params(s) == 3 * 7168 * 18432
    assert work.held_share(s) == 1 / 16 and work.moe_layers(s) == 4


def test_flops_and_bytes_of_a_token():
    s = shape()
    per_moe = 7168 * 256 + 44_040_192 * (1 + 8 / 16)
    n = 5 * work.attn_matrix_params(s) + 3 * 7168 * 18432 + 4 * per_moe
    assert work.matrix_flops_per_token(s) == 2.0 * n
    assert work.head_flops(s) == 2.0 * 16160 * 7168
    assert work.index_flops_per_pair(s) == 2 * 64 * 128 + 2 * 64
    assert work.attend_flops_per_key(s) == 2 * 128 * (512 + 64 + 512)
    assert work.index_key_bytes(s) == 256
    assert work.latent_row_bytes(s) == 1152
    assert work.expert_bytes(s) == 88_080_384
    # what a decode step reads whatever it routes: 4.635 G parameters
    # less the 16 routed experts of each of four layers and the
    # embedding (a look-up), in bfloat16
    total = (5 * work.attn_matrix_params(s) + 3 * 7168 * 18432
             + 4 * (7168 * 256 + 44_040_192) + 16160 * 7168)
    assert work.non_expert_weight_bytes(s) == 2.0 * total
    assert round(2 * (total + 64 * 44_040_192 + 16160 * 7168) / 1e9, 2) \
        == 9.27            # the 9.27 GB the AOT plan holds as weights


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_configuration_file_is_the_catalog_row_with_its_cuts():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3.2")
    file = recipe.load_json("configs", "deepseek-v3.2-ep16.json")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "deepseek-v3.2-ep16")
    assert entry["source"] == row["source_url"] == file["source"]
    differs = {k for k, v in row["config"].items() if file.get(k) != v}
    assert differs == set(entry["reduced"]) == set(file["reduced"])
    assert file["published"] == {k: row["config"][k] for k in differs}
    widths = [k for k in differs if k.endswith(("_dim", "_rank", "_size"))
              and k != "vocab_size"]
    assert not widths


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "dsv32_docqa_shared", "--seed", "2147483777", "--seconds", "2",
         "--trace", str(trace), "--tiny-cpu"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 8
    want = ({"serve_tokens_per_s", "setup_s"} if not trace else {
        "dsa_attended_share", "moe_experts_hit_share",
        "moe_held_load_ratio", "prefix_shared_token_share",
        "docqa_admit_padding_share", "docqa_batch_occupancy",
        "hbm_plan_gib", "compiles_in_window", "compile_s"})
    assert want <= set(line["rehearsal"]), line["rehearsal"]
    assert "documents_s" in out.stderr
    # the window opens only when the first wave has ended, and two of
    # the four compared requests were admitted after that
    assert "the first wave of 4 has ended" in out.stderr
    assert "reference: 4 requests" in out.stderr


def test_the_comparison_rejects_every_control():
    """The job's own ``judge`` on the streams of a rehearsal: the
    float32 reference passes; a lower precision (bfloat16 and float8
    under a float32 rehearsal) and the three wrong models come out
    ``correct: false`` (``tools/docqa_limits.py`` exits 1 otherwise)."""
    out = subprocess.run(
        [sys.executable, "benchmark/tools/docqa_limits.py", "--workload",
         "dsv32_docqa_shared", "--seed", "2147483778", "--seconds", "2",
         "--tiny-cpu", "--wrong-requests", "4"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr[-3000:]
    verdict = {line.split(":")[0]: line.rsplit("correct: ", 1)[1].split()[0]
               for line in out.stdout.splitlines() if "correct: " in line}
    assert verdict.pop("float32 reference") == "true"
    assert len(verdict) == 5 and set(verdict.values()) == {"false"}, verdict


def _evidence():
    """A synthetic traced run: 2 s of trace, one decode step program
    busy 0.5 s of it, three regions; a 10 s window of counts."""
    path = lambda r: f"jit(step_local)/apex.attn/{r}/dot_general:"
    ops = [("fusion", 0.0, 0.25, "jit_step_local fusion", path("apex.dsa.index")),
           ("gather", 0.25, 0.45, "jit_step_local gather",
            path("apex.mla.sparse_attn")),
           ("gmm", 0.45, 0.5, "jit_step_local gmm", path("apex.moe.experts")),
           ("fusion", 1.0, 2.0, "jit_admit_local fusion",
            path("apex.mla.sparse_attn"))]
    count = lambda name, n: (2, 5.0, name, n, None)
    section = lambda name, a, b: (1, a, name, b, None)
    spans = [count("dsa.keys_scored", 1e9), count("dsa.keys_attended", 1e8),
             count("moe.pairs_routed", 16000), count("moe.pairs_held", 900),
             count("moe.experts_hit", 600), count("moe.experts_offered", 640),
             count("prefix.tokens_shared", 9000),
             count("prefix.tokens_prefilled", 1000),
             count("prefill.rows", 10)] + [
        section("engine.dispatch", 1.0 + i, 1.1 + i) for i in range(5)]
    return {"shape": shape(), "window": {"start": 0.0, "end": 10.0,
                                         "seconds": 10.0},
            "spans": spans, "decode_reads": [(5.0, 2.4e6)],
            "decode_tokens_in_window": 100, "decode_chunk": 4,
            "tokens_in_window": 110,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "scoped_trace": {"ops": ops, "in_flight": [], "host": []}}


def test_the_readers_against_a_hand_count():
    from benchmark.layer_metrics.readers import docqa

    ev, s = _evidence(), shape()
    assert docqa.count_share(ev, "moe.experts_hit",
                             ["moe.experts_offered"]) == 100 * 600 / 640
    assert docqa.count_share(ev, "moe.pairs_held", ["moe.pairs_routed"],
                             16.0) == 16 * 900 / 16000
    assert docqa.count_share(ev, "prefix.tokens_shared", [
        "prefix.tokens_shared", "prefix.tokens_prefilled"]) == 90.0
    assert docqa.count_share(ev, "nothing.counted", ["moe.pairs_held"]) \
        is None
    need = (1100 * work.matrix_flops_per_token(s) + 110 * work.head_flops(s)
            + 1e9 * work.index_flops_per_pair(s)
            + 1e8 * work.attend_flops_per_key(s))
    assert docqa.serve_mfu(ev) == pytest.approx(100 * need / (10 * 197e12))
    # the attended region ran 1.2 of the 2 traced seconds
    per_s = 1e8 / 10
    least = max(per_s * work.attend_flops_per_key(s) / 197e12,
                per_s * work.latent_row_bytes(s) / 819e9)
    assert docqa.sparse_attn_roofline(
        ev, ["apex.mla.sparse_attn"]) == pytest.approx(100 * least / 0.6)
    held, hit = 900 / 10, 600 / 10
    least = max(held * 2 * work.expert_params(s) / 197e12,
                hit * work.expert_bytes(s) / 819e9)
    assert docqa.experts_roofline(
        ev, ["apex.moe.experts"]) == pytest.approx(100 * least / 0.025)
    # 5 dispatches x 4 steps; the step program busy 0.25 of a second
    steps = 20
    need = (steps * (work.non_expert_weight_bytes(s) + 600 / 640 * 4 * 16
                     * work.expert_bytes(s))
            + 5 * (2.4e6 * 256 + 100 * 2048 * 1152))
    assert docqa.decode_hbm_roofline(
        ev, "jit_step_local", "engine.dispatch") == pytest.approx(
        100 * need / 10 / (819e9 * 0.25))
    bare = dict(ev, scoped_trace={"ops": [], "in_flight": [], "host": []})
    assert docqa.index_roofline(bare, ["apex.dsa.index"]) is None
