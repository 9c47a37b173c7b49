"""The two metrics of PR 36 that read the scheduler's decode row counts
(``decode.row_steps_live`` / ``decode.row_steps_grid``): their files
name the reader that exists, read hand-made ticks, give nothing on a
program that counts nothing, and appear in a traced CPU rehearsal of
their cell."""

import pytest

from benchmark.harness import recipe
from benchmark.layer_metrics.readers import phases
from benchmark.tests.test_rehearsal import REPO, last_line, run

METRICS = {"gpt2m_chat": "decode_live_row_share",
           "gpt2m_score_offline": "offline_decode_live_row_share"}


@pytest.fixture
def ticks():
    """Three dispatches of chunks of 8 over 4 slots, the first before
    the window 0.5-2.5: two slots live the whole chunk, then one live
    for 8 steps and one for 3, then one for 5."""
    C = 2
    return {"spans": [
        (C, 0.2, "decode.row_steps_live", 16, None),
        (C, 0.2, "decode.row_steps_grid", 32, None),
        (C, 1.0, "decode.row_steps_live", 11, None),
        (C, 1.0, "decode.row_steps_grid", 32, None),
        (C, 2.0, "decode.row_steps_live", 5, None),
        (C, 2.0, "decode.row_steps_grid", 32, None),
        (C, 2.0, "decode.chunks_needed", 3, None),
    ], "window": {"start": 0.5, "end": 2.5, "seconds": 2.0}}


@pytest.mark.parametrize("metric", sorted(METRICS.values()))
def test_live_row_share_reads_the_counts_inside_the_window(ticks, metric):
    spec = recipe.load_json("layer_metrics", metric + ".json")
    read, params = recipe.reader_of(spec)
    assert read is phases.count_ratio
    assert spec["layer"] == "decode_kernels"
    assert read(ticks, **params) == pytest.approx(16 / 64)
    # a parent that counts no decode rows gives nothing, and no error
    old = {**ticks, "spans": [e for e in ticks["spans"]
                              if not e[2].startswith("decode.row_")]}
    assert read(old, **params) is None


@pytest.mark.parametrize("cell", sorted(METRICS))
def test_traced_rehearsal_lists_the_live_row_share(cell):
    line = last_line(run(REPO, "--workload", cell, "--seed", "3",
                         "--seconds", "3", "--trace", "1", "--tiny-cpu"))
    assert line["correct"] is True
    assert METRICS[cell] in line["rehearsal"]
