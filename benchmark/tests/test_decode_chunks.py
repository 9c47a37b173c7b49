"""The two metrics of PR 28 that read the scheduler's decode-read
counts (``decode.chunks_needed`` / ``decode.chunks_grid``): their files
name the reader that exists, read hand-made ticks, give nothing on a
program that counts nothing, and appear in a traced CPU rehearsal of
their cell."""

import pytest

from benchmark.harness import recipe
from benchmark.layer_metrics.readers import phases
from benchmark.tests.test_rehearsal import REPO, last_line, run

METRICS = {"gpt2m_chat": "decode_chunk_fetch_share",
           "gpt2m_score_offline": "offline_decode_chunk_fetch_share"}


@pytest.fixture
def ticks():
    """Three dispatches, the first before the window 0.5-2.5: two live
    slots needing 1 and 2 chunks of a 2 x 4 grid, then three needing
    2, 3 and 4 of a 3 x 4 grid."""
    C = 2
    return {"spans": [
        (C, 0.2, "decode.chunks_needed", 8, None),
        (C, 0.2, "decode.chunks_grid", 8, None),
        (C, 1.0, "decode.chunks_needed", 3, None),
        (C, 1.0, "decode.chunks_grid", 8, None),
        (C, 2.0, "decode.chunks_needed", 9, None),
        (C, 2.0, "decode.chunks_grid", 12, None),
        (C, 2.0, "prefill.rows", 1, None),
    ], "window": {"start": 0.5, "end": 2.5, "seconds": 2.0}}


@pytest.mark.parametrize("metric", sorted(METRICS.values()))
def test_fetch_share_reads_the_counts_inside_the_window(ticks, metric):
    spec = recipe.load_json("layer_metrics", metric + ".json")
    read, params = recipe.reader_of(spec)
    assert read is phases.count_ratio
    assert read(ticks, **params) == pytest.approx(12 / 20)
    # a parent that counts no decode chunks gives nothing, and no error
    old = {**ticks, "spans": [e for e in ticks["spans"]
                              if not e[2].startswith("decode.")]}
    assert read(old, **params) is None


@pytest.mark.parametrize("cell", sorted(METRICS))
def test_traced_rehearsal_lists_the_fetch_share(cell):
    line = last_line(run(REPO, "--workload", cell, "--seed", "3",
                         "--seconds", "3", "--trace", "1", "--tiny-cpu"))
    assert line["correct"] is True
    assert METRICS[cell] in line["rehearsal"]
