"""The two metrics of PR 32 that read the scheduler's sampler counts
(``sample.dispatches_sorted`` / ``sample.dispatches``): their files
name the reader that exists, read hand-made ticks, read 0.0 — not
nothing — where every dispatch was greedy, give nothing on a program
that counts nothing, and appear in a traced CPU rehearsal of their
cell."""

import pytest

from benchmark.harness import recipe
from benchmark.layer_metrics.readers import phases
from benchmark.tests.test_rehearsal import REPO, last_line, run

METRICS = {"gpt2m_chat": "sampler_sort_dispatch_share",
           "gpt2m_score_offline": "offline_sampler_sort_dispatch_share"}


@pytest.fixture
def ticks():
    """Five dispatches, the first before the window 0.5-2.5: a nucleus
    request is active at the first, the third and the fourth; a
    sampled, unfiltered one at the second."""
    C = 2
    rows = []
    for t, drawn, sorted_ in ((0.2, 1, 1), (1.0, 1, 0), (1.5, 1, 1),
                              (2.0, 1, 1), (2.4, 0, 0)):
        rows += [(C, t, "sample.dispatches", 1, None),
                 (C, t, "sample.dispatches_drawn", drawn, None),
                 (C, t, "sample.dispatches_sorted", sorted_, None)]
    rows.append((C, 2.0, "decode.chunks_grid", 12, None))
    return {"spans": rows,
            "window": {"start": 0.5, "end": 2.5, "seconds": 2.0}}


@pytest.mark.parametrize("metric", sorted(METRICS.values()))
def test_sort_share_reads_the_counts_inside_the_window(ticks, metric):
    spec = recipe.load_json("layer_metrics", metric + ".json")
    read, params = recipe.reader_of(spec)
    assert read is phases.count_ratio
    assert read(ticks, **params) == pytest.approx(2 / 4)
    # all greedy: the count is there with value 0, so the share is 0.0
    greedy = {**ticks, "spans": [
        e[:3] + (0,) + e[4:] if e[2] == "sample.dispatches_sorted" else e
        for e in ticks["spans"]]}
    assert read(greedy, **params) == 0.0
    # a parent that counts no sampler dispatches gives nothing, no error
    old = {**ticks, "spans": [e for e in ticks["spans"]
                              if not e[2].startswith("sample.")]}
    assert read(old, **params) is None


@pytest.mark.parametrize("cell", sorted(METRICS))
def test_traced_rehearsal_lists_the_sort_share(cell):
    line = last_line(run(REPO, "--workload", cell, "--seed", "3",
                         "--seconds", "3", "--trace", "1", "--tiny-cpu"))
    assert line["correct"] is True
    assert METRICS[cell] in line["rehearsal"]
