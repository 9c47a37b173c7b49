"""CPU rehearsal (``--tiny-cpu``) of the serving job kinds with the
program's phases on: a traced run lists the span and counter metrics
of PR 24 under ``rehearsal`` — the sections, parents and counts they
read are recorded on any backend — and none of the device-trace ones,
for which a CPU run has nothing to read."""

import pytest

from benchmark.tests.test_rehearsal import REPO, last_line, run

SPANS = {
    "gpt2m_chat": {"prefill_ms_p50", "first_token_hold_ms_p50",
                   "sched_step_ms_max", "admit_padding_share",
                   "admit_rows_per_dispatch"},
    "gpt2m_score_offline": {"offline_sched_step_ms_max",
                            "offline_admit_padding_share",
                            "offline_admit_rows_per_dispatch"},
}
TRACE = {"cache_copy_share", "offline_cache_copy_share", "sampler_share",
         "decode_attn_read_share", "host_bound_idle_share",
         "offline_host_bound_idle_share", "region_unattributed_share.chat",
         "region_unattributed_share.offline"}


@pytest.mark.parametrize("cell", sorted(SPANS))
def test_traced_rehearsal_lists_the_phase_metrics(cell):
    line = last_line(run(REPO, "--workload", cell, "--seed", "3",
                         "--seconds", "3", "--trace", "1", "--tiny-cpu"))
    assert line["correct"] is True and line["metrics"] == {}
    assert SPANS[cell] <= set(line["rehearsal"])
    assert not TRACE & set(line["rehearsal"])
