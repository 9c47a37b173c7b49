"""The idle readers (``readers/idle.py``) on a slice made by hand: the
recorder's rows put on the trace's axis by its clock rows, idle time
with no request left out, and the phases' shares of the rest exact."""

import pytest

from benchmark.harness import recipe
from benchmark.layer_metrics.readers import idle, regions

NEW = ("idle_with_work_share", "idle_with_work_ms_per_chunk",
       "idle_fetch_share", "idle_fetch_copy_share", "idle_admit_share",
       "idle_between_ticks_share", "offline_idle_admit_share",
       "offline_idle_between_ticks_share")


def read(metric, ev):
    spec = recipe.load_json("layer_metrics", metric + ".json")
    reader, params = recipe.reader_of(spec)
    return reader(ev, **params)


@pytest.fixture
def made():
    """One second of a chat slice on the trace's axis. The recorder's
    clock runs 10 s behind it: clock rows pair recorder 10.0 and 11.0
    with wall 1000.0 and 1001.0, and the capture started at wall 1000.0.
    Device 0 runs 0-0.10, 0.15-0.25, 0.60-0.70 and 0.95-1.0, so it is
    idle 0.10-0.15, 0.25-0.60 and 0.70-0.95 (0.65 s). Request ``a``
    was queued before the slice and retired at 0.30; ``b`` was queued
    at 0.65 and is still in the server at the end: work is 0-0.30 and
    0.65-1.0, and the idle inside it 0.10-0.15, 0.25-0.30 and 0.70-0.95
    (0.35 s); 0.30-0.60 had no request. Three ticks: 0-0.16 (a fetch
    0.08-0.16, its copies from 0.12), 0.24-0.32 (an admission
    0.24-0.27) and 0.66-0.80 (a fetch 0.75-0.80, copies from 0.78);
    0.80-0.95 lies between ticks."""
    ops = [(f"fusion.{i}", a, b, "jit_step_local fusion", "apex.mlp")
           for i, (a, b) in enumerate(
               [(0.0, 0.10), (0.15, 0.25), (0.60, 0.70), (0.95, 1.0)])]
    host = [("apex.sched.step", 0.0, 0.16),
            ("apex.engine.fetch", 0.08, 0.16),
            ("apex.engine.fetch.wait", 0.08, 0.12),
            ("apex.engine.fetch.copy", 0.12, 0.16),
            ("apex.sched.step", 0.24, 0.32),
            ("apex.sched.admit", 0.24, 0.27),
            ("apex.sched.step", 0.66, 0.80),
            ("apex.engine.fetch", 0.75, 0.80),
            ("apex.engine.fetch.wait", 0.75, 0.78),
            ("apex.engine.fetch.copy", 0.78, 0.80),
            ("bench.sched_step", 0.0, 0.16)]
    spans = [(3, 9.0, "clock", 999.0, None),
             (0, 9.5, "a", "queued", None),
             (3, 10.0, "clock", 1000.0, None),
             (1, 10.000003, "sched.step", 10.16, None),
             (1, 10.240004, "sched.step", 10.32, None),
             (0, 10.3, "a", "retired", "length"),
             (0, 10.65, "b", "queued", None),
             (1, 10.659998, "sched.step", 10.80, None),
             (3, 11.0, "clock", 1001.0, None)]
    return {"scoped_trace": {"ops": ops, "in_flight": [], "host": host},
            "spans": spans, "profile_start_s": 1000.0}


def test_idle_with_no_request_is_left_out(made):
    idle_all = regions.idle_intervals(made["scoped_trace"])
    lo, hi, with_work = idle.idle_with_work(made)
    assert (lo, hi) == (0.0, 1.0)
    total = sum(b - a for a, b in idle_all)
    inside = sum(b - a for a, b in with_work)
    assert (total, inside) == (pytest.approx(0.65), pytest.approx(0.35))
    assert read("idle_with_work_share", made) == pytest.approx(35.0)
    # against what counts every idle second not under a fetch
    assert regions.uncovered_idle_share(
        made, ["apex.engine.fetch"]) == pytest.approx(55.0)


def test_phases_share_the_idle_with_work(made):
    fetch = read("idle_fetch_share", made)
    copy = read("idle_fetch_copy_share", made)
    admit = read("idle_admit_share", made)
    between = read("idle_between_ticks_share", made)
    assert fetch == pytest.approx(100 * 0.10 / 0.35)
    assert copy == pytest.approx(100 * 0.05 / 0.35)
    assert admit == pytest.approx(100 * 0.02 / 0.35)
    assert between == pytest.approx(100 * 0.15 / 0.35)
    # the closed loop reads the same names
    assert read("offline_idle_admit_share", made) == admit
    assert read("offline_idle_between_ticks_share", made) == between
    # what is left is the tick's own host time outside fetch and admit
    assert 100 - fetch - admit - between == pytest.approx(100 * 0.08 / 0.35)
    # two fetches end in the slice: 350 ms of idle with work over them
    assert read("idle_with_work_ms_per_chunk", made) == pytest.approx(175.0)


def test_clock_rows_map_the_recorder_onto_the_trace():
    ev = {"spans": [(3, 10.0, "clock", 1000.0, None),
                    (3, 11.0, "clock", 1001.002, None)],
          "profile_start_s": 1000.0}
    at = idle.to_trace(ev)
    assert at(10.5) == pytest.approx(0.501)     # between: interpolated
    assert at(12.0) == pytest.approx(2.002)     # after: the last offset
    assert at(9.0) == pytest.approx(-1.0)       # before: the first
    assert idle.to_trace({"spans": ev["spans"][:1],
                          "profile_start_s": 999.0})(10.25) == \
        pytest.approx(1.25)


def test_step_rows_land_on_their_annotations(made):
    got = idle.step_clock_residuals_us(made)
    assert got == pytest.approx([3.0, 4.0, -2.0], abs=1e-3)


def test_nothing_to_read_without_clock_rows(made):
    made["spans"] = [e for e in made["spans"] if e[0] != 3]
    assert all(read(m, made) is None for m in NEW)
    assert idle.step_clock_residuals_us(made) is None


def test_nothing_to_read_after_dropped_rows(made):
    # the ring dropped a's queued mark: a's work cannot be placed
    made["spans"] = [e for e in made["spans"] if e[1] >= 9.8]
    assert all(read(m, made) is None for m in NEW)


def test_nothing_to_read_when_the_oldest_row_is_inside_the_slice(made):
    made["spans"] = [e for e in made["spans"] if e[1] >= 10.6]
    assert all(read(m, made) is None for m in NEW)


def test_a_request_open_at_both_edges_is_work_throughout(made):
    made["spans"] = [e for e in made["spans"]
                     if not (e[0] == 0 and e[2] == "a")] + [
        (0, 9.7, "c", "queued", None)]
    assert read("idle_with_work_share", made) == pytest.approx(65.0)
