"""The readers of what the program names itself: regions and kernel
names in the device trace (``readers/regions.py``), the tick's phases
and the admission counts in its spans (``readers/phases.py``) — exact
on hand-made evidence, sane on the slice recorded on the chip
(``harness/testdata/trace_scoped_small.json``: the end of one tick of
``gpt2m_chat`` and the start of the next, PR 24)."""

import json
import os

import pytest

from benchmark.harness import recipe
from benchmark.layer_metrics.readers import phases, regions

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "harness", "testdata",
                        "trace_scoped_small.json")

FWD = "jit(_local_step)/jvp(apex.layers)/while/body/closed_call/"
BWD = "jit(_local_step)/transpose(jvp(apex.layers))/while/body/"


def params_of(metric):
    return recipe.load_json("layer_metrics", metric + ".json")["params"]


@pytest.fixture
def made():
    """One second of a train step on device 0. A forward loop 0.0-0.4
    (``apex.layers``) around an attention matmul 0.0-0.2 and an MLP
    matmul 0.2-0.35 — so the loop's own time is 0.05; a backward loop
    0.4-0.8 around a recomputed attention matmul 0.4-0.5, a flash
    backward kernel 0.5-0.7 and a tensor-parallel all-reduce 0.7-0.8;
    the gradient sync 0.8-0.9; the optimizer 0.9-0.95; and a convert
    the compiler made, without metadata, 0.95-1.0."""
    step = "jit__local_step "
    return {"scoped_trace": {
        "ops": [
            ("while.1", 0.0, 0.4, step + "while",
             "jit(_local_step)/jvp(apex.layers)/while"),
            ("fusion.1", 0.0, 0.2, step + "fusion kOutput",
             FWD + "apex.attn/dot_general"),
            ("fusion.2", 0.2, 0.35, step + "fusion kOutput",
             FWD + "apex.mlp/dot_general"),
            ("while.2", 0.4, 0.8, step + "while",
             "jit(_local_step)/transpose(jvp(apex.layers))/while"),
            ("fusion.3", 0.4, 0.5, step + "fusion kOutput",
             BWD + "checkpoint/rematted_computation/apex.attn/dot_general"),
            ("flash_attn_bwd.2", 0.5, 0.7,
             step + "custom-call tpu_custom_call",
             BWD + "checkpoint/apex.attn/flash_attn_bwd/pallas_call"),
            ("psum.4", 0.7, 0.8, step + "all-reduce",
             BWD + "transpose(jvp(apex.mlp))/psum"),
            ("all-reduce.9", 0.8, 0.9, step + "all-reduce",
             "jit(_local_step)/apex.grad_sync/psum"),
            ("fusion.7", 0.9, 0.95, step + "fusion kLoop",
             "jit(_local_step)/apex.optimizer/add"),
            ("convert.5", 0.95, 1.0, step + "convert", ""),
        ],
        "in_flight": [],
        "host": [("apex.engine.fetch", 0.0, 1.0)]}}


def test_train_regions_partition_busy_time(made):
    share = lambda metric: regions.region_share(made, **params_of(metric))
    assert share("train_fwd_share") == pytest.approx(40.0)     # with the loop
    assert share("train_bwd_share") == pytest.approx(40.0)     # recompute too
    assert share("optimizer_share") == pytest.approx(5.0)
    assert share("ce_head_share") is None     # no such operation: not 0
    rest = regions.unattributed_share(
        made, **params_of("region_unattributed_share.train"))
    assert rest == pytest.approx(5.0)
    dp = regions.collective_share(made, **params_of("dp_sync_share"))
    tp = regions.collective_share(made, **params_of("tp_collective_share"))
    assert (tp, dp) == (pytest.approx(10.0), pytest.approx(10.0))
    # forward + backward + optimizer + gradient sync + the rest = all
    assert 40.0 + 40.0 + 5.0 + dp + rest == pytest.approx(100.0)


def test_a_loop_owns_the_time_between_its_operations(made):
    own = {e[0]: t for e, t in
           regions.own_times(made["scoped_trace"]["ops"])}
    assert own["while.1"] == pytest.approx(0.05)
    assert own["while.2"] == pytest.approx(0.0)
    assert sum(own.values()) == pytest.approx(1.0)


def test_a_program_without_scopes_gives_nothing_to_read(made):
    bare = {"scoped_trace": dict(made["scoped_trace"], ops=[
        (*e[:4], "") for e in made["scoped_trace"]["ops"]])}
    for metric in ("train_fwd_share", "optimizer_share"):
        assert regions.region_share(bare, **params_of(metric)) is None
    assert regions.unattributed_share(bare) is None
    assert regions.collective_share(
        bare, region="apex.grad_sync", inside=True) is None
    assert regions.region_share({}, **params_of("sampler_share")) is None
    assert regions.uncovered_idle_share({}, ["apex.engine.fetch"]) is None


def test_idle_time_the_host_did_not_spend_waiting():
    scoped = {"ops": [("fusion.1", 0.0, 0.4, "jit_step_local fusion", ""),
                      ("fusion.2", 0.6, 0.9, "jit_step_local fusion", "")],
              "in_flight": [],
              "host": [("apex.sched.step", 0.0, 1.0),
                       ("apex.sched.collect", 0.0, 0.55),
                       ("apex.engine.fetch", 0.0, 0.45)]}
    ev = {"scoped_trace": scoped}
    # idle 0.4-0.6 and 0.9-1.0; the fetch covers 0.4-0.45 of it
    assert regions.uncovered_idle_share(
        ev, **params_of("host_bound_idle_share")) == pytest.approx(25.0)
    gaps = regions.longest_idle_gaps(scoped, 2)
    assert [(round(s, 3), g[2]) for g in gaps for s in [g[1]]] == [
        (0.2, "apex.sched.collect"), (0.1, "apex.sched.step")]
    # a parent records no such annotation
    scoped["host"] = [("bench.sched_step", 0.0, 1.0)]
    assert regions.uncovered_idle_share(ev, ["apex.engine.fetch"]) is None


def test_recorded_slice_names_its_time():
    scoped = regions.load_plain(RECORDED)
    ev = {"scoped_trace": scoped}
    table = {(p, r): s for p, r, _, s in regions.region_table(scoped)}
    # the admission programs' attention and MLP, the step program's
    # kernels, cache slices and sampler all carry their region
    for key in (("jit_admit_local", "apex.attn"),
                ("jit_admit_local", "apex.prefill.cache_insert"),
                ("jit_step_local", "apex.decode.attn"),
                ("jit_step_local", "apex.decode.cache_slice"),
                ("jit_step_local", "apex.decode.layers"),
                ("jit_step_local", "apex.sample")):
        assert table[key] > 0, key
    busy = sum(table.values())
    copies = regions.region_share(ev, **params_of("cache_copy_share"))
    # the next step program's entry copy of the whole cache (18 ms, no
    # scope) is in the slice and counts by its opcode
    # (with a few us of the sampler's unscoped copies)
    entry = [e for e in scoped["ops"] if e[0].startswith("copy.")
             and e[3] == "jit_step_local copy"
             and not regions.regions_of(e[4])]
    assert 0.015 < sum(e[2] - e[1] for e in entry) < 0.019
    assert copies == pytest.approx(100.0 * (
        table["jit_step_local", "apex.decode.layers"]
        + table["jit_step_local", "apex.decode.cache_slice"]
        + table.get(("jit_step_local", "apex.decode.cache_stack"), 0.0)
        + sum(e[2] - e[1] for e in entry)) / busy)
    rest = regions.unattributed_share(ev)
    assert rest == pytest.approx(100.0 * (
        table["jit_step_local", "-"] + table["jit_admit_local", "-"])
        / busy)
    assert regions.unattributed_ops(scoped, 1)[0][0] == "jit_step_local:copy"
    # the longest gaps lie under the program's own annotations
    assert {g[2] for g in regions.longest_idle_gaps(scoped, 3)} <= {
        "apex.engine.fetch", "apex.sched.collect", "apex.engine.admit"}
    assert 0 < regions.uncovered_idle_share(
        ev, **params_of("host_bound_idle_share")) < 100


def test_scope_paths_from_the_wire_format(tmp_path):
    """A two-plane XSpace written by hand: the device plane's metadata
    for one operation with a ``tf_op`` stat, a host plane that is
    skipped."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(no, payload):
        if isinstance(payload, int):
            return varint(no << 3) + varint(payload)
        return varint(no << 3 | 2) + varint(len(payload)) + payload

    stat_meta = field(1, 7) + field(2, b"tf_op")
    stat = field(1, 7) + field(5, b"jit(f)/apex.mlp/dot_general:")
    other = field(1, 8) + field(5, b"convolution fusion")
    event_meta = (field(1, 3) + field(2, b"%fusion.1 = f32[] fusion()")
                  + field(5, other) + field(5, stat))
    device = (field(2, b"/device:TPU:0")
              + field(4, field(1, 3) + field(2, event_meta))
              + field(5, field(1, 7) + field(2, stat_meta)))
    host = (field(2, b"/host:CPU")
            + field(4, field(1, 3) + field(2, event_meta)))
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(field(1, host) + field(1, device))
    assert regions.scope_paths(str(path)) == {
        "%fusion.1 = f32[] fusion()": "jit(f)/apex.mlp/dot_general"}
    assert regions.regions_of(
        "jit(s)/transpose(jvp(apex.layers))/while/body/apex.attn/dot"
    ) == ["apex.layers", "apex.attn"]


# -- spans and counts --------------------------------------------------------

@pytest.fixture
def ticks():
    """Two ticks of a scheduler, 0.0-1.0 and 1.0-2.2, the window
    0.5-2.5. Request a is admitted in the first (prefill mark 0.10,
    first token 0.15), b and c in the second (0.98 -> 1.05 + 0.05);
    a fell due before the window."""
    M, S, C = 0, 1, 2
    rows = [
        (M, 0.10, "a", "prefill", "slot 0"), (M, 0.15, "a", "first_token",
                                              None),
        (C, 0.15, "prefill.tokens_real", 100, None),
        (C, 0.15, "prefill.tokens_padded", 256, None),
        (S, 0.10, "engine.admit", 0.15, "sched.admit"),
        (S, 0.0, "sched.step", 1.0, None),
        (M, 1.05, "b", "prefill", "slot 1"), (M, 1.05, "c", "prefill",
                                              "slot 2"),
        (M, 1.10, "b", "first_token", None), (M, 1.10, "c", "first_token",
                                              None),
        (M, 1.9, "b", "decode", None),
        (C, 1.10, "prefill.tokens_real", 300, None),
        (C, 1.10, "prefill.tokens_padded", 512, None),
        (C, 1.10, "prefill.rows", 2, None),
        (C, 1.10, "prefill.dispatches", 1, None),
        (S, 1.05, "engine.admit", 1.10, "sched.admit"),
        (S, 1.0, "sched.step", 2.2, None),
    ]
    return {"spans": rows,
            "window": {"start": 0.5, "end": 2.5, "seconds": 2.0},
            "due_at": {"b": 0.6, "c": 0.9}}


def test_span_readers_on_hand_made_ticks(ticks):
    assert phases.mark_to_mark_ms_p50(
        ticks, **params_of("prefill_ms_p50")) == pytest.approx(50.0)
    # b and c wait from 1.10 to the end of their tick at 2.2
    assert phases.mark_to_section_end_ms_p50(
        ticks, **params_of("first_token_hold_ms_p50")
    ) == pytest.approx(1100.0)
    # the first tick began before the window
    assert phases.section_ms_max(
        ticks, **params_of("sched_step_ms_max")) == pytest.approx(1200.0)
    assert phases.padding_share(
        ticks, **params_of("admit_padding_share")
    ) == pytest.approx(100.0 * (1 - 300 / 512))
    # b and c rode one program
    assert phases.count_ratio(
        ticks, **params_of("admit_rows_per_dispatch")) == 2.0


def test_closed_loop_has_no_due_times(ticks):
    del ticks["due_at"]       # then: the requests marked in the window
    assert phases.mark_to_mark_ms_p50(
        ticks, start="prefill", end="first_token") == pytest.approx(50.0)
    ticks["window"] = {"start": 0.0, "end": 2.5, "seconds": 2.5}
    assert phases.mark_to_section_end_ms_p50(
        ticks, phase="first_token", section="sched.step"
    ) == pytest.approx(1100.0)      # a waits 850, b and c 1100


def test_a_program_without_phases_gives_nothing_to_read(ticks):
    old = {**ticks, "spans": [
        (*e[:4], None) for e in ticks["spans"]
        if e[0] != 2 and e[2] != "sched.step"]}
    assert phases.mark_to_section_end_ms_p50(
        old, phase="first_token", section="sched.step") is None
    assert phases.section_ms_max(old, section="sched.step") is None
    assert phases.padding_share(old, real="prefill.tokens_real",
                                padded="prefill.tokens_padded") is None
    assert phases.count_ratio(old, of="prefill.rows",
                              per="prefill.dispatches") is None
    # the marks were there before: this one reads on a parent too
    assert phases.mark_to_mark_ms_p50(
        old, start="prefill", end="first_token") == pytest.approx(50.0)
    assert phases.section_ms_max({"spans": None, "window": ticks["window"]},
                                 section="sched.step") is None


def test_every_new_metric_file_names_a_reader():
    folder = os.path.join(ROOT, "layer_metrics")
    for fn in sorted(os.listdir(folder)):
        if fn.endswith(".json"):
            with open(os.path.join(folder, fn)) as f:
                spec = json.load(f)
            recipe.reader_of(spec)
