"""The per-layer readers that ISSUE 26 adds under perfbench/metrics/ (and
ISSUE 27's `paged_attend.pages_visited_share`, ISSUE 31's
`seq.prefill_tokens_per_pass_mean` and its decode-cell twin, ISSUE 35's
`seq.device_sampled_share`, `seq.dispatch_ahead_share`, and the five
that split the host's time around a prompt's first token), fed
hand-made spans: each returns the number worked out by hand below, None
where the ring dropped spans (a truncated window gives no number) and
None, without raising, where the program left nothing to read (the
parent commit's case). Tier-1 does not collect perfbench/tests/, so the
readers of the program's spans are checked here, beside the spans.
"""

import threading

import pytest

from deeplearning4j_tpu.runtime import telemetry
from perfbench import harness

D = "gpt1.3b-generate-decode"
P = "gpt1.3b-generate-prefill"
R = "resnet50-fit-resident"
G = "glm4.7flash-generate-reasoning"

#: metric -> the number the spans of _fill() give (worked out in _fill)
EXPECTED = {
    "seq.iteration_ms_p50": 80.0,
    "seq.sample_ms_p50": 7.0,
    "seq.iteration_self_ms_p50": 2.0,
    "seq.iterations_with_prefill_share": 100.0 / 3,
    "seq.inter_token_gap_p50_ms": 100.0,
    "seq.inter_token_gap_p99_ms": 109.8,
    "kv.pages_in_use_max": 319.0,
    "seq.prefill_wait_p95_ms": 48.0,
    "seq.prefill_service_p50_ms": 145.0,
    "seq.ttft_inside_p50_ms": 175.0,
    "seq.ttft_inside_p95_ms": 242.5,
    "prefill.idle_with_work_share": 10.0,
    "fit.dispatch_ms_mean": 3.0,
    "fit.sync_wait_ms_mean": 97.0,
    "fit.outside_step_ms_mean": 2.8,
    "paged_attend.pages_visited_share": 100.0 * 129 / 304,
    "seq.prefill_tokens_per_pass_mean": 200.0,
    "seq.prefill_tokens_per_pass_mean.decode": 200.0,
    "seq.device_sampled_share": 100.0 * 43 / 46,
    "seq.dispatch_ahead_share": 100.0 * 2 / 3,
    "aot.prefill_sign_ms_p50": 0.75,
    "aot.prefill_call_ms_p50": 1.5,
    "seq.first_token_wait_ms_p50": 3.0,
    "seq.first_token_host_ms_p50": 2.0,
    "seq.wake_ms_p50": 0.5,
}
#: what the parent commit's program leaves of a prompt's passes: the
#: passes and their finish, none of the spans inside them
FIRST_TOKEN = ("aot.prefill_sign_ms_p50", "aot.prefill_call_ms_p50",
               "seq.first_token_wait_ms_p50", "seq.first_token_host_ms_p50",
               "seq.wake_ms_p50")
SETUP = ("setup.weights_init_s", "setup.warm_s")


class StubRun:
    """What a reader takes from the harness's Run: the window, the
    reduced trace and the window's program spans by name."""

    window = {"t0": 10.0, "t1": 50.0}
    traced = {"window_s": 3.0, "busy_s": 2.1,
              "idle_gaps": [["sequence.idle", 0.6],
                            ["sequence.prefill", 0.2],
                            ["unattributed", 0.1]]}
    program_spans = harness.Run.program_spans


def _iteration(reg, ts, dur, pages, parts):
    """One sequence.iteration at `ts` with children laid end to end;
    a part is (name, seconds) or (name, seconds, args)."""
    it = reg.new_span_id()
    at = ts
    for name, d, *args in parts:
        sid = reg.add_span(name, "serving", at, d, parent=it,
                           **(args[0] if args else {}))
        if name == "sequence.step":
            reg.add_span("sequence.fetch", "serving", at + d / 2, d / 2,
                         parent=sid, bytes=1)
        at += d
    reg.add_span("sequence.iteration", "serving", ts, dur, span_id=it,
                 pages_in_use=pages)


def _request(reg, rid, enq, chunk, tokens, error=None):
    reg.event("sequence.request", "serving", ts=tokens[-1], rid=rid,
              prompt_tokens=1, new_tokens=len(tokens), chunks=1,
              enqueued_at=enq, started_at=enq, first_chunk_at=chunk,
              first_token_at=tokens[0], finished_at=tokens[-1],
              token_times=tuple(tokens), error=error)


def _pages(visited, table, picked=16, slots=16, ahead=1):
    return {"attend": "pallas", "pages_visited": visited,
            "pages_table": table, "device_picked": picked, "slots": slots,
            "ahead": ahead}


def _fill(reg):
    ms = 1e-3
    # three iterations in the window: 100, 70, 80 ms -> median 80; their
    # children cover 98, 69, 77 ms -> self 2, 1, 3 -> median 2; samples
    # 5, 7, 9 ms -> median 7; one of three carries a chunk; pages 300,
    # 319, 310 -> 319. Their steps read 40 of 96, 45 of 96 and 44 of 112
    # pages -> 129 of 304, and took the device's token in 16 of 16, 15 of
    # 16 and 12 of 14 live slots -> 43 of 46; the first two were queued
    # on the ids of the step before and the third was not -> 2 of 3. One
    # iteration before the window counts nowhere.
    # Two prefill passes in the window: 300 tokens in a chunk of 512,
    # and 100 in a span without `bucket` (the parent commit's) -> 200.
    _iteration(reg, 5.0, 1.0, 999, [
        ("sequence.prefill", 0.4, {"chunk": 999, "bucket": 1024}),
        ("sequence.step", 0.1, _pages(16, 16, 0)),
        ("sequence.sample", 0.5)])
    _iteration(reg, 10.0, 100 * ms, 300, [
        ("sequence.admit", 1 * ms),
        ("sequence.prefill", 30 * ms, {"chunk": 300, "bucket": 512}),
        ("sequence.decode_prep", 2 * ms),
        ("sequence.step", 60 * ms, _pages(40, 96)),
        ("sequence.sample", 5 * ms)])
    _iteration(reg, 10.2, 70 * ms, 319, [
        ("sequence.admit", 1 * ms), ("sequence.decode_prep", 1 * ms),
        ("sequence.step", 60 * ms, _pages(45, 96, 15)),
        ("sequence.sample", 7 * ms)])
    _iteration(reg, 10.4, 80 * ms, 310, [
        ("sequence.admit", 1 * ms), ("sequence.decode_prep", 1 * ms),
        ("sequence.step", 66 * ms, _pages(44, 112, 12, 14, 0)),
        ("sequence.sample", 9 * ms)])
    reg.add_span("sequence.prefill", "serving", 10.6, 4 * ms, chunk=100)
    # two requests count: time to first token 100 and 250 ms (median 175,
    # 95th 242.5), wait for the first chunk 10 and 50 ms (95th 48),
    # service 90 and 200 ms (median 145), gaps 100, 110 and 80 ms
    # (median 100, 99th 100 + 0.98 x 10). Left out: enqueued before the
    # window, failed, ended after the window.
    _request(reg, 1, 11.0, 11.010, [11.100, 11.200, 11.310])
    _request(reg, 2, 12.0, 12.050, [12.250, 12.330])
    _request(reg, 3, 9.0, 9.5, [10.5, 30.0])
    _request(reg, 4, 13.0, 13.5, [14.0, 20.0], error="ServingClosedError")
    _request(reg, 5, 49.0, 49.5, [49.9, 51.0])
    _first_token_spans(reg)
    # two steps: dispatch 2 and 4 ms (mean 3), sync 98 and 96 (mean 97),
    # outside the step 1 + 0.5 + 0.3 and 3 + 0.5 + 0.3 ms (mean 2.8)
    for t, prep, disp in ((20.0, 1 * ms, 2 * ms), (20.2, 3 * ms, 4 * ms)):
        step = reg.new_span_id()
        reg.add_span("train.data_wait", "train", t - 0.001, 0.3 * ms)
        reg.add_span("train.prepare", "train", t, prep)
        reg.add_span("train.dispatch", "train", t + prep, disp, parent=step)
        reg.add_span("train.sync", "train", t + prep + disp,
                     100 * ms - disp, parent=step)
        reg.add_span("train.step", "train", t + prep, 100 * ms,
                     span_id=step)
        reg.add_span("train.listeners", "train", t + prep + 100 * ms,
                     0.5 * ms)


def _first_token_spans(reg):
    """Around the window's two prefill passes (10.001 for 30 ms and 10.6
    for 4 ms): signs of 1 and 0.5 ms (median 0.75) and calls of 2 and
    1 ms (median 1.5) inside them; a sign inside no pass, one in the
    first pass's time on another thread and one in the pass before the
    window count nowhere. Two finishes of 4 and 6 ms whose waits are 1
    and 5 ms (median 3) leave 3 and 1 ms to the host (median 2); a
    finish without a wait (the parent commit's) and one before the
    window count for neither. Wake-ups of requests 1 and 2, 0.4 and
    0.6 ms (median 0.5); those of 3 (enqueued before the window) and 4
    (failed) do not count."""
    ms = 1e-3
    for ts, sign, call in ((10.002, 1 * ms, 2 * ms),
                           (10.6005, 0.5 * ms, 1 * ms),
                           (5.1, 5 * ms, 5 * ms)):
        reg.add_span("aot.sign", "compile", ts, sign, entry="paged_prefill")
        reg.add_span("aot.call", "compile", ts + sign, call,
                     entry="paged_prefill")
    reg.add_span("aot.sign", "compile", 10.2, 9 * ms, entry="paged_decode")
    other = threading.Thread(target=reg.add_span, args=(
        "aot.sign", "compile", 10.010, 7 * ms))
    other.start()
    other.join()
    for ts, dur, wait in ((10.035, 4 * ms, 1 * ms),
                          (10.61, 6 * ms, 5 * ms),
                          (5.5, 0.1, 0.05)):
        fid = reg.new_span_id()
        reg.add_span("sequence.prefill_wait", "serving", ts, wait,
                     parent=fid)
        reg.add_span("sequence.prefill_finish", "serving", ts, dur,
                     span_id=fid)
    reg.add_span("sequence.prefill_finish", "serving", 10.7, 10 * ms)
    for rid, dur in ((1, 0.4 * ms), (2, 0.6 * ms), (3, 50 * ms),
                     (4, 50 * ms)):
        reg.add_span("sequence.wake", "serving", 11.0 + rid, dur, rid=rid)


@pytest.fixture
def filled():
    reg = telemetry.get_registry()
    reg.trace.clear()
    _fill(reg)
    yield reg
    reg.trace.clear()


def _read(name):
    return harness.load_module("metrics", name).read(StubRun())


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_hand_made_number(name, filled):
    assert _read(name) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED) + list(SETUP))
def test_reader_gives_none_where_the_ring_dropped(name, filled):
    filled.counter("dl4j_setup_seconds", labels=("phase",))
    filled.trace.dropped = 1
    assert _read(name) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_where_nothing_was_recorded(name):
    """The parent commit's case: a program without the spans."""
    trace = telemetry.get_registry().trace
    trace.clear()
    assert _read(name) is None


@pytest.mark.parametrize("name", ["paged_attend.pages_visited_share",
                                  "seq.device_sampled_share",
                                  "seq.dispatch_ahead_share"])
def test_step_reader_is_none_where_a_step_lacks_the_args(name, filled):
    """The parent commit's `sequence.step` carries slots and bucket
    only: one such step in the window and the reader gives no number."""
    filled.add_span("sequence.step", "serving", 10.6, 0.06, slots=16,
                    bucket=16)
    assert _read(name) is None


@pytest.mark.parametrize("name", ["seq.prefill_tokens_per_pass_mean",
                                  "seq.prefill_tokens_per_pass_mean.decode"])
def test_tokens_per_pass_is_none_where_a_pass_lacks_its_chunk(name, filled):
    filled.add_span("sequence.prefill", "serving", 10.7, 0.004)
    assert _read(name) is None


@pytest.mark.parametrize("name", FIRST_TOKEN)
def test_first_token_reader_is_none_on_the_parents_spans(name):
    """The parent commit leaves passes, finishes and requests but no
    span inside them and no wake-up: no number, and no error."""
    reg = telemetry.get_registry()
    reg.trace.clear()
    reg.add_span("sequence.prefill", "serving", 10.0, 0.03, chunk=300)
    reg.add_span("sequence.prefill_finish", "serving", 10.03, 0.004)
    _request(reg, 1, 11.0, 11.010, [11.100])
    try:
        assert _read(name) is None
    finally:
        reg.trace.clear()


def test_idle_with_work_is_none_where_the_top_ten_hide_the_waiting(filled):
    """tracered keeps the ten largest names of idle_gaps: ten of them
    and no sequence.idle cannot be told from no waiting at all; fewer
    than ten and none means there was none."""
    class Cut(StubRun):
        traced = dict(StubRun.traced, idle_gaps=[
            [f"bench.span{i}", 0.05] for i in range(10)])
    read = harness.load_module(
        "metrics", "prefill.idle_with_work_share").read
    assert read(Cut()) is None
    Cut.traced = dict(Cut.traced, idle_gaps=Cut.traced["idle_gaps"][:9])
    assert read(Cut()) == pytest.approx(30.0)


def test_setup_readers_read_the_counter():
    reg = telemetry.get_registry()
    reg.trace.clear()
    fam = reg.counter("dl4j_setup_seconds", labels=("phase",))
    w0 = _read("setup.weights_init_s")
    fam.labels(phase="weights_init").inc(1.5)
    assert _read("setup.weights_init_s") == pytest.approx(w0 + 1.5)
    # a phase the process never entered reads 0, not nothing
    mod = harness.load_module("metrics", "setup.weights_init_s")
    assert mod.phase_seconds("never_entered") == 0.0
    assert fam.labels_get(phase="never_entered") is None
    m0 = _read("setup.warm_s")
    fam.labels(phase="warm").inc(0.25)
    assert _read("setup.warm_s") == pytest.approx(m0 + 0.25)


def test_self_time_of_a_span_with_two_children():
    """Duration minus what the children cover: overlapping children
    count once, and what sticks out of the parent does not count."""
    self_seconds = harness.load_module(
        "metrics", "seq.iteration_self_ms_p50").self_seconds
    parent = {"ts": 1.0, "dur": 1.0}
    apart = [{"ts": 1.1, "dur": 0.2}, {"ts": 1.5, "dur": 0.3}]
    assert self_seconds(parent, apart) == pytest.approx(0.5)
    overlap = [{"ts": 1.1, "dur": 0.4}, {"ts": 1.3, "dur": 0.4}]
    assert self_seconds(parent, overlap) == pytest.approx(0.4)
    beyond = [{"ts": 0.5, "dur": 0.7}, {"ts": 1.9, "dur": 0.5}]
    assert self_seconds(parent, beyond) == pytest.approx(0.7)
    assert self_seconds(parent, []) == 1.0


def test_manifest_lists_the_new_metrics_with_their_readers():
    manifest = harness.load_manifest()
    by = {m["name"]: m for m in manifest["per_layer"]}
    cells = {"seq.prefill_wait_p95_ms": P, "seq.prefill_service_p50_ms": P,
             "seq.ttft_inside_p50_ms": P, "seq.ttft_inside_p95_ms": P,
             "prefill.idle_with_work_share": P,
             "seq.prefill_tokens_per_pass_mean": P, "fit.dispatch_ms_mean": R,
             **{name: P for name in FIRST_TOKEN},
             "fit.sync_wait_ms_mean": R, "fit.outside_step_ms_mean": R}
    # the readers of any paged LM list the GLM-4.7-Flash cell too
    glm = {"seq.iteration_ms_p50", "seq.dispatch_ahead_share"}
    for name in EXPECTED:
        assert by[name]["workloads"] == [cells.get(name, D)] + (
            [G] if name in glm else []), name
    assert by["paged_attend.pages_visited_share"]["layer"] == "kernels" \
        and by["paged_attend.pages_visited_share"]["better"] == "lower"
    for name in SETUP:                      # every cell reports setup_s
        assert by[name]["workloads"] == [R, D, P, G] and \
            by[name]["moves"] == "setup_s" and by[name]["layer"] == "set-up"
    for name in list(EXPECTED) + list(SETUP):
        doc = harness.load_module("metrics", name).__doc__
        assert doc.startswith(f"Layer: {by[name]['layer']}. "
                              f"Source: {by[name]['source']}"), name
    # and the six of that cell (tests/test_latent_moe_readers.py)
    assert len(by) == 19 + len(EXPECTED) + len(SETUP) + 6
