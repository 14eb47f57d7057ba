"""`paged_prefill_roofline` on hand-made spans and device times: the
ratio of sums over the passes of the traced slice, the pairing of the
trace's executions with the window's passes across the two clocks, and
no number where the ring dropped spans or the slice held no pass. (The
readers of PR 26 to PR 31 are checked in tests/test_perfbench_readers.py,
which a `benchmark` PR may not edit.)"""

import numpy as np
import pytest

from deeplearning4j_tpu.runtime import telemetry
from perfbench import harness, peaks

MS = 1e-3
OFF = -10.9                     # trace clock = perf_counter + OFF

# (rid, chunk, perf_counter start of the span, delay to the device's
# start, device seconds); the fifth to seventh are one 1024-token prompt
PASSES = [(7, 128, 10.100, 1.5 * MS, 3.9 * MS),     # before the trace
          (8, 300, 10.150, 1.5 * MS, 7.3 * MS),     # before the trace
          (12, 128, 10.930, 1.5 * MS, 3.9 * MS),    # traced, before w0
          (1, 384, 11.000, 1.5 * MS, 7.3 * MS),
          (1, 384, 11.001, 7.8 * MS, 8.4 * MS),     # queued behind it
          (1, 256, 11.002, 15.2 * MS, 5.9 * MS),
          (9, 200, 11.040, 1.5 * MS, 5.0 * MS),
          (10, 128, 11.090, 1.6 * MS, 100 * MS),    # ends after w1
          (11, 384, 12.500, 1.5 * MS, 7.3 * MS)]    # after the trace
TRACED = slice(2, 8)


def least_s(chunk, context):
    """The floor of one pass, spelled out for Cerebras-GPT 1.3B on a
    v5e: 24 layers of 4 d^2 + 2 d ff, one row of logits, 4 d L a (query,
    key) pair; weights once and 2 L d bfloat16 a live token."""
    layers = 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192)
    logits = 2048 * 50257
    pairs = sum(range(context + 1, context + chunk + 1))
    flops = 2 * layers * chunk + 2 * logits + 4 * 2048 * 24 * pairs
    bytes_ = 2 * (layers + logits) + (context + chunk) * 2 * 24 * 2048 * 2
    return max(flops / 197e12, bytes_ / 819e9)


class StubRun:
    window = {"t0": 10.0, "t1": 50.0}
    program_spans = harness.Run.program_spans
    config = harness.load_json("configs", "cerebras-gpt-1.3b.json")
    arith = harness.load_module("arith", config["arith"])
    peaks = peaks.PEAKS["TPU v5 lite"]
    traced = {"w0": 10.95 + OFF, "w1": 11.15 + OFF, "modules": [
        ("jit__prefill_paged(%d)" % chunk, ts + OFF + delay, dur)
        for _, chunk, ts, delay, dur in PASSES[TRACED]]
        + [("jit__decode_paged(1)", 11.05 + OFF, 7 * MS)]}


@pytest.fixture
def spans():
    reg = telemetry.get_registry()
    reg.trace.clear()
    for rid, chunk, ts, _, _ in PASSES:
        reg.add_span("sequence.prefill", "serving", ts, 1 * MS, rid=rid,
                     chunk=chunk)
    yield reg
    reg.trace.clear()


def read(run=StubRun):
    return harness.load_module("metrics", "paged_prefill_roofline").read(run())


def test_ratio_of_sums_over_the_passes_of_the_slice(spans):
    """Four passes lie wholly inside the slice: the 1024-token prompt's
    three (contexts 0, 384, 768) and one of 200 tokens."""
    least = least_s(384, 0) + least_s(384, 384) + least_s(256, 768) \
        + least_s(200, 0)
    assert least_s(384, 0) == pytest.approx(4.78402e-3, rel=1e-5)
    assert least_s(200, 0) == pytest.approx(3.24919e-3, rel=1e-5)
    assert read() == pytest.approx(
        100 * least / ((7.3 + 8.4 + 5.9 + 5.0) * MS), rel=1e-9)


def test_no_number_where_the_ring_dropped_or_the_slice_is_empty(spans):
    class Empty(StubRun):
        traced = dict(StubRun.traced, w0=0.30, w1=0.40)

    class Untraced(StubRun):
        traced = None
    assert read(Empty) is None and read(Untraced) is None
    spans.add_span("sequence.prefill", "serving", 11.2, 1 * MS, rid=13)
    assert read() is None           # a pass without its chunk
    spans.trace.clear()
    assert read() is None           # the program left no span
    spans.trace.dropped = 1
    assert read() is None


def test_pairing_finds_the_passes_under_queueing():
    """300 passes at uneven gaps, 40 of them traced from the 137th on;
    six in ten start 1.4-1.6 ms after their dispatch, the others queue
    for up to 20 ms more."""
    rng = np.random.default_rng(33)
    pair = harness.load_module("metrics", "paged_prefill_roofline").pair
    starts = 10 + np.cumsum(rng.uniform(5 * MS, 60 * MS, 300))
    delay = 1.4 * MS + rng.uniform(0, 0.2 * MS, 300) \
        + np.where(rng.random(300) < 0.4, rng.uniform(0, 20 * MS, 300), 0)
    j, spread = pair((starts + delay - 9.5)[137:177], starts)
    assert j == 137 and spread < 0.3 * MS
