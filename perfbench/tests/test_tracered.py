"""The trace reduction, on hand-made intervals with exact answers and on a
small trace recorded on the chip (recorded_trace.json: 0.4 s of the
ResNet-50 cell's traced slice, device events and host spans)."""

import json
import os

import numpy as np
import pytest

from perfbench import tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def test_merge_and_busy():
    ops = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 0.5),
           ("d", 3.2, 0.1), ("e", 9.0, 5.0)]
    busy, merged = tracered.busy_seconds(ops, 0.0, 10.0)
    assert merged == [(0.0, 1.5), (3.0, 3.5), (9.0, 10.0)]
    assert busy == pytest.approx(3.0)
    assert tracered.idle_gaps(merged, 0.0, 10.0) == [(1.5, 3.0), (3.5, 9.0)]


def test_gap_goes_to_innermost_span():
    gaps = [(1.0, 3.0), (5.0, 5.00001)]
    spans = [("outer", 0.0, 10.0), ("inner", 2.0, 0.5)]
    got = tracered.attribute_gaps(gaps, spans)
    assert got["inner"] == pytest.approx(0.5)
    assert got["outer"] == pytest.approx(1.5)
    assert got[tracered.SHORT_GAPS] == pytest.approx(1e-5)
    assert tracered.attribute_gaps([(20.0, 21.0)], spans) == {
        tracered.UNATTRIBUTED: pytest.approx(1.0)}


def test_entry_durations_and_top_ops():
    mods = [("jit__train_step(123)", 1.0, 0.1), ("jit_other", 1.2, 0.3),
            ("jit__train_step(123)", 1.9, 0.2)]
    assert tracered.entry_durations(mods, "train_step", 0.0, 2.0) == [0.1]
    ops = [("x", 0.1, 0.2), ("y", 0.4, 0.1), ("x", 0.6, 0.2)]
    assert tracered.top_ops(ops, 0.0, 1.0, n=1) == [("x", pytest.approx(0.4))]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_busy_matches_sampling(recorded):
    """The union's length against an independent estimate: the share of
    200 000 evenly spaced instants that fall inside some operation."""
    w0, w1 = recorded["w0"], recorded["w1"]
    loaded = {"devices": {p: {ln: [tuple(e) for e in evs]
                              for ln, evs in lines.items()}
                          for p, lines in recorded["devices"].items()}}
    spans = [tuple(s) for s in recorded["host_spans"]]
    red = tracered.reduce_trace(loaded, w0, w1, spans)
    ops = next(iter(loaded["devices"].values()))[tracered.OPS_LINE]
    starts = np.array([s for _, s, _ in ops])
    ends = starts + np.array([d for _, _, d in ops])
    order = np.argsort(starts)
    starts, ends = starts[order], np.maximum.accumulate(ends[order])
    pts = np.linspace(w0, w1, 200_000, endpoint=False)
    idx = np.searchsorted(starts, pts, side="right") - 1
    inside = (idx >= 0) & (pts < ends[np.clip(idx, 0, None)])
    assert red["busy_s"] / red["window_s"] == pytest.approx(
        inside.mean(), abs=2e-3)
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9
    assert 0 < red["busy_s"] < red["window_s"]
    steps = tracered.entry_durations(red["modules"], recorded["entry"],
                                     w0, w1)
    assert len(steps) == recorded["entry_executions"]
