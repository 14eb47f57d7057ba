"""bench.py headline A/B selection logic, stubbed (no TPU, no compiles).

The maxpool / stem / remat A/Bs decide what the ONE driver-visible
headline number reports. A control-flow bug here would only surface
during a chip run — the scarcest resource there is — so the selection
logic is pinned against stub measurements.
"""

import json

import numpy as np
import pytest

import bench


def _rec(ips, **extra):
    r = {"images_per_sec": ips, "step_ms": round(128 / ips * 1e3, 2),
         "batch": 128, "compile_s": 1.0, "flops_per_step": 1e12,
         "hbm_bytes_per_step": 1e10, "mfu": 0.3,
         "limiter": "stub"}
    r.update(extra)
    return r


@pytest.fixture
def stub(monkeypatch):
    # bench_resnet50's maxpool A/B rebinds the module global
    # _BACKWARD_IMPL to the measured winner; restore the default (stock)
    # for later tests in this process
    from deeplearning4j_tpu.ops import pooling as _pooling

    monkeypatch.setattr(_pooling, "_BACKWARD_IMPL",
                        _pooling._BACKWARD_IMPL)
    calls = []

    def fake_measure(stem, remat=False, tail_mode=None):
        if tail_mode is not None:
            # the round-6 dtype-tail leg: serve the ("<stem>", "wide")
            # entry when a test provides one, else a slow losing leg so
            # selection tests written before the leg stay untouched
            calls.append((stem, f"tail:{tail_mode}"))
            return dict(stub.table.get((stem, tail_mode), _rec(1.0)))
        calls.append((stem, remat))
        return dict(stub.table[(stem, remat)])

    monkeypatch.setattr(bench, "_measure_resnet50", fake_measure)
    monkeypatch.setattr(bench, "bench_maxpool_backward",
                        lambda: {"argmax_bwd_ms": 2.0,
                                 "select_and_scatter_bwd_ms": 1.0,
                                 "speedup": 0.5})
    stub.calls = calls
    return stub


class TestHeadlineSelection:
    def test_remat_wins_flips_headline_and_carries_abs(self, stub):
        stub.table = {("standard", False): _rec(1000.0),
                      ("space_to_depth", False): _rec(900.0),
                      ("standard", True): _rec(1100.0)}
        rec = bench.bench_resnet50()
        assert rec["images_per_sec"] == 1100.0
        assert rec["headline_uses_remat"] is True
        # the losing legs stay visible in the record
        assert rec["remat_off"]["images_per_sec"] == 1000.0
        assert rec["stem_space_to_depth"]["images_per_sec"] == 900.0
        assert rec["stem"] == "standard"
        assert rec["maxpool_backward_ab"]["headline_uses"] == "stock"

    def test_remat_loses_keeps_standard_headline(self, stub):
        stub.table = {("standard", False): _rec(1000.0),
                      ("space_to_depth", False): _rec(900.0),
                      ("standard", True): _rec(800.0)}
        rec = bench.bench_resnet50()
        assert rec["images_per_sec"] == 1000.0
        assert rec["headline_uses_remat"] is False
        assert rec["remat_ab"]["images_per_sec"] == 800.0

    def test_s2d_wins_then_remat_measured_on_winning_stem(self, stub):
        stub.table = {("standard", False): _rec(900.0),
                      ("space_to_depth", False): _rec(1000.0),
                      ("space_to_depth", True): _rec(950.0)}
        rec = bench.bench_resnet50()
        assert rec["stem"] == "space_to_depth"
        assert rec["images_per_sec"] == 1000.0
        # remat leg ran on the WINNING stem
        assert ("space_to_depth", True) in stub.calls
        assert rec["stem_standard"]["images_per_sec"] == 900.0

    def test_remat_leg_failure_does_not_lose_headline(self, stub):
        stub.table = {("standard", False): _rec(1000.0),
                      ("space_to_depth", False): _rec(900.0)}

        orig = bench._measure_resnet50

        def boom(stem, remat=False):
            if remat:
                raise RuntimeError("device lost mid-leg")
            return orig(stem, remat)

        import pytest as _pytest
        mp = _pytest.MonkeyPatch()
        mp.setattr(bench, "_measure_resnet50", boom)
        try:
            rec = bench.bench_resnet50()
        finally:
            mp.undo()
        assert rec["images_per_sec"] == 1000.0
        assert "error" in rec["remat_ab"]

    def test_remat_opt_out_env(self, stub, monkeypatch):
        stub.table = {("standard", False): _rec(1000.0),
                      ("space_to_depth", False): _rec(900.0),
                      ("standard", True): _rec(2000.0)}
        monkeypatch.setenv("DL4J_TPU_REMAT", "off")
        rec = bench.bench_resnet50()
        assert rec["images_per_sec"] == 1000.0
        assert "remat_ab" not in rec and "headline_uses_remat" not in rec

    def test_partial_records_parse_as_json(self, stub, capsys):
        stub.table = {("standard", False): _rec(1000.0),
                      ("space_to_depth", False): _rec(900.0),
                      ("standard", True): _rec(1100.0)}
        bench.bench_resnet50()
        partials = [l for l in capsys.readouterr().out.splitlines()
                    if l.startswith("BENCHREC-PARTIAL ")]
        # post-maxpool, post-stem and post-dtype-tail banking
        assert len(partials) == 3
        for p in partials:
            rec = json.loads(p[len("BENCHREC-PARTIAL "):])
            assert rec["images_per_sec"] > 0

    def test_dtype_tail_ab_records_bytes_and_can_flip(self, stub):
        stub.table = {("standard", False): _rec(1000.0),
                      ("space_to_depth", False): _rec(900.0),
                      ("standard", "wide"): _rec(
                          1200.0, hbm_bytes_per_step=1.2e10),
                      ("standard", True): _rec(800.0)}
        rec = bench.bench_resnet50()
        # wide measured faster on this (stubbed) backend: the headline
        # flips — self-protection — but the byte cut of the compute
        # tail stays recorded either way
        assert rec["images_per_sec"] == 1200.0
        ab = rec["dtype_tail_ab"]
        assert ab["headline_uses"] == "wide"
        assert ab["bytes_cut"] == pytest.approx(0.2e10)
        assert ab["compute"]["images_per_sec"] == 1000.0

    def test_dtype_tail_ab_compute_wins_keeps_headline(self, stub):
        stub.table = {("standard", False): _rec(1000.0),
                      ("space_to_depth", False): _rec(900.0),
                      ("standard", "wide"): _rec(
                          700.0, hbm_bytes_per_step=1.2e10),
                      ("standard", True): _rec(800.0)}
        rec = bench.bench_resnet50()
        assert rec["images_per_sec"] == 1000.0
        assert rec["dtype_tail_ab"]["headline_uses"] == "compute"
        assert ("standard", "tail:wide") in stub.calls

    def test_dtype_tail_opt_out_env(self, stub, monkeypatch):
        stub.table = {("standard", False): _rec(1000.0),
                      ("space_to_depth", False): _rec(900.0),
                      ("standard", True): _rec(800.0)}
        monkeypatch.setenv("DL4J_TPU_TAIL_AB", "off")
        rec = bench.bench_resnet50()
        assert "dtype_tail_ab" not in rec
        assert all(not str(c[1]).startswith("tail:") for c in stub.calls)


class TestFailedLegExitCode:
    """A leg that failed makes the run exit nonzero — after the full
    record is printed, so nothing already measured is lost."""

    @pytest.fixture
    def stub_legs(self, monkeypatch):
        monkeypatch.setattr(bench, "_CONFIGS", {})
        monkeypatch.setattr(bench, "_HEADLINE", None)
        monkeypatch.setattr(bench, "_run_config_subprocess",
                            lambda fn, budget: _rec(1000.0))
        for _name, fn_name, _cap in bench.PARENT_LEGS:
            monkeypatch.setattr(bench, fn_name, lambda t: {"ok": True})

    def test_all_legs_ok_returns_normally(self, stub_legs, monkeypatch,
                                          capsys):
        monkeypatch.setattr(
            bench, "_run_secondaries_subprocess",
            lambda budget, deadline_capped=False: {
                n: {"ok": True} for n, _ in bench.SECONDARY_CONFIGS})
        bench.main()
        line = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert line["value"] == 1000.0 and "failed_legs" not in line

    def test_failed_chip_leg_exits_nonzero_with_full_record(
            self, stub_legs, monkeypatch, capsys):
        def secondaries(budget, deadline_capped=False):
            out = {n: {"ok": True} for n, _ in bench.SECONDARY_CONFIGS}
            out["attention"] = {"error": "MosaicError: refused"}
            return out

        monkeypatch.setattr(bench, "_run_secondaries_subprocess",
                            secondaries)
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code == 1
        line = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert line["failed_legs"] == ["attention"]
        assert line["value"] == 1000.0            # headline still banked
        assert line["configs"]["compile_cache"] == {"ok": True}

    def test_raising_parent_leg_is_recorded_and_fails_the_run(
            self, stub_legs, monkeypatch, capsys):
        monkeypatch.setattr(
            bench, "_run_secondaries_subprocess",
            lambda budget, deadline_capped=False: {
                n: {"ok": True} for n, _ in bench.SECONDARY_CONFIGS})

        def boom(t):
            raise RuntimeError("child crashed")

        monkeypatch.setattr(bench, "bench_compile_cache", boom)
        with pytest.raises(SystemExit):
            bench.main()
        line = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert line["failed_legs"] == ["compile_cache"]

    def test_secondaries_group_exits_nonzero_after_banking(
            self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "SECONDARY_CONFIGS",
                            [("a", "_leg_a"), ("b", "_leg_b")])

        def leg_a():
            raise ValueError("kernel refused")

        monkeypatch.setattr(bench, "_leg_a", leg_a, raising=False)
        monkeypatch.setattr(bench, "_leg_b", lambda: {"ok": 1},
                            raising=False)
        with pytest.raises(SystemExit) as exc:
            bench.bench_tpu_secondaries()
        assert exc.value.code == 1
        recs = [json.loads(l[len("BENCHREC-CONFIG "):])
                for l in capsys.readouterr().out.splitlines()
                if l.startswith("BENCHREC-CONFIG ")]
        assert [r["name"] for r in recs] == ["a", "b"]   # b still ran
        assert "kernel refused" in recs[0]["rec"]["error"]


class TestServingPagedLeg:
    """bench_serving_paged's wrapper contract, against a stand-in
    child (the real paged child is a subprocess measurement, not
    selection logic)."""

    def test_parses_pagedrec_line_and_attaches_note(self, monkeypatch):
        rec = {"residency": {"ratio": 0.41, "gate": 0.6, "pass": True},
               "paged": {"decode_tokens_per_s": 512.0}}
        monkeypatch.setattr(
            bench, "_SERVING_PAGED_CHILD",
            "import json\nprint('PAGEDREC ' + json.dumps(%r))" % (rec,))
        out = bench.bench_serving_paged(60)
        assert out["residency"]["pass"] is True
        assert out["paged"]["decode_tokens_per_s"] == 512.0
        assert "note" in out

    def test_child_failure_returns_error_record(self, monkeypatch):
        monkeypatch.setattr(
            bench, "_SERVING_PAGED_CHILD",
            "import sys; sys.stderr.write('pool exploded'); sys.exit(3)")
        out = bench.bench_serving_paged(60)
        assert "pool exploded" in out["error"]


class TestMaxpoolABSelection:
    def test_argmax_winning_flips_default(self, stub, monkeypatch):
        monkeypatch.setattr(bench, "bench_maxpool_backward",
                            lambda: {"argmax_bwd_ms": 1.0,
                                     "select_and_scatter_bwd_ms": 2.0,
                                     "speedup": 2.0})
        stub.table = {("standard", False): _rec(1000.0),
                      ("space_to_depth", False): _rec(900.0),
                      ("standard", True): _rec(800.0)}
        rec = bench.bench_resnet50()
        assert rec["maxpool_backward_ab"]["headline_uses"] == "argmax"

    def test_default_is_stock(self):
        from deeplearning4j_tpu.ops import pooling as _pooling
        import os
        if "DL4J_TPU_MAXPOOL_BWD" not in os.environ:
            assert _pooling._BACKWARD_IMPL == "stock"
