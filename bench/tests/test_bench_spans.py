"""The readers of the program's own spans: a traced run at a tiny size on
the CPU reports the span metrics of its cell, and ``_spans`` windows,
groups and names a hand-made trace as the metrics read it."""
from __future__ import annotations

import math

import pytest

from bench import run as bench_run
from bench.metrics import _spans
from bench.metrics._spans import Span
from bench.tests import helpers

SPAN_METRICS = {"train_ckpt": ["ckpt_d2h_gbps", "train_allreduce_ms",
                               "train_feed_ms"],
                "resume_xflavor": ["restore_read_ms",
                                   "restore_place_dispatch_ms"]}


@pytest.mark.parametrize("traffic", sorted(SPAN_METRICS))
def test_traced_run_reports_the_program_span_metrics(tmp_path, traffic,
                                                     monkeypatch):
    from bench import flops
    monkeypatch.setattr(flops, "peak_flops", lambda kind: 1e12)
    res = helpers.run_tiny(tmp_path, traffic, trace=1)
    assert res["correct"], res["checks"]
    for name in SPAN_METRICS[traffic]:
        v = res["metrics"][name]["value"]
        assert math.isfinite(v) and v > 0, (name, v)


MS = 1_000_000

#: a window [100, 200) ms with two steps of two ranks, one save of two
#: batches, two restores, and spans on either side of the window
HAND = [
    Span("train.feed", 90 * MS, 95 * MS, "main", {"step": 0}),
    Span("train.feed", 100 * MS, 102 * MS, "main", {"step": 1}),
    Span("mpi.allreduce", 110 * MS, 111 * MS, "r0", {"rank": 0, "step": 1}),
    Span("mpi.allreduce", 110 * MS, 113 * MS, "r1", {"rank": 1, "step": 1}),
    Span("train.feed", 120 * MS, 124 * MS, "main", {"step": 2}),
    Span("mpi.allreduce", 130 * MS, 135 * MS, "r0", {"rank": 0, "step": 2}),
    Span("mpi.allreduce", 130 * MS, 131 * MS, "r1", {"rank": 1, "step": 2}),
    Span("ckpt.blocking", 140 * MS, 150 * MS, "main", {"step": 3}),
    Span("ckpt.d2h", 141 * MS, 145 * MS, "main",
         {"step": 3, "batch": 0, "rank": 0, "bytes": 6 * MS}),
    Span("ckpt.d2h", 145 * MS, 147 * MS, "main",
         {"step": 3, "batch": 1, "rank": 0, "bytes": 3 * MS}),
    Span("restore.read", 160 * MS, 164 * MS, "io0", {"restore": "3@1"}),
    Span("restore.read", 161 * MS, 168 * MS, "io1", {"restore": "3@1"}),
    Span("restore.place", 168 * MS, 170 * MS, "main", {"restore": "3@1"}),
    Span("restore.read", 180 * MS, 182 * MS, "io0", {"restore": "3@2"}),
    Span("restore.place", 182 * MS, 186 * MS, "main", {"restore": "3@2"}),
    Span("restore.read", 195 * MS, 205 * MS, "io0", {"restore": "3@3"}),
]


def test_spans_window_group_and_feed_the_metrics():
    inside = _spans.in_window(HAND, (100 * MS, 200 * MS))
    assert len(inside) == len(HAND) - 2          # the two crossing edges go
    assert sorted(_spans.grouped(inside, "restore.read", "restore")) == [
        "3@1", "3@2"]
    assert sorted(_spans.grouped(inside, "mpi.allreduce", "step")) == [1, 2]

    def value(name):
        return bench_run.load_module("metrics", name).value(inside)

    assert value("ckpt_d2h_gbps") == pytest.approx(9 / 6)  # 9 MB in 6 ms
    assert value("train_allreduce_ms") == pytest.approx(4.0)   # (3 + 5) / 2
    assert value("train_feed_ms") == pytest.approx(3.0)        # (2 + 4) / 2
    assert value("restore_read_ms") == pytest.approx(5.0)      # (8 + 2) / 2
    assert value("restore_place_dispatch_ms") == pytest.approx(3.0)  # (2+4)/2
    assert bench_run.load_module("metrics", "ckpt_d2h_gbps").value([]) is None


def test_idle_gaps_are_named_by_the_innermost_program_span():
    device = [("fusion.1", 100 * MS, 10 * MS),      # busy [100, 110)
              ("fusion.2", 113 * MS, 17 * MS),      # busy [113, 130)
              ("fusion.3", 136 * MS, 4 * MS),       # busy [136, 140)
              ("fusion.4", 150 * MS, 40 * MS)]      # busy [150, 190)
    bench = [("window", 100 * MS, 100 * MS), ("step", 100 * MS, 40 * MS),
             ("ckpt", 140 * MS, 10 * MS), ("step", 190 * MS, 10 * MS)]
    program = [s for s in HAND if s.name in ("mpi.allreduce", "ckpt.blocking",
                                             "ckpt.d2h")]
    gaps, uncovered = _spans.name_gaps(device, program, bench,
                                       (100 * MS, 200 * MS))
    # each stretch of a gap goes to the innermost span over it
    assert gaps == [
        (110 * MS, 113 * MS, {"mpi.allreduce": 3 * MS}),   # r0's, then r1's
        (130 * MS, 136 * MS, {"mpi.allreduce": 5 * MS, "step": 1 * MS}),
        (140 * MS, 150 * MS, {"ckpt.blocking": 4 * MS, "ckpt.d2h": 6 * MS}),
        (190 * MS, 200 * MS, {"step": 10 * MS})]           # no program span
    # [135, 136) and [190, 200) lie under no program span
    assert uncovered == 11 * MS
    assert _spans.idle_totals(gaps) == {
        "step": 11 * MS, "mpi.allreduce": 8 * MS, "ckpt.d2h": 6 * MS,
        "ckpt.blocking": 4 * MS}


def test_span_report_counts_each_operation_by_its_own_time():
    from bench import span_report
    # a loop [0, 100) holding two fusions, then one fusion alone
    ops = [("%while.1", 0, 100), ("%fusion.2", 10, 30), ("%fusion.3", 50, 40),
           ("%fusion.4", 120, 10)]
    assert span_report.exclusive_ns(ops) == {
        "%while.1": 30, "%fusion.2": 30, "%fusion.3": 40, "%fusion.4": 10}
    names = span_report.op_names(
        '  %fusion.2 = f32[8] fusion(), metadata={op_name="jit(step)/'
        'transpose(jvp(attention))/dot_general"}\n'
        '  ROOT %fusion.3 = f32[8] fusion(), metadata={op_name="jit(step)/'
        'loss/head/dot_general"}\n  %while.1 = (f32[8]) while()')
    assert names == {"fusion.2": "jit(step)/transpose(jvp(attention))/"
                                 "dot_general",
                     "fusion.3": "jit(step)/loss/head/dot_general"}
    assert span_report.scope_table(ops, names, span_report.SCOPES) == {
        "other": 40e-9, "head": 40e-9, "attention": 30e-9}


def test_an_architectures_own_scope_is_counted_under_its_name():
    from bench import span_report
    op = "jit(train_step)/while/body/transpose(jvp(mamba))/mixer/dot_general"
    assert span_report.scope_of(op, span_report.SCOPES) == "other"
    scopes = span_report.SCOPES + ("mamba",)
    assert span_report.scope_of(op, scopes) == "mamba"
    assert span_report.scope_of("jit(step)/mamba/attention/x", scopes) == (
        "attention")
    ops, names = [("%fusion.1", 0, 70)], {"fusion.1": op}
    assert span_report.scope_table(ops, names, scopes) == {"mamba": 70e-9}


def test_span_report_times_the_first_step_after_each_restore():
    from bench import span_report
    program = [Span("train.restore", 0, 100 * MS, "main", {"restore": "3@1"}),
               Span("mpi.allreduce", 400 * MS, 401 * MS, "r0", {"step": 4})]
    ops = [("%copy", 50 * MS, 40 * MS), ("%fusion", 150 * MS, 200 * MS),
           ("%fusion", 360 * MS, 30 * MS)]
    assert span_report.after_restores(program, ops) == [
        {"restore": "3@1", "first_op_ms": 50.0, "loss_on_host_ms": 301.0,
         "busy_ms": 230.0}]


def test_span_report_reduces_a_tiny_traced_run(tmp_path, monkeypatch):
    from bench import flops, span_report
    monkeypatch.setattr(flops, "peak_flops", lambda kind: 1e12)
    report = {}
    with span_report.reporting(report, training_cell=True):
        res = helpers.run_tiny(tmp_path, "train_ckpt", trace=1)
    assert res["correct"], res["checks"]
    assert report["n_spans"] > 0
    d2h = report["spans"]["ckpt.d2h"]
    assert d2h["n"] > 0 and d2h["sum_ms"] > 0
    # the CPU trace has no TPU plane: no idle or scope table, no error
    assert "idle" not in report and "scopes" not in report
