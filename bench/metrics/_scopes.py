"""Shared by the readers of a train step's device time by scope: chip 0's
operations of the window's ``jit_train_step`` runs, each counted by its
own time and named by the innermost ``jax.named_scope`` in its HLO
``op_name`` (``span_report.scope_table``).

The trace names an operation by its HLO instruction alone, so the map from
instruction to ``op_name`` comes from the compiled step: after the window
the reader builds the cell's ``Trainer`` again and compiles its step, which
the persistent cache holds, and reads the compiled module's text.  A run
without a trace, or whose trace holds no train step, gives ``None``.
"""
from __future__ import annotations

import json

from bench import common, devtrace, span_report, training
from bench.metrics import _spans


def _op_names(run):
    import jax.numpy as jnp
    tr, _ = training.build(run, record=0)
    try:
        batch = tr._device_batch(tr.pipeline.next())
        text = tr.train_step.lower(tr.params, tr.opt_state, batch,
                                   jnp.int32(tr.step)).compile().as_text()
    finally:
        training.close(tr)
    return span_report.op_names(text)


def _table(run):
    path = _spans._trace_path(run)
    if path is None or not run.values.get("step_s"):
        return None
    _, bench = _spans.load(path)
    win = devtrace.window_of(bench)
    if win is None:
        return None
    _, ops = span_report.device_lines(path, win, "jit_train_step")
    if not ops:
        return None
    scopes = span_report.SCOPES + common.arch(run.config).SCOPES
    return span_report.scope_table(ops, _op_names(run), scopes)


def step_scope_s(run, *names):
    """Device seconds a window step spends under the scopes ``names``
    (summed), or ``None``; the table is built once per run."""
    if not hasattr(run, "_step_scopes"):
        run._step_scopes = _table(run)
    table = run._step_scopes
    if not table or not any(n in table for n in names):
        return None
    return sum(table.get(n, 0.0) for n in names) / len(run.values["step_s"])


def hbm_bytes_per_s(device_kind):
    """Peak HBM bytes/s of one chip (``peaks.json``)."""
    table = json.loads((common.BENCH / "peaks.json").read_text())["devices"]
    return table[device_kind]["hbm_bytes_per_s"]
