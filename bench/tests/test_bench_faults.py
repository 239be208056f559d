"""The checks that decide ``correct`` fail when the timed path is broken.

Each test drives a whole run at a tiny size on the CPU (the harness's look
for a chip is skipped), with one fault planted in the program underneath,
and sees ``correct`` come out false under the cell's own limits: a step
that returns its state unchanged, half of each batch left out, a saved or
restored byte altered where it is produced, a served token altered where
it is produced.  The control, the reference computed in float8 in the
program's place, fails one of the cell's numbers too.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import calibrate, common
from bench import run as bench_run
from bench.tests import helpers


def _wrap_step(monkeypatch, make):
    from repro.launch.train import Trainer
    build = Trainer._build_step

    def patched(self):
        build(self)
        self.train_step = make(self)
    monkeypatch.setattr(Trainer, "_build_step", patched)


def _unchanged(tr):
    from repro import steps as ST
    fn = ST.make_train_step(tr.model, tr.ctx, tr.optimizer)
    return jax.jit(lambda p, o, b, i: (p, o, fn(p, o, b, i)[2]))


def _half_batch(tr):
    calibrate.half_batch(tr)
    return tr.train_step


def _flip_saved_byte(monkeypatch):
    from repro.core import ckpt_io
    view = ckpt_io._byte_view

    def flipped(arr):
        v = view(arr).copy()
        v[-1] ^= 0x01
        return v
    monkeypatch.setattr(ckpt_io, "_byte_view", flipped)


def _flip_restored_leaf(monkeypatch):
    from repro.core import restore
    place = restore.place_leaf

    def flipped(arr, sharding):
        arr = np.array(arr)
        arr.reshape(-1).view(np.uint8)[0] ^= 0x01
        return place(arr, sharding)
    monkeypatch.setattr(restore, "place_leaf", flipped)


def _alter_served_token(monkeypatch):
    from repro import steps as ST
    make = ST.make_decode_step

    def altered(model, ctx):
        step = make(model, ctx)

        def decode(*a):
            logits, caches = step(*a)
            return jax.numpy.roll(logits, 1, axis=-1), caches
        return decode
    monkeypatch.setattr(ST, "make_decode_step", altered)


FAULTS = {
    "train_state_unchanged": ("train_ckpt", lambda mp: _wrap_step(mp, _unchanged),
                              "change_gap"),
    "train_half_batch": ("train_ckpt", lambda mp: _wrap_step(mp, _half_batch),
                         "grad_gap"),
    "train_saved_byte": ("train_ckpt", _flip_saved_byte, "save_mismatch"),
    "resume_state_unchanged": ("resume_xflavor",
                               lambda mp: _wrap_step(mp, _unchanged), "change_gap"),
    "resume_half_batch": ("resume_xflavor", lambda mp: _wrap_step(mp, _half_batch),
                          "grad_gap"),
    "resume_restored_byte": ("resume_xflavor", _flip_restored_leaf,
                             "state_mismatch"),
    "serve_token_altered": ("serve_closed", _alter_served_token, "served_gap"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    traffic, plant, number = FAULTS[fault]
    plant(monkeypatch)
    res = helpers.run_tiny(tmp_path, traffic)
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("traffic", ["train_ckpt", "resume_xflavor",
                                     "serve_closed"])
def test_the_float8_control_is_not_correct(tmp_path, traffic):
    spec, name = helpers.tiny_root(tmp_path, traffic)
    cell, config, mix, limits = common.find_cell(name, spec, tmp_path)
    r = bench_run.Run(name, config, mix, limits, seed=2**35 + 9, seconds=0.5,
                      trace=0, devices=jax.devices(), chips=1)
    try:
        if traffic == "serve_closed":
            ctl = calibrate.serve_readings(r, control=True)["control"]
        else:
            ctl = calibrate.train_readings(r, 3, control=True,
                                           fault=False)["control"]
    finally:
        r.close()
    assert any(v > limits[k] for k, v in ctl.items()), (ctl, limits)
