"""The architecture modules, on the CPU: the dense ``granite`` block reads,
at the tiny size and a fixed seed, the numbers the harness read before the
block moved into ``bench/archs/granite.py`` (the weights' bytes, the
reference's losses and per-leaf norms, the served logits, each also as the
float8 control), and an unknown ``model_type`` is refused by name."""
from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

from bench import common, reference, weights
from bench.tests import helpers

SEED = 2**33 + 5

#: recorded on the CPU from the harness as it was before the move: a
#: sha256 over the bytes of the leaves or logits, and the exact floats
GOLDEN = {
    "weights": "38a88a8fd3fa7d267a31e4d7b93f6ce6f09075fd67c818bd0fae185894304c9f",
    "train": {
        "losses": [6.052390098571777, 5.871903419494629, 6.218258380889893],
        "grad_norms": [
            0.9701923727989197, 0.009544807486236095, 0.05608268082141876,
            0.07794208079576492, 0.10554468631744385, 0.07976788282394409,
            0.11441870033740997, 0.0693478137254715, 0.06995567679405212,
            0.09214111417531967, 0.021265186369419098, 0.01326235756278038],
        "change_norms": [
            0.09278759360313416, 0.0, 0.15750369429588318, 0.06936518847942352,
            0.09817908704280853, 0.097860187292099, 0.06810109317302704,
            0.1391492784023285, 0.1384410709142685, 0.1360175609588623, 0.0,
            0.0],
    },
    "train_control": {
        "losses": [6.036342144012451, 5.89946174621582, 6.237415313720703],
        "grad_norms": [
            0.9708095788955688, 0.009340710937976837, 0.05531869828701019,
            0.07888221740722656, 0.10362648218870163, 0.08016856759786606,
            0.11314356327056885, 0.06803785264492035, 0.06831882148981094,
            0.09101579338312149, 0.02130436711013317, 0.012550745159387589],
        "change_norms": [
            0.09281936287879944, 0.0, 0.15632176399230957, 0.06883340328931808,
            0.09834801405668259, 0.09734566509723663, 0.06822837144136429,
            0.13925661146640778, 0.1383768916130066, 0.13607445359230042, 0.0,
            0.0],
    },
    "serve": "e676d44001698f3a502591f9c2e9725c88f6d11125d1cbe675d46b950ce98f97",
    "serve_control":
        "bad5d7bfddf91ca1ba5ca90e28fabdeac7ece63b5d7ac78ac84e9822fb9dab84",
}


def _tiny():
    cfg = common.load_json(common.BENCH / "configs" / "granite-3-2b-d6.json")
    cfg.update(helpers.TINY)
    return cfg


def _digest(arrays, dtype=None):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a, dtype).tobytes())
    return h.hexdigest()


def _readings(case, cfg, params):
    rng = np.random.default_rng(11)
    batches = []
    for _ in range(3):
        t = rng.integers(0, cfg["vocab_size"], size=(2, 33), dtype=np.int32)
        batches.append({"tokens": t[:, :-1], "targets": t[:, 1:]})
    quant = case.endswith("_control")
    if case.startswith("train"):
        mix = common.load_json(common.BENCH / "workloads" / "train_ckpt.json")
        opt = {k: mix[k] for k in ("lr", "total_steps", "adam_b1", "adam_b2",
                                   "adam_eps", "weight_decay", "grad_clip")}
        r = reference.train_readings(cfg, opt, params, batches, quant)
        return {k: [float(v) for v in r[k]]
                for k in ("losses", "grad_norms", "change_norms")}
    seqs = [list(rng.integers(0, cfg["vocab_size"], size=n)) for n in (20, 29)]
    return _digest(common.arch(cfg).serve_logits(cfg, params, seqs, 32,
                                                 quant=quant), np.float32)


@pytest.mark.parametrize("case", sorted(GOLDEN))
def test_granite_reads_what_it_read_before_the_move(case):
    cfg = _tiny()
    params = weights.make(cfg, SEED)
    if case == "weights":
        got = _digest(np.asarray(x) for x in jax.tree.leaves(params))
    else:
        got = _readings(case, cfg, params)
    assert got == GOLDEN[case]


def test_an_unknown_model_type_is_refused_by_name():
    cfg = dict(_tiny(), model_type="no-such-arch")
    with pytest.raises(KeyError, match="'no-such-arch'.*known: .*'granite'"):
        common.arch(cfg)
    with pytest.raises(KeyError, match="no-such-arch"):
        weights.make(cfg, SEED)
    assert common.arch(_tiny()).SCOPES == ()
