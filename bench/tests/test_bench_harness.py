"""The harness's own pieces, on the CPU: finding a cell's files and its
architecture by name, the trace reduction, the model-FLOP count, the peaks table, the seeds, the
weights, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import shutil

import numpy as np
import pytest

from bench import common, devtrace, flops, weights
from bench import run as bench_run
from bench.tests import helpers


def test_every_cell_finds_its_files_by_name():
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    for cell in spec["workloads"]:
        _, config, mix, limits = common.find_cell(cell["name"], spec)
        assert config["num_hidden_layers"] >= 1
        assert (common.BENCH / "drivers" / f"{mix['driver']}.py").exists()
        assert limits, f"{cell['name']} has no limits file"
    for m in spec["per_layer"]:
        assert hasattr(bench_run.load_module("metrics", m["name"]), "read")


def test_a_new_traffic_file_alone_defines_a_cell_that_runs(tmp_path):
    """A cell whose traffic file is new (and whose config file is new)
    runs through the unchanged harness: nothing existing is edited."""
    res = helpers.run_tiny(tmp_path, "train_ckpt", cell_traffic="train_new",
                           mix={"save_every": 3})
    assert res["correct"], res["checks"]
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert "tiny.train_new" not in json.dumps(
        common.load_json(common.ROOT / "BENCHMARK.json"))


def test_a_new_architecture_file_alone_defines_a_cell_that_runs(
        tmp_path, monkeypatch):
    """A cell whose configuration names an architecture that only a new
    file in ``bench/archs/`` defines (here the dense block with biases on
    its query, key and value projections) runs through the unchanged
    harness and drivers, and its reference holds the program to it."""
    archs = tmp_path / "bench" / "archs"
    archs.mkdir(parents=True)
    for f in common.ARCHS.glob("*.py"):
        shutil.copy(f, archs)
    shutil.copy(common.BENCH / "tests" / "archs" / "dense_qkv_bias.py", archs)
    monkeypatch.setattr(common, "ARCHS", archs)
    # the cell's own change_gap limit, set as the cells' are, from CPU
    # readings over ten seeds: the program 1.2e-3 to 1.1e-2 (its worst leaf
    # is the key's bias, whose gradient largely cancels over positions in
    # bfloat16), half the batch 0.22-0.26; the float8 control reads 3.9e-3
    # to 5.9e-3 here and fails grad_gap (1.5e-2 to 2.1e-2) and loss_gap
    res = helpers.run_tiny(tmp_path, "train_ckpt", limits={"change_gap": 0.04},
                           config={"model_type": "dense_qkv_bias",
                                   "attention_bias": True})
    assert res["correct"], res["checks"]
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    spec = json.dumps(common.load_json(common.ROOT / "BENCHMARK.json"))
    assert "dense_qkv_bias" not in spec
    assert not (common.BENCH / "archs" / "dense_qkv_bias.py").exists()


def test_unknown_cell_and_unknown_device_are_refused():
    with pytest.raises(KeyError):
        common.find_cell("no-such.cell")
    with pytest.raises(KeyError):
        flops.peak_flops("TPU v99 imaginary")
    assert flops.peak_flops("TPU v5 lite") == 197e12


def test_run_refuses_a_machine_without_tpu(capsys):
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", spec["workloads"][0]["name"],
                        "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


def test_trace_reduction_on_a_recorded_trace():
    ms = 1_000_000
    chip0 = [("fusion.1", 0, 10 * ms), ("fusion.2", 5 * ms, 10 * ms),
             ("copy", 40 * ms, 20 * ms), ("fusion.1", 90 * ms, 20 * ms)]
    chip1 = [("fusion.1", 0, 50 * ms)]
    host = [("window", 0, 100 * ms), ("step", 0, 20 * ms),
            ("ckpt", 20 * ms, 40 * ms), ("step", 60 * ms, 40 * ms)]
    s = devtrace.reduce([chip0, chip1], host, (0, 100 * ms))
    # chip 0 busy [0,15) [40,60) [90,100) = 45 ms; chip 1 busy 50 ms
    assert s["busy_s"] == pytest.approx(0.0475)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["idle_share"] == pytest.approx(0.525)
    # gaps of chip 0, named by the span open at their middle
    assert s["idle_gaps"] == [["step", pytest.approx(0.03)],
                              ["ckpt", pytest.approx(0.025)]]
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(0.07)]  # clipped
    assert devtrace.window_of(host) == (0, 100 * ms)
    assert devtrace.reduce([[]], host, (0, 100 * ms)) is None


def test_model_flops_match_a_hand_count_for_d6():
    cfg = common.load_json(common.BENCH / "configs" / "granite-3-2b-d6.json")
    arch = common.arch(cfg)
    d, f, v = 2048, 8192, 49155
    per_layer = d * 2048 + 2 * d * 512 + 2048 * d + 3 * d * f
    assert arch.layer_matmul_params(cfg) == per_layer == 60_817_408
    matmul = 2 * 4 * 2048 * (6 * per_layer + d * v)
    attn = 4 * 2048 * (2048 * 2049 // 2) * 6 * 4
    assert arch.train_step_flops(cfg, 4, 2048) == 3 * (matmul + attn)
    assert arch.decode_flops(cfg, 99) == (2 * (6 * per_layer + d * v)
                                          + 4 * 2048 * 100 * 6)


def test_seeds_of_any_size_differ():
    a, b = common.derived_seeds(2**40 + 1), common.derived_seeds(2**40 + 2)
    assert a != b and a == common.derived_seeds(2**40 + 1)
    assert all(0 <= v < 2**30 for v in a.values())


def test_bench_weights_equal_the_programs_own_init():
    """The benchmark's one jitted call makes the program's own weights:
    equal bits, except that XLA may fold the embedding's scale into the
    sampler and round a rare element one bf16 ulp the other way."""
    import jax

    from repro.models import Model
    cfg = common.load_json(common.BENCH / "configs" / "granite-3-2b-d6.json")
    cfg.update(helpers.TINY)
    model = Model(common.program_config(cfg))
    weights.check_layout(cfg, model.abstract())
    a, b = model.init(jax.random.key(77)), weights.make(cfg, 77)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x).view(np.uint16).astype(np.int32)
        y = np.asarray(y).view(np.uint16).astype(np.int32)
        assert np.abs(x - y).max() <= 1 and np.mean(x != y) < 1e-3
