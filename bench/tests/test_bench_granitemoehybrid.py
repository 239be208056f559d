"""The ``granitemoehybrid`` architecture on the CPU at tiny widths: the
program (``repro``) against the plain reference of
``bench/archs/granitemoehybrid.py`` (the Mamba-2 mixer against the
sequential recurrence, NoPE attention, each multiplier, the whole model's
logits and gradients), the tensor-parallel share against the uncut layer,
and the cell's configuration through ``bench/run.py``'s path."""
from __future__ import annotations

import copy
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import calibrate, common, reference, span_report, weights
from bench import run as bench_run
from bench.metrics import _scopes
from bench.tests import helpers

CONFIG = common.BENCH / "configs" / "granite-4.0-h-micro-p1-tp2.json"
CELL = "granite-4.0-h-micro-p1-tp2.train_ckpt"

#: the cell's configuration at widths a CPU test can run: half of each
#: layer of a 4-layer pattern (2 x 64 channels of Mamba-2 heads, 4 of 8
#: query heads, 128 of 256 MLP columns), chunks of 8 steps
TINY = {"hidden_size": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_chunk_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "shared_intermediate_size": 256, "shared_intermediate_held": 128,
        "num_hidden_layers": 4,
        "layer_types": ["mamba", "mamba", "attention", "mamba"],
        "vocab_size": 300, "padded_vocab": 512}

#: the tiny cell's limits, set as the cell's are, from CPU readings over
#: ten seeds (the program in bfloat16 against the float32 reference): the
#: program reads loss 3.2e-6 to 1.1e-5, gradient 8.5e-3 to 4.6e-2 (its
#: worst leaves are per-head scalars and biases, whose gradients largely
#: cancel over positions in bfloat16), change 8.5e-3 to 2.2e-2; the float8
#: control reads loss 5.3e-5 to 1.9e-4 and gradient 0.12 to 0.44; half the
#: batch reads loss 5.9e-4 to 7.9e-4, gradient 0.54 to 0.90, change 0.081
#: to 0.12
TINY_LIMITS = {"loss_gap": 3e-5, "grad_gap": 0.08, "change_gap": 0.04}


def _config(**kw):
    cfg = common.load_json(CONFIG)
    cfg.update(TINY)
    cfg.update(kw)
    return cfg


def _program(cfg, **kw):
    """The program's model in float32 for the configuration ``cfg``."""
    from repro.models import Model
    from repro.sharding import ShardingCtx, rules_for
    pc = dataclasses.replace(common.program_config(cfg), param_dtype="float32",
                             compute_dtype="float32", **kw)
    return Model(pc), ShardingCtx(None, rules_for(pc, "train"))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def test_logits_and_gradients_match_the_reference():
    """The whole period, its multipliers and tied head included, in float32
    at the highest matmul precision: logits and every leaf's gradient."""
    from repro import steps as ST
    cfg = _config()
    model, ctx = _program(cfg)
    weights.check_layout(cfg, model.abstract())
    params = weights.make(cfg, 5, dtype="float32")
    toks = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg["vocab_size"])
    x, tgt = toks[:, :-1], toks[:, 1:]
    arch = common.arch(cfg)
    with jax.default_matmul_precision("highest"):
        lp = model.train_logits(ctx, params, {"tokens": x})[0]
        gp = jax.grad(lambda p: ST.lm_loss(model.cfg, model.train_logits(
            ctx, p, {"tokens": x})[0], tgt))(params)
    assert _rel(lp, arch.logits(cfg, params, x)) < 1e-5
    gr = jax.grad(lambda p: reference.lm_loss(cfg, arch.logits(cfg, p, x),
                                              tgt))(params)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert _rel(a, b) < 1e-4


@pytest.mark.parametrize("groups", [1, 2])
def test_mamba2_mixer_matches_the_sequential_recurrence(groups):
    """The program's chunked mixer (conv with bias, B/C per group, gate then
    norm over the group's width) against the reference's step-by-step
    recurrence, on a layer's weights."""
    from repro.models import ssm as S
    cfg = _config(mamba_n_groups=groups)
    model, ctx = _program(cfg)
    params = weights.make(cfg, 9, dtype="float32")
    arch = common.arch(cfg)
    p = jax.tree.map(lambda a: a[1], params["segments"][0]["mamba"])
    h = jax.random.normal(jax.random.key(2), (2, 32, cfg["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got, _ = S.mamba2_apply(ctx, model.cfg, p, h, mode="train")
    want = arch.mamba_out(cfg, p, arch.mamba_gated(cfg, p, h))
    assert _rel(got, want) < 1e-5


NEUTRAL = {"embedding_multiplier": 1.0, "attention_multiplier": 0.25,
           "residual_multiplier": 1.0, "logits_scaling": 1.0}


@pytest.mark.parametrize("name", sorted(NEUTRAL))
def test_nope_attention_and_each_multiplier(name):
    """With every multiplier neutral but one at its published value, the
    program and the reference agree, and differ from the all-neutral
    model: each multiplier is applied, once, where the reference applies
    it.  Attention has no positions (NoPE): rotary ones would move it."""
    published = common.load_json(CONFIG)[name]
    cfg = _config(**NEUTRAL)
    params = weights.make(cfg, 3, dtype="float32")
    toks = jax.random.randint(jax.random.key(4), (2, 16), 0, cfg["vocab_size"])
    arch = common.arch(cfg)

    def program(**kw):
        model, ctx = _program(_config(), **dict(NEUTRAL, **kw))
        with jax.default_matmul_precision("highest"):
            return model.train_logits(ctx, params, {"tokens": toks})[0]
    one = dict(cfg, **{name: published})
    assert _rel(program(**{name: published}), arch.logits(one, params, toks)) < 1e-5
    assert _rel(program(**{name: published}), program()) > 1e-3
    assert _rel(program(), arch.logits(cfg, params, toks)) < 1e-5
    assert _rel(program(position_embedding="rope"), program()) > 1e-3


def test_the_two_tensor_parallel_halves_sum_to_the_uncut_layer():
    """The share the cell holds is a Megatron half: the MLP's, attention's
    and Mamba-2 mixer's outputs of the two halves (B/C replicated, each
    half's gated norm given the whole width's variance) sum to the uncut
    layer's, and the vocabulary halves' logits are the uncut logits."""
    arch = common.arch(_config())
    full = _config(tensor_parallel=1, mamba_n_heads=8, num_attention_heads=8,
                   num_key_value_heads=4, shared_intermediate_held=256,
                   vocab_size=512)
    half = _config()
    z = {k: full[k] for k in ("mamba_d_head", "head_dim")}
    params = weights.make(full, 11, dtype="float32")
    m = arch.segment_weights(full, params, 0)
    m = jax.tree.map(lambda a: a[0], m)
    a = arch.segment_weights(full, params, 1)
    h = jax.random.normal(jax.random.key(6), (2, 32, full["hidden_size"]))
    di, hd = full["mamba_n_heads"] * z["mamba_d_head"], z["head_dim"]
    f, Hq, K = 256, 8, 4

    def cols(w, n, i):
        return w[..., i * n // 2:(i + 1) * n // 2]

    def rows(w, n, i):
        return w[i * n // 2:(i + 1) * n // 2]

    def halves(p, col, row, keep=()):
        return [{**{k: p[k] for k in keep},
                 **{k: cols(p[k], n, i) for k, n in col.items()},
                 **{k: rows(p[k], n, i) for k, n in row.items()}}
                for i in (0, 1)]

    np.testing.assert_allclose(
        sum(arch.mlp(q, h) for q in halves(m, {"wg": f, "wi": f}, {"wo2": f})),
        arch.mlp(m, h), rtol=1e-5, atol=1e-5)
    att = halves(a, {"wq": Hq * hd, "wk": K * hd, "wv": K * hd},
                 {"wo": Hq * hd})
    np.testing.assert_allclose(sum(arch.attention(half, q, h) for q in att),
                               arch.attention(full, a, h), rtol=1e-5, atol=1e-5)
    H = full["mamba_n_heads"]
    mix = halves(m, {"w_z": di, "w_x": di, "w_dt": H, "conv_x": di,
                     "conv_x_bias": di, "dt_bias": H, "a_log": H, "d_skip": H,
                     "norm": di}, {"wo": di},
                 keep=("w_bc", "conv_bc", "conv_bc_bias"))
    ys = [arch.mamba_gated(half, q, h) for q in mix]
    var = arch.group_variance(full, jnp.concatenate(ys, axis=-1))
    np.testing.assert_allclose(
        sum(arch.mamba_out(half, q, y, variance=var) for q, y in zip(mix, ys)),
        arch.mamba_out(full, m, arch.mamba_gated(full, m, h)),
        rtol=1e-4, atol=1e-5)
    emb = params["embed"]
    np.testing.assert_array_equal(
        jnp.concatenate([h @ rows(emb, 512, i).T for i in (0, 1)], -1),
        h @ emb.T)


def _tiny_spec(tmp):
    """The tiny cell in a temporary checkout, reporting the cell's own
    per-layer metrics too."""
    spec, name = helpers.tiny_root(tmp, "train_ckpt", config=_config(),
                                   limits=TINY_LIMITS)
    spec = copy.deepcopy(spec)
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []) and name not in metric["workloads"]:
            metric["workloads"].append(name)
    return spec, name


def test_the_cell_runs_through_the_harness(tmp_path):
    """The cell's configuration at tiny widths, with the train_ckpt traffic
    at a CPU size, through ``bench/run.py``'s path: correct under its
    limits."""
    spec, name = _tiny_spec(tmp_path)
    res = bench_run.execute(spec, name, seed=2**40 + 3, seconds=0.5, trace=0,
                            devices=jax.devices(), root=tmp_path)
    assert res["correct"], res["checks"]
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("metric", ["train_mamba_ms", "train_ssd_roofline"])
def test_the_mixer_readers_give_nothing_without_a_trace(tmp_path, metric):
    run = types.SimpleNamespace(trace=1, tmp=tmp_path, config=_config(),
                                values={"step_s": [0.5]}, mix={},
                                device={"kind": "TPU v5 lite"})
    assert bench_run.load_module("metrics", metric).read(run) is None


def test_the_control_and_half_the_batch_fail_the_limits(tmp_path):
    spec, name = _tiny_spec(tmp_path)
    _, config, mix, limits = common.find_cell(name, spec, tmp_path)
    r = bench_run.Run(name, config, mix, limits, seed=2**40 + 3, seconds=1,
                      trace=0, devices=jax.devices()[:1], chips=1)
    try:
        out = calibrate.train_readings(r, 3, True, True)
    finally:
        r.close()
    assert all(v <= TINY_LIMITS[k] for k, v in out["program"].items())
    for case in ("control", "half_batch"):
        assert any(v > TINY_LIMITS[k] for k, v in out[case].items()), case


def test_the_step_op_names_find_the_mixer_scopes(tmp_path):
    """The map from the compiled step's instructions to their scopes, which
    the mixer's device metrics read, holds the ``mamba`` and ``ssd``
    scopes of every pass (forward, recomputation, backward)."""
    spec, name = _tiny_spec(tmp_path)
    _, config, mix, limits = common.find_cell(name, spec, tmp_path)
    r = bench_run.Run(name, config, mix, limits, seed=7, seconds=1, trace=0,
                      devices=jax.devices()[:1], chips=1)
    try:
        names = _scopes._op_names(r)
    finally:
        r.close()
    scopes = span_report.SCOPES + common.arch(config).SCOPES
    found = {span_report.scope_of(v, scopes) for v in names.values()}
    assert {"mamba", "ssd", "attention", "mlp"} <= found
    assert any("transpose" in v and span_report.scope_of(v, scopes) == "ssd"
               for v in names.values())


def test_bench_weights_equal_the_programs_own_init():
    from repro.models import Model
    cfg = _config()
    model = Model(common.program_config(cfg))
    weights.check_layout(cfg, model.abstract())
    a, b = model.init(jax.random.key(77)), weights.make(cfg, 77)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x).view(np.uint16).astype(np.int32)
        y = np.asarray(y).view(np.uint16).astype(np.int32)
        assert np.abs(x - y).max() <= 1 and np.mean(x != y) < 1e-3


def test_the_configuration_maps_onto_the_preset():
    """At the cell's sizes: 478M parameters, the first period's pattern;
    a file whose settings the program does not run is refused."""
    cfg = common.load_json(CONFIG)
    pc = common.program_config(cfg)
    assert pc.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (pc.ssm.n_ssm_heads, pc.n_heads, pc.n_kv_heads, pc.d_ff,
            pc.padded_vocab) == (32, 16, 4, 4096, 50176)
    n = sum(int(np.prod(s[1])) for s in common.arch(cfg).leaf_specs(cfg))
    assert 478e6 < n < 479e6
    for bad in ({"embedding_multiplier": 1}, {"shared_intermediate_held": 8192},
                {"mamba_proj_bias": True}, {"position_embedding_type": "rope"}):
        with pytest.raises(ValueError):
            common.program_config(dict(cfg, **bad))


def test_model_flops_match_a_hand_count():
    cfg = common.load_json(CONFIG)
    arch = common.arch(cfg)
    d, di, f = 2048, 2048, 4096
    mamba = 2 * d * di + d * 256 + d * 32 + di * d + 3 * d * f
    attn = d * 1024 + 2 * d * 256 + 1024 * d + 3 * d * f
    matmul = 2 * 4 * 2048 * (9 * mamba + attn + d * 50176)
    causal = 4 * 1024 * (2048 * 2049 // 2) * 4
    ssd = 9 * 4 * 32 * 8 * (2 * 256 * 256 * (128 + 64) + 4 * 256 * 128 * 64)
    assert arch.train_step_flops(cfg, 4, 2048) == 3 * (matmul + causal + ssd)
    assert arch.ssd_flops(cfg, 4, 2048) == 4 * ssd
    io = 4 * 2048 * (4 * di + 4 * 32 + 2 * 256), 4 * 2048 * 4 * di
    assert arch.ssd_bytes(cfg, 4, 2048) == 9 * (4 * io[0] + 3 * io[1])


def test_serving_is_refused_by_name():
    cfg = _config()
    with pytest.raises(NotImplementedError, match="Mamba-2 state cache"):
        common.arch(cfg).serve_logits(cfg, None, [[1, 2]], 8)
    with pytest.raises(NotImplementedError, match="granitemoehybrid"):
        common.arch(cfg).decode_flops(cfg, 3)
