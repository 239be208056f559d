"""The hybrid block (granite-4.0-h-micro): a layer pattern of Mamba-2 and
NoPE attention layers, Granite's multipliers and tied embeddings.  Its
segment plan, its parameter count, the checkpoint round trip across MPI
families, and that the blocks already there read what they read before
(Hymba's SSD heads, the dense granite preset)."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import steps as ST
from repro.configs import get_config, smoke_config
from repro.models import Model
from repro.models import ssm as S
from repro.models.params import init_params
from repro.models.transformer import Segment, plan_segments
from repro.sharding import ShardingCtx, rules_for

HYBRID = "granite-4.0-h-micro"


def _digest(*trees):
    h = hashlib.sha256()
    for t in trees:
        for x in jax.tree.leaves(t):
            h.update(np.ascontiguousarray(np.asarray(x, np.float32)).tobytes())
    return h.hexdigest()


def test_plan_segments_follows_the_layer_pattern():
    """Runs of Mamba-2 layers are scanned segments (merged across period
    boundaries), each attention layer a segment of its own."""
    m, a = (lambda n: Segment("mamba", n, True, None)), Segment("attn", 1, False, None)
    assert plan_segments(get_config(HYBRID)) == [m(5), a, m(9), a, m(9), a,
                                                 m(9), a, m(4)]
    cfg = dataclasses.replace(get_config(HYBRID), n_layers=10,
                              layer_types=get_config(HYBRID).layer_types[:10])
    assert plan_segments(cfg) == [m(5), a, m(4)]
    with pytest.raises(ValueError, match="unknown layer type"):
        plan_segments(dataclasses.replace(cfg, layer_types=("mamba",) * 9
                                          + ("slstm",)))


def test_preset_is_the_published_model():
    cfg = get_config(HYBRID)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size, cfg.resolved_head_dim) == (40, 2048, 32, 8, 8192,
                                                       100352, 64)
    s = cfg.ssm
    assert (s.n_ssm_heads, s.head_dim, s.d_state, s.n_groups, s.d_conv,
            s.chunk, s.conv_bias) == (64, 64, 128, 1, 4, 256, True)
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] \
        == [5, 15, 25, 35]
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (12, 1 / 64, 0.22, 8)
    assert cfg.position_embedding == "nope" and cfg.tie_embeddings
    assert 3.0e9 < cfg.param_count() < 3.3e9          # "3B"


@pytest.mark.parametrize("arch", [HYBRID, "granite-3-2b", "hymba-1.5b"])
def test_param_count_counts_the_parameter_tree(arch):
    """The analytic count is the tree's size less the pre-norm weights (the
    count leaves those out): for the hybrid, the Mamba-2 leaves, the
    pattern and the tied head included."""
    cfg = smoke_config(arch)
    flat = jax.tree_util.tree_flatten_with_path(Model(cfg).abstract())[0]
    n = sum(int(np.prod(x.shape)) for p, x in flat
            if jax.tree_util.keystr(p).split("[")[-1].strip("]'\"")
            not in ("ln1", "ln2", "final_norm"))
    if arch == "hymba-1.5b":     # the count leaves Hymba's small SSD leaves out
        assert abs(cfg.param_count() - n) < 0.01 * n
    else:
        assert cfg.param_count() == n


def test_smoke_config_keeps_the_hybrid_mechanisms():
    cfg = smoke_config(HYBRID)
    assert cfg.layer_types == ("mamba", "attention", "mamba")
    assert cfg.n_layers == 3 and cfg.tie_embeddings
    assert cfg.ssm.conv_bias and cfg.ssm.n_groups == 1
    assert cfg.position_embedding == "nope" and cfg.logits_scaling == 8
    assert "head" not in Model(cfg).specs()


#: recorded on the CPU before the Mamba-2 mixer and the multipliers were
#: added: Hymba's SSD heads (train forward; prefill then one decode step)
#: and the dense granite smoke model's logits and gradients
HYMBA_FWD = "7f06c933b62d1f9f3d93b8f005f89f0dd575b78bd82498ff1428a67b5a683789"
HYMBA_CACHE = "6ebf408d6b867c55c0298dd2849b76fd1f6216821572f67833f9f5804eeb5170"
GRANITE = ("118bd82c2575b86e285d5cf508d222cdf36797779b6879f56cb49071039d7b5f",
           "7d97ca61c2173bb75adf2c8eb18b30fcb741b25d6d4307275023e001bf3a89e5")


def test_hymba_ssd_heads_read_what_they_read_before():
    cfg = smoke_config("hymba-1.5b")
    ctx = ShardingCtx(None, rules_for(cfg, "train"))
    p = init_params(S.ssd_specs(cfg), jax.random.key(3), jnp.float32)
    x = jax.random.normal(jax.random.key(4), (2, 32, cfg.d_model))
    y, _ = S.ssd_apply(ctx, cfg, p, x, mode="train")
    assert _digest(y) == HYMBA_FWD
    yp, c = S.ssd_apply(ctx, cfg, p, x, mode="prefill")
    yd, c2 = S.ssd_apply(ctx, cfg, p, x[:, 0], mode="decode", cache=c)
    assert _digest(yp, c, yd, c2) == HYMBA_CACHE


def test_granite_preset_reads_what_it_read_before():
    cfg = smoke_config("granite-3-2b")
    ctx = ShardingCtx(None, rules_for(cfg, "train"))
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, -1)}
    lg, _ = m.train_logits(ctx, params, batch)
    gr = jax.grad(lambda p: ST.lm_loss(cfg, m.train_logits(ctx, p, batch)[0],
                                       batch["targets"]))(params)
    assert (_digest(lg), _digest(gr)) == GRANITE


def test_chunked_scan_gradient_is_finite_over_long_strongly_decaying_chunks():
    """Above the diagonal a chunk's decay differences reach its whole
    decay (here 128): the exp is masked before it is taken, so the
    gradient through the log decays stays finite."""
    ks = jax.random.split(jax.random.key(0), 3)
    q, k = (jax.random.normal(kk, (1, 64, 2, 4)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, 64, 2, 4))
    lg = jnp.full((1, 64, 2), -2.0)
    g = jax.grad(lambda lg: jnp.sum(S.chunked_gla(q, k, v, lg, chunk=64)[0]))(lg)
    assert np.isfinite(np.asarray(g)).all()


def test_mamba2_decode_continues_the_prefill():
    """A prefill's state and conv inputs, then one decode step, give the
    output of the whole sequence's last position."""
    cfg = dataclasses.replace(smoke_config(HYBRID),
                              ssm=dataclasses.replace(smoke_config(HYBRID).ssm,
                                                      n_groups=2))
    ctx = ShardingCtx(None, rules_for(cfg, "decode"))
    p = init_params(S.mamba2_specs(cfg), jax.random.key(5), jnp.float32)
    x = jax.random.normal(jax.random.key(6), (2, 17, cfg.d_model))
    full, _ = S.mamba2_apply(ctx, cfg, p, x, mode="train")
    _, cache = S.mamba2_apply(ctx, cfg, p, x[:, :16], mode="prefill")
    last, _ = S.mamba2_apply(ctx, cfg, p, x[:, 16], mode="decode", cache=cache)
    np.testing.assert_allclose(last, full[:, 16], rtol=1e-4, atol=1e-5)


def _trainer(ckpt_dir, backend):
    from repro.configs import CkptIOConfig
    from repro.launch.train import Trainer
    cfg = dataclasses.replace(smoke_config(HYBRID), d_model=64, n_heads=4,
                              n_kv_heads=2, head_dim=16, d_ff=128,
                              vocab_size=256, param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    return Trainer(cfg, batch_size=2, seq_len=16, world_size=2,
                   backend=backend, ckpt_dir=ckpt_dir, total_steps=32,
                   ckpt_io=CkptIOConfig(codec="none", keep=0))


def test_hybrid_state_saved_under_craympi_resumes_under_openmpi(tmp_path):
    """The hybrid's parameters and AdamW state, saved under craympi and
    restored under openmpi by a fresh trainer, are byte-identical, and the
    next step's loss is the uninterrupted run's."""
    tr = _trainer(tmp_path, "craympi")
    tr.init_state()
    try:
        for _ in range(2):
            tr.step_once()
        tr.checkpoint().wait()
        want = [np.asarray(x).tobytes()
                for x in jax.tree.leaves((tr.params, tr.opt_state))]
        nxt = float(tr.step_once()["loss"])
    finally:
        tr.pipeline.stop()
        tr.cluster.writer.close()
    tr2 = _trainer(tmp_path, "craympi")
    tr2.init_state()
    try:
        assert tr2.resume_latest(new_backend="openmpi") is not None
        assert tr2.cluster.backend_name == "openmpi" and tr2.step == 2
        got = [np.asarray(x).tobytes()
               for x in jax.tree.leaves((tr2.params, tr2.opt_state))]
        assert got == want
        assert float(tr2.step_once()["loss"]) == nxt
    finally:
        tr2.pipeline.stop()
        tr2.cluster.writer.close()
