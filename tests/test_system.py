"""End-to-end system behaviour: training convergence, transparent checkpoint/
restart determinism, failure injection + cross-backend failover, serving
snapshots, and the 8-device elastic scenario (subprocess)."""
import os
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.launch.train import Trainer

TINY = replace(smoke_config("granite-3-2b"), n_layers=2, d_model=64,
               n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
               vocab_size=256, vocab_pad_multiple=64)


def make_trainer(tmp, **kw):
    kw.setdefault("batch_size", 4)
    kw.setdefault("seq_len", 16)
    kw.setdefault("world_size", 2)
    kw.setdefault("ckpt_dir", tmp)
    kw.setdefault("total_steps", 100)
    return Trainer(TINY, mesh=None, **kw)


def test_training_loss_decreases(tmp_path):
    tr = make_trainer(tmp_path / "ck")
    tr.init_state()
    tr.run(60, log_every=10)
    tr.pipeline.stop()
    assert tr.history[-1]["loss"] < tr.history[0]["loss"] - 0.3


def test_checkpoint_restart_is_deterministic(tmp_path):
    """Train 30; separately train 20, ckpt, restore, train 10 — identical."""
    a = make_trainer(tmp_path / "a", backend="mpich")
    a.init_state()
    a.run(30, log_every=30)
    a.pipeline.stop()

    b = make_trainer(tmp_path / "b", backend="mpich")
    b.init_state()
    b.run(20, log_every=20)
    b.checkpoint().wait()
    b.pipeline.stop()
    c = make_trainer(tmp_path / "b", backend="mpich")
    c._build_step()
    c.restore(b.cluster.writer.latest())
    assert c.step == 20
    c.run(10, log_every=10)
    c.pipeline.stop()
    assert c.history[-1]["loss"] == pytest.approx(a.history[-1]["loss"],
                                                  rel=1e-6)


def test_failure_injection_and_cross_backend_failover(tmp_path):
    tr = make_trainer(tmp_path / "ck", backend="craympi")
    tr.init_state()
    tr.run(30, ckpt_every=10, kill_rank_at=25,
           new_backend_on_restart="exampi", log_every=10)
    tr.pipeline.stop()
    assert tr.cluster.backend_name == "exampi"
    assert tr.cluster.restart_count == 1
    kinds = [e[0] for e in tr.cluster.events]
    assert "restarted" in kinds
    # made it back to (at least) the target step
    assert tr.step == 30


def test_failure_detection_by_heartbeat(tmp_path):
    tr = make_trainer(tmp_path / "ck")
    tr.init_state()
    tr.cluster.ranks[1].last_heartbeat -= 100.0
    dead = tr.cluster.detect_failures(timeout_s=5.0)
    assert dead == [1]
    assert not tr.cluster.ranks[1].alive
    tr.pipeline.stop()


def test_serving_snapshot_roundtrip(tmp_path):
    from repro.serving.engine import Server
    cfg = TINY
    srv = Server(cfg, ckpt_dir=tmp_path / "sck")
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8),
                                                dtype=np.int32)
    logits = srv.prefill(prompts, pad_to=16)
    first = np.argmax(np.asarray(logits)[..., :cfg.vocab_size], -1).astype(np.int32)
    a_toks, _ = srv.decode(3, first)
    srv.checkpoint(tag=1).wait()
    b_toks, _ = srv.decode(2, a_toks[-1])

    # a second server restores mid-generation and must produce the same tokens
    srv2 = Server(cfg, ckpt_dir=tmp_path / "sck")
    srv2.prefill(prompts, pad_to=16)  # builds cache structure
    srv2.restore(srv.cluster.writer.latest())
    assert srv2.pos == srv.pos - 2
    c_toks, _ = srv2.decode(2, a_toks[-1])
    np.testing.assert_array_equal(b_toks[0], c_toks[0])
    np.testing.assert_array_equal(b_toks[1], c_toks[1])


@pytest.mark.slow
def test_elastic_scenario_8_devices():
    """Full elastic restart on an 8-device fleet (separate process so the
    placeholder device count never leaks into this test session)."""
    script = Path(__file__).parent / "scenarios" / "elastic_scenario.py"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).parents[1] / "src")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=560)
    assert "ELASTIC_SCENARIO_OK" in out.stdout, out.stdout + out.stderr


def test_chaos_matrix_quick():
    """The chaos harness itself (sweep driver, injector wiring, byte-
    identical assertion) on two cells; the full fault-type sweep runs as
    the CI `chaos` job (`chaos_matrix.py --smoke`)."""
    script = Path(__file__).parent / "scenarios" / "chaos_matrix.py"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).parents[1] / "src")
    out = subprocess.run([sys.executable, str(script), "--quick"], env=env,
                         capture_output=True, text=True, timeout=560)
    assert "CHAOS_MATRIX_OK" in out.stdout, out.stdout + out.stderr


def test_serve_restore_rewinds_generated_stream(tmp_path):
    """Rewinding pos at restore must also truncate Server.generated — the
    tokens decoded between snapshot and failure would otherwise appear
    twice after the supervisor replays them."""
    from repro.serving.engine import Server
    cfg = smoke_config("granite-3-2b")
    srv = Server(cfg, ckpt_dir=tmp_path / "g")
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, cfg.vocab_size, (2, 8), dtype=np.int32)
    logits = srv.prefill(prompts, pad_to=16)
    first = np.argmax(np.asarray(logits)[..., : cfg.vocab_size],
                      axis=-1).astype(np.int32)
    toks, _ = srv.decode(3, first)
    srv.checkpoint().wait()
    srv.decode(2, toks[-1])                 # progress that will be lost
    assert len(srv.generated) == 5
    srv.restore(srv.cluster.writer.latest(), rebuild=True)
    assert srv.pos == 8 + 3
    assert len(srv.generated) == 3          # replayed tokens not duplicated
    srv.decode(2, srv.resume_tok)
    assert len(srv.generated) == 5


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """The environment's cache directory wins and nothing else is set;
    without it the cache goes to the fixed <repo>/.jax_cache."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = Path(__file__).resolve().parents[1] / ".jax_cache"
    else:
        want = tmp_path / env_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(want))
    try:
        assert compile_cache.enable_compile_cache() == str(want)
        set_dir = jax.config.jax_compilation_cache_dir
        assert set_dir == (before if env_dir else str(want))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_host_mesh_over_given_devices():
    """make_host_mesh(devices=...) spans exactly the devices it is given
    (the target of an elastic restart onto fewer chips)."""
    from repro.launch.mesh import make_host_mesh
    devs = jax.devices()[:1]
    mesh = make_host_mesh(devices=devs)
    assert list(mesh.devices.flat) == devs
    assert dict(mesh.shape) == {"data": 1, "model": 1}
