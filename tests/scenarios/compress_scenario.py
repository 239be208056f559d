"""Subprocess scenario (8 devices, 3-axis mesh): int8+error-feedback gradient
reduction over the 'pod' axis matches exact f32 reduction to quantization
tolerance per step, and the error-feedback residual keeps the ACCUMULATED
reduction unbiased across steps."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.optim.compress import init_error_feedback, make_pod_grad_reducer


def main():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    sh = {"w": NamedSharding(mesh, P("data", "model")),
          "b": NamedSharding(mesh, P(None))}
    reduce_fn = make_pod_grad_reducer(mesh, sh, compress=True)
    exact_fn = make_pod_grad_reducer(mesh, sh, compress=False)

    rng = np.random.default_rng(0)
    ef = None
    acc_c = {"w": np.zeros((8, 8), np.float32), "b": np.zeros((4,), np.float32)}
    acc_e = {"w": np.zeros((8, 8), np.float32), "b": np.zeros((4,), np.float32)}
    for step in range(20):
        # per-pod distinct gradients: simulate by a value that varies along 'pod'
        base = {"w": rng.standard_normal((8, 8)).astype(np.float32),
                "b": rng.standard_normal((4,)).astype(np.float32)}
        grads = {k: jax.device_put(jnp.asarray(v), sh[k]) for k, v in base.items()}
        if ef is None:
            ef = jax.device_put(init_error_feedback(grads),
                                jax.tree.map(lambda s: s, sh))
        red_c, ef = reduce_fn(grads, ef)
        red_e, _ = exact_fn(grads, jax.tree.map(jnp.zeros_like, ef))
        for k in acc_c:
            acc_c[k] += np.asarray(red_c[k], np.float32)
            acc_e[k] += np.asarray(red_e[k], np.float32)
        step_err = max(float(jnp.max(jnp.abs(red_c[k] - red_e[k])) /
                             (jnp.max(jnp.abs(red_e[k])) + 1e-9)) for k in red_c)
        assert step_err < 0.05, f"step {step}: rel err {step_err}"
    # error feedback keeps the accumulated estimate tight (bias does not grow)
    for k in acc_c:
        rel = np.max(np.abs(acc_c[k] - acc_e[k])) / (np.max(np.abs(acc_e[k])) + 1e-9)
        assert rel < 0.02, f"accumulated bias {rel} on {k}"
    print("COMPRESS_SCENARIO_OK")


if __name__ == "__main__":
    main()
