"""Weights from the seed, made on the device in one jitted call, in the
layout and the type the program serves them in.

The leaf order, the initialisers and their scales are the architecture's
``leaf_specs`` (``bench/archs/<model_type>.py``), which follow the
program's parameter tree; each leaf is drawn from ``fold_in(key, leaf
index)``.  The benchmark keeps its own copy so that the reference can make
the same weights again without importing the program; a driver checks that
the program's tree has exactly these shapes before it hands the weights
over.
"""
from __future__ import annotations

from bench import common


def _nest(flat):
    """{"a.b.0.c": x} -> nested dicts, with list levels for integer keys."""
    root: dict = {}
    for path, leaf in flat.items():
        node = root
        parts = path.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf

    def lists(n):
        if not isinstance(n, dict):
            return n
        if n and all(k.isdigit() for k in n):
            return [lists(n[k]) for k in sorted(n, key=int)]
        return {k: lists(v) for k, v in n.items()}
    return lists(root)


def make(config, seed, dtype=None):
    """The whole parameter tree on the device, from ``seed``, in the type
    the configuration states (``torch_dtype``) unless ``dtype`` is given."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype or config["torch_dtype"])
    specs = common.arch(config).leaf_specs(config)

    def build(key):
        out = {}
        for i, (path, shape, init, scale) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if init == "ones":
                out[path] = jnp.ones(shape, dt)
            else:
                out[path] = (jax.random.normal(k, shape, jnp.float32)
                             * scale).astype(dt)
        return _nest(out)

    return jax.jit(build)(jax.random.key(int(seed)))


def check_layout(config, abstract_tree):
    """Refuse a program whose parameter tree differs from the
    architecture's ``leaf_specs``."""
    import jax
    leaves = jax.tree.leaves(abstract_tree)
    want = [s[1] for s in common.arch(config).leaf_specs(config)]
    got = [tuple(x.shape) for x in leaves]
    if got != [tuple(w) for w in want]:
        raise ValueError(f"the program's parameter leaves {got} differ from "
                         f"the benchmark's layout {want}")
