"""A configuration file, the program's model config it names, and the
seeded weights both the program and the reference are given.

The weights are made here, from the run's seed, in one jitted call on the
device and in the dtype they are served in. The program receives them as
its parameter tree; the reference reads the same arrays and nothing the
program made.
"""
from __future__ import annotations

import importlib
import json
import math
from pathlib import Path

HERE = Path(__file__).resolve().parent

# configuration-file key -> the program's ModelConfig attribute
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim_",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm",
    "attention_bias": "attn_bias",
}


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def reference(spec: dict):
    """The plain reference the file names: ``references/<reference>.py``."""
    return importlib.import_module(f"chipbench.references.{spec['reference']}")


def program_config(spec: dict):
    """The program's ModelConfig for ``spec['arch']``, refused unless it
    runs the sizes the file states and the model its reference computes."""
    from repro.configs import get_config
    cfg = get_config(spec["arch"])
    want = {a: spec[k] for k, a in PROGRAM_KEYS.items() if k in spec}
    want |= reference(spec).program_attrs(spec)
    bad = {a: (v, getattr(cfg, a)) for a, v in want.items()
           if getattr(cfg, a) != v}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"benchmark's file: {bad}")
    return cfg


def param_shapes(spec: dict) -> dict:
    return reference(spec).param_shapes(spec)


def _tree(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


def make_weights(spec: dict, seed: int):
    """The parameter tree from ``seed``, made on the default device in one
    jitted call: matrices truncated-normal with std 1/sqrt(fan-in)
    (embedding 0.02), norm scales 1 + 0.1 N(0, 1)."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(spec)

    def make(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (path, (shape, dt)) in zip(keys, sorted(shapes.items())):
            if path[-1] == "scale" or path[-1].endswith("_norm"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                std = 0.02 if path == ("embed",) else shape[-2] ** -0.5
                x = jax.random.truncated_normal(k, -3.0, 3.0, shape,
                                                jnp.float32) * std
            flat[path] = x.astype(dt)
        return _tree(flat)

    return jax.jit(make)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any whole number (the seed may exceed 32 bits)."""
    import jax
    import numpy as np
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def check_layout(spec: dict, cfg, params) -> None:
    """Refuse weights whose tree differs from the program's own init."""
    import jax
    from repro import models as M
    want = jax.eval_shape(lambda k: M.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got_s = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    want_s = jax.tree.map(lambda a: (a.shape, str(a.dtype)), want)
    if got_s != want_s:
        raise ValueError(f"weight tree differs from the program's: "
                         f"{got_s} != {want_s}")


def n_params(spec: dict) -> int:
    return sum(math.prod(s) for s, _ in param_shapes(spec).values())
