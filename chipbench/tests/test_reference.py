"""The plain reference computes the program's model: at small sizes on
the CPU, in float32, its logits equal the program's forward; the fp8
control does not."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import model
from chipbench.references import dense
from chipbench.tests import small


def test_reference_matches_program_forward():
    """At the program's own norm epsilon (1e-5; the benchmark's reference
    keeps the published 1e-6, see the configuration's ``assumed``) the two
    agree to float32 rounding."""
    from repro import models as M
    from repro.kernels import ops
    spec, cfg = small.config()
    spec = {**spec, "torch_dtype": "float32", "rms_norm_eps": 1e-5}
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = model.make_weights(spec, 2**33 + 5)
    model.check_layout(spec, cfg, params)
    toks = np.random.default_rng(0).integers(0, spec["vocab_size"], 24)
    with ops.use_impl("naive"), jax.default_matmul_precision("highest"):
        want = np.asarray(M.forward(params, cfg, jnp.asarray(toks)[None])[0][0])
    got = np.asarray(jax.jit(lambda w, t: dense.logits(w, spec, t))(
        params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    ctrl = np.asarray(jax.jit(lambda w, t: dense.logits(w, spec, t, "fp8"))(
        params, jnp.asarray(toks)))
    rel = np.linalg.norm(ctrl - want) / np.linalg.norm(want)
    assert rel > 1e-3


def test_weights_are_a_function_of_the_seed():
    spec, _ = small.config()
    a = model.make_weights(spec, 3_000_000_001)
    b = model.make_weights(spec, 3_000_000_001)
    c = model.make_weights(spec, 3_000_000_002)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["embed"] == c["embed"]).all())
    assert a["blocks"]["attn"]["wq"].dtype == jnp.bfloat16
