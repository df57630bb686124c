"""Norm parameters in the weight tree: a non-parametric LayerNorm (OLMo)
holds no node at all, a parametric norm keeps its scale (and bias), and
every reader takes a norm through ``layers.norm_params``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models as M
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import layers as nn

KEY = jax.random.PRNGKey(0)


def _shapes(cfg) -> dict:
    tree = jax.eval_shape(lambda k: M.init_params(k, cfg), KEY)
    return {jax.tree_util.keystr(p): (a.shape, str(a.dtype))
            for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _empty_nodes(tree, path=()) -> list:
    if not isinstance(tree, dict):
        return []
    if not tree:
        return [path]
    return [e for k, v in tree.items() for e in _empty_nodes(v, path + (k,))]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_no_empty_nodes_in_any_tree(arch):
    cfg = get_smoke_config(arch)
    tree = jax.eval_shape(lambda k: M.init_params(k, cfg), KEY)
    assert _empty_nodes(tree) == []


def test_nonparametric_norms_hold_no_node():
    cfg = get_config("olmo-1b")
    tree = jax.eval_shape(lambda k: M.init_params(k, cfg), KEY)
    assert set(tree) == {"embed", "blocks"}
    assert set(tree["blocks"]) == {"attn", "mlp"}
    assert nn.init_norm(cfg, "ln1", "ln2") == {}


def test_qwen3_tree_unchanged():
    """The published qwen3-1.7b tree, leaf by leaf."""
    L, d, hd, f, V = 28, 2048, 128, 6144, 151_936
    bf, f32 = "bfloat16", "float32"
    want = {
        "['blocks']['attn']['k_norm']": ((L, hd), f32),
        "['blocks']['attn']['q_norm']": ((L, hd), f32),
        "['blocks']['attn']['wk']": ((L, d, 8 * hd), bf),
        "['blocks']['attn']['wo']": ((L, 16 * hd, d), bf),
        "['blocks']['attn']['wq']": ((L, d, 16 * hd), bf),
        "['blocks']['attn']['wv']": ((L, d, 8 * hd), bf),
        "['blocks']['ln1']['scale']": ((L, d), f32),
        "['blocks']['ln2']['scale']": ((L, d), f32),
        "['blocks']['mlp']['w_down']": ((L, f, d), bf),
        "['blocks']['mlp']['w_gate']": ((L, d, f), bf),
        "['blocks']['mlp']['w_up']": ((L, d, f), bf),
        "['embed']": ((V, d), bf),
        "['final_norm']['scale']": ((d,), f32),
    }
    assert _shapes(get_config("qwen3-1.7b")) == want


def test_parametric_layernorm_keeps_scale_and_bias():
    cfg = get_smoke_config("whisper-base")
    norms = nn.init_norm(cfg, "ln1", "ln2")
    assert set(norms) == {"ln1", "ln2"}
    assert all(set(p) == {"scale", "bias"} for p in norms.values())
    shapes = _shapes(cfg)
    assert "['final_norm']['bias']" in shapes
    assert "['enc_norm']['scale']" in shapes


def test_absent_norm_is_plain_layernorm():
    cfg = get_smoke_config("olmo-1b")
    x = jax.random.normal(KEY, (3, cfg.d_model), jnp.float32)
    got = nn.apply_norm(nn.norm_params({}, "ln1"), cfg, x)
    xf = np.asarray(x, np.float64)
    mu = xf.mean(-1, keepdims=True)
    want = (xf - mu) / np.sqrt(((xf - mu) ** 2).mean(-1, keepdims=True)
                               + 1e-5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)

