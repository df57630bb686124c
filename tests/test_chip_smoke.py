"""CPU rehearsal of ``chip_smoke.py``: its phases at a reduced size with the
Pallas kernels interpreted, its refusal to run without a TPU, and the
one-process-per-chip rule of socket mode."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def setup():
    from repro.serving.smoke import smoke_setup
    return smoke_setup(n_layers=3, seq_len=8)


def test_fragment_phase_interpreted(chip_smoke, setup):
    """Depth-2 chains through align pools [p, 2) and the shared pool
    [2, 3), every result against the float32 forward."""
    cfg, book, params = setup
    with ops.use_impl("pallas_interpret"):
        out = chip_smoke.run_fragment_phase(
            cfg, book, params, points=(0, 1, 2), shared_at=2, batch=2,
            prompt_len=8)
    assert out["fallbacks"] == dict.fromkeys(out["fallbacks"], 0)
    assert out["report"]["served"] == len(out["served"]) == 6
    assert out["pools"] == {"0-2": 1, "1-2": 1, "2-3": 3}
    check = chip_smoke.check_fragments(cfg, params, out["served"])
    # a float32 model served in float32: far inside the bf16 bound
    assert check["ok"] and check["max_rel_err"] < 1e-4, check


def test_decode_phase_interpreted(chip_smoke, setup):
    """Continuous batching at B=2 with prefix reuse; every token is the
    float32 argmax."""
    cfg, book, params = setup
    with ops.use_impl("pallas_interpret"):
        out = chip_smoke.run_decode_phase(
            cfg, book, params, batch=2, decode_ctx=32, kv_block_tokens=4,
            n_streams=4, max_new=4, prompt_len=8)
    assert out["fallbacks"] == dict.fromkeys(out["fallbacks"], 0)
    assert out["report"]["decode_served"] == 4
    assert out["kv"]["prefix_hits"] >= 1
    check = chip_smoke.check_decode(cfg, params, out["served"])
    assert check["ok"] and check["exact"] == check["n_tokens"] == 16, check


def test_main_refuses_cpu(chip_smoke, capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err


def test_remote_executor_refuses_accelerator_parent(setup, monkeypatch):
    """Socket-mode workers would need the chip the parent already holds."""
    from repro.serving.remote import RemoteExecutor
    from repro.serving.smoke import decode_plan, smoke_fragments
    cfg, book, params = setup
    plan = decode_plan(cfg, book, smoke_fragments(cfg, 1))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one process"):
        RemoteExecutor(plan, params, cfg)


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_placement(tmp_path, monkeypatch, env_dir):
    """The entry points' cache: JAX_COMPILATION_CACHE_DIR when set (and
    then nothing is configured), else ``<checkout>/.jax_cache``."""
    from repro.serving.smoke import configure_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = configure_compile_cache(tmp_path)
        if env_dir:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            assert got == str(tmp_path / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
