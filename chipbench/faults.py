"""Faults planted under the timed decode path, to show that the check
sees them: by the CPU tests at small sizes, and by ``limits.py --fault``
on the chip at a cell's own sizes. Nothing in a benchmark run plants one.

``token_altered``    the step's logits shifted by one vocabulary entry, so
                     the token it produces is not the one the model puts
                     first;
``state_unchanged``  the step returns the cache it was given: no position
                     advances and no key or value is kept;
``recent_dropped``   decode attention leaves out the ``RECENT`` positions
                     before the query's own (a window or cache-index bug).
"""
from __future__ import annotations

RECENT = 16                               # one KV block of the arena


def _token_altered(step):
    import jax.numpy as jnp

    def bad(params, cfg, cache, tokens):
        logits, cache = step(params, cfg, cache, tokens)
        return jnp.roll(logits, 1, axis=-1), cache
    return bad


def _state_unchanged(step):
    def bad(params, cfg, cache, tokens):
        logits, _ = step(params, cfg, cache, tokens)
        return logits, cache
    return bad


def _recent_dropped(attend):
    import jax.numpy as jnp

    def bad(q, k, v, q_pos, kv_pos, **kw):
        qp = q_pos[:, None]
        hide = (kv_pos >= qp - RECENT) & (kv_pos < qp)
        # a position past the query's own is masked as causal
        return attend(q, k, v, q_pos, jnp.where(hide, 1 << 30, kv_pos), **kw)
    return bad


# fault -> (module, attribute it wraps, wrapper)
FAULTS = {
    "token_altered": ("repro.serving.executor", "decode_step",
                      _token_altered),
    "state_unchanged": ("repro.serving.executor", "decode_step",
                        _state_unchanged),
    "recent_dropped": ("repro.kernels.ops", "attend_cache", _recent_dropped),
}


def plant(name: str):
    """Plant fault ``name`` in the program; returns the undo."""
    import importlib
    mod_name, attr, wrap = FAULTS[name]
    mod = importlib.import_module(mod_name)
    orig = getattr(mod, attr)
    setattr(mod, attr, wrap(orig))
    return lambda: setattr(mod, attr, orig)
