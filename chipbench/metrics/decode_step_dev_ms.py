"""Device time of one run of the jitted batched decode-step program
(``jax.jit`` of a lambda in the executor, so its module is named after
``<lambda>``), averaged over its runs that started in the window."""
from chipbench import trace as tr

STEP_MODULE = r"^jit__lambda\("


def read(r, trace):
    runs, ns = tr.module_runs(trace, STEP_MODULE)
    return ns / runs / 1e6 if runs else None
