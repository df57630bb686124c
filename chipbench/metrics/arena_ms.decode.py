"""Host time per decode step in the paged KV arena's own numpy work
(``kv/append``, ``kv/write_prompt``, ``kv/gather``): the program's
phases in the profiler trace (``chipbench/spans.py``)."""
from chipbench import spans

PHASES = ("kv/append", "kv/write_prompt", "kv/gather")


def read(r, trace):
    return spans.ms_per_step(trace, PHASES)
