"""Host time per decode step in the server's own decode scheduling
(``server/decode_tick``, ``server/decode_admit``: batcher take, event
bookkeeping, shed checks; hops and pool work excluded): the program's
phases in the profiler trace (``chipbench/spans.py``)."""
from chipbench import spans

PHASES = ("server/decode_tick", "server/decode_admit")


def read(r, trace):
    return spans.ms_per_step(trace, PHASES)
