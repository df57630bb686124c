"""Host time per decode step in the transport's framing
(``transport/pack``, ``transport/unpack``: msgpack only, no socket
wait): the program's phases in the profiler trace
(``chipbench/spans.py``)."""
from chipbench import spans

PHASES = ("transport/pack", "transport/unpack")


def read(r, trace):
    return spans.ms_per_step(trace, PHASES)
