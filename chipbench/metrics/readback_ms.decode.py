"""Host time per decode step spent reading the dense cache back from the
device (``decode/readback``, at the cache's own dtype) and widening the
host copy to float32 (``decode/widen``): the program's phases in the
profiler trace (``chipbench/spans.py``)."""
from chipbench import spans

PHASES = ("decode/readback", "decode/widen")


def read(r, trace):
    return spans.ms_per_step(trace, PHASES)
