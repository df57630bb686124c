"""Share of its roofline the decode attention kernel reached: the least
time the chip needs for the work of the tokens decoded in the window
(live positions only; ``costs/decode_attention.py``), the larger of
FLOPs over peak FLOP/s and bytes over peak HBM bandwidth, over the
kernel's device time in the trace. Bandwidth bounds it."""
from chipbench import trace as tr

KERNEL = r"^%decode_attention\b.*tpu_custom_call"


def read(r, trace):
    ns = tr.op_ns(trace, KERNEL)
    if not ns or not r.get("decode_attention_flops"):
        return None
    least = max(r["decode_attention_flops"] / r["peak_flops"],
                r["decode_attention_bytes"] / r["peak_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
