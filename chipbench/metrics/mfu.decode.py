"""Model FLOPs of the work served in the decode window (the admissions'
prompts and every generated token, ``costs/model.py``) over the window
times the chip's peak FLOP/s."""


def read(r, trace):
    if not r.get("model_flops"):
        return None
    return 100.0 * r["model_flops"] / (r["window_s"] * r["peak_flops"])
