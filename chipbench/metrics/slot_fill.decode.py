"""Share of decode slots that produced a token per batched step:
step-emitted tokens (window delta of the pool's ``decode_tokens`` less its
admissions' first tokens) over steps x slots."""


def read(r, trace):
    if not r.get("decode_steps"):
        return None
    emitted = r["decode_tokens"] - r["decode_admits"]
    return 100.0 * emitted / (r["decode_steps"] * r["slots"])
