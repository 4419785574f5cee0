"""Model step: device time of the engine's prefill programs
(``_prefill_and_write``) over the prompt tokens they served (the
engine's ``prefill_tokens`` counter, padding not counted), in us per
token."""

PROGRAM = "_prefill_and_write"


def read(rec):
    s = sum(v[1] for k, v in rec["trace"]["programs"].items()
            if PROGRAM in k)
    n = rec["counters"]["prefill_tokens"]
    return s / n * 1e6 if s and n else None
