"""Model step: device time of one execution of the engine's decode
program (``_decode_step``), from the trace, in ms."""

PROGRAM = "_decode_step"


def read(rec):
    runs = [v for k, v in rec["trace"]["programs"].items()
            if PROGRAM in k]
    n = sum(c for c, _ in runs)
    return sum(s for _, s in runs) / n * 1e3 if n else None
