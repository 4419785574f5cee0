"""Engine: host time per decode step, in ms. The mean, over the
window's engine steps that ran a decode and admitted nothing, of the
``engine.step`` span's duration less its blocking reads
(``engine.read``): the time in which the device waits on the engine's
host code (inputs, dispatch, emission)."""
import engine_spans


def read(rec):
    red = engine_spans.for_record(rec)
    if red is None:
        return None
    host = [s.dur - s.below(engine_spans.READ)
            for s in engine_spans.walk(red["roots"])
            if s.name == "step" and s.has("decode")
            and not s.has("admission")]
    return sum(host) / len(host) * 1e-6 if host else None
