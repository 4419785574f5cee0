"""Engine: host time per admission, in ms. The mean, over the window's
admissions, of the ``engine.admission`` span's duration less its
blocking reads (``engine.read``): input assembly, page and allocator
work, the prefill and sampling dispatches, emission of first tokens."""
import engine_spans


def read(rec):
    red = engine_spans.for_record(rec)
    if red is None:
        return None
    host = [s.dur - s.below(engine_spans.READ)
            for s in engine_spans.walk(red["roots"])
            if s.name == "admission"]
    return sum(host) / len(host) * 1e-6 if host else None
