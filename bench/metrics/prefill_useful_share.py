"""Engine: the window's prompt tokens (the engine's ``prefill_tokens``
counter) over the tokens its prefill programs computed, rows times
length of every one (the args of each ``engine.prefill`` span, the
increments of the engine's ``prefill_computed_tokens`` counter), in %.
Padding rows and columns are the rest."""
import engine_spans


def read(rec):
    red = engine_spans.for_record(rec)
    if red is None:
        return None
    computed = sum(s.args["rows"] * s.args["S"]
                   for s in engine_spans.walk(red["roots"])
                   if s.name == "prefill")
    useful = rec["counters"]["prefill_tokens"]
    return 100.0 * useful / computed if computed else None
