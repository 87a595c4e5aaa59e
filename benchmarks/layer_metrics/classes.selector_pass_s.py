"""Set-up: the seconds of the constructor's selector pass over the distinct
pod label rows, the INCLUSIVE length of the start-up record's
`engine.selector_pass` spans before the window, summed.  Not one of the
nine exclusive `setup.*` phases (its time lies inside `setup.classes_s`'s
`engine.compact` there), so those still add up.  Nothing (never 0) where the
record holds no such span: a program whose pass runs over the pods in no
span of its own, a constructor that ran no pass, or a record the ring has
wrapped."""

from benchmarks import program_spans, startup_spans


def read(layers):
    found, window = startup_spans.record(), program_spans.capture()
    if not found or found["wrapped"] or not window or not window["spans"]:
        return None
    hi = window["spans"][0]["start_s"]
    lengths = [
        sp["dur_s"] for sp in found["spans"]
        if sp["name"] == "engine.selector_pass" and sp["start_s"] + sp["dur_s"] <= hi
    ]
    return sum(lengths) if lengths else None
