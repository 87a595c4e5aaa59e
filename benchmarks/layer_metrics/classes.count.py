"""Set-up: the classes the constructor bucketed the pods into, the `classes`
attribute of the start-up record's `engine.classify` span (the last one
before the window).  Nothing (never 0) where the record holds no such span:
a constructor that ran no classification (no class route considered), a
program that keeps no start-up record, or a record the ring has wrapped."""

from benchmarks import program_spans, startup_spans


def read(layers):
    found, window = startup_spans.record(), program_spans.capture()
    if not found or found["wrapped"] or not window or not window["spans"]:
        return None
    hi = window["spans"][0]["start_s"]
    classes = [
        sp["attrs"]["classes"] for sp in found["spans"]
        if sp["name"] == "engine.classify" and "classes" in sp["attrs"]
        and sp["start_s"] + sp["dur_s"] <= hi
    ]
    return classes[-1] if classes else None
