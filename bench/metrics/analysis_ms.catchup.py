"""Mean host time of the program's ``tm.analysis`` span per analysis in
the traced part of the window. The span ends at the host read of the
accuracies, so it holds the analysis' device time. Layer: analysis
(``core/accuracy.py:analyze_replicated``)."""
from bench import program


def read(ctx):
    spans = program.spans(ctx, "tm.analysis")
    if not spans:
        return None
    return sum(s.end - s.start for s in spans) / len(spans) * 1e3
