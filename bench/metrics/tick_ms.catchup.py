"""Mean host time of a ``TMService.tick`` call in the window (the
``bench.tick`` spans): flush, drain, analysis and policy together.
Layer: consumer tick (``TMService.tick``)."""


def read(ctx):
    spans = ctx.spans.get("bench.tick", [])
    if not spans:
        return None
    return sum(b - a for a, b in spans) / len(spans) * 1e3
