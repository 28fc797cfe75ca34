"""Host time inside ``TMService.submit_rows``/``submit`` calls (the
``bench.submit`` spans of the window) per row the calls accepted.
Layer: ingress (``serve/router.py`` via ``TMService.submit_rows``)."""


def read(ctx):
    rows = ctx.counts["rows_accepted"]
    spans = ctx.spans.get("bench.submit", [])
    if not rows or not spans:
        return None
    return sum(b - a for a, b in spans) / rows * 1e6
