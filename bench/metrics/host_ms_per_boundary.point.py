"""Host time the engine spends at lane-retirement boundaries (ms each).

The union of the ``serve.boundary``, ``serve.gather*`` and ``serve.reseed``
spans of the window (the gathers and reseeds nest inside a boundary or run
after it), over the number of boundaries (``serve.boundary`` spans).
"""


def read(ctx):
    names = ("serve.boundary", "serve.reseed")
    spans = sorted((ts, ts + dur) for name, ts, dur in ctx["spans"]
                   if name in names or name.startswith("serve.gather"))
    boundaries = sum(name == "serve.boundary" for name, _, _ in ctx["spans"])
    if not boundaries:
        return None
    total, end = 0.0, float("-inf")
    for s, e in spans:
        if e > end:
            total += e - max(s, end)
            end = e
    return 1000.0 * total / boundaries
