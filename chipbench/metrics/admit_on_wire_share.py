"""Share of the traced window's cohort admissions made while earlier
escalations were on the wire: 100 x the ``scheduler.admit`` spans that
start inside the window while a ``transport.call`` was out, over the
admissions that start inside the window. None when the trace holds no
``scheduler.admit`` span (a program without them).

A call is out from its submission to the end of its span. It is
submitted in a ``cascade.route`` span, one call a route, and the
transport pool opens the calls in the order they were submitted; but
its thread may take the interpreter lock only after the serving thread
has gone on to the next admission, or further. So the i-th call's
submission is read as the start of the i-th route, not of the call's own
span. A route whose escalated rows all hit the cache makes no call and
would pair the later calls with earlier routes; that takes every
escalated row of a window to repeat an earlier escalated one, which the
cells' traffic does not come near."""

ADMIT, CALL, ROUTE = "scheduler.admit", "transport.call", "cascade.route"


def read(run):
    if run.trace is None or run.trace.window is None:
        return None
    host = run.trace.host
    lo, hi = run.trace.window
    admits = [e.start for e in host if e.name == ADMIT]
    if not admits:
        return None
    admits = [a for a in admits if lo <= a < hi]
    if not admits:
        return 0.0
    routes = sorted(e.start for e in host if e.name == ROUTE)
    calls = sorted((e for e in host if e.name == CALL), key=lambda e: e.start)
    out = [(min(r, c.start), c.end) for r, c in zip(routes, calls)]
    out += [(c.start, c.end) for c in calls[len(routes):]]

    def on_wire(a: float) -> bool:
        return any(sent < a < end for sent, end in out)

    return 100.0 * sum(map(on_wire, admits)) / len(admits)
