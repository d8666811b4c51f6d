"""Mean per window of the engine's host half: the observability stamps
gate -> route (gate triple on the host -> escalations looked up in the
cache, routed and submitted), over windows whose gate stamp lies in the
measured window."""


def read(run):
    if not run.spans:
        return None
    lo, hi = run.opened, run.opened + run.seconds
    halves = {}
    for s in run.spans:
        st = dict((k, t) for k, t in s["stages"])
        if "route" in st and lo <= st.get("gate", lo - 1) < hi:
            halves[s["window"]] = st["route"] - st["gate"]
    return 1e3 * sum(halves.values()) / len(halves) if halves else None
