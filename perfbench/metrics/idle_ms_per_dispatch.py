from metriclib import has

META = {
    "name": "idle_ms_per_dispatch", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "device",
    "moves": "states_per_s",
    "what": "idle seconds of the busiest device in the traced pass "
            "(tracereduce's idle_by, every span summed) x 1000 over that "
            "pass's committed dispatches (its level records' dispatches "
            "less discarded_dispatches): the host between launches in "
            "seconds a launch, which does not rise when a device stage "
            "shrinks as device_idle_share does",
}


def read(ctx):
    trace, traced = ctx["trace"], ctx["traced"]
    if not trace or not traced:
        return None
    recs = traced["level_records"]
    if not has(recs, "dispatches") or not has(recs, "discarded_dispatches"):
        return None
    committed = sum(r["dispatches"] - r["discarded_dispatches"] for r in recs)
    return sum(trace["idle_by"].values()) * 1e3 / committed \
        if committed else None
