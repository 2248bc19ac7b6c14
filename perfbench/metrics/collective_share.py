META = {
    "name": "collective_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "exchange",
    "moves": "states_per_s",
    "what": "device seconds inside collective operations (all-to-all, "
            "all-gather, all-reduce; union of their intervals, mean over "
            "the devices) over device busy seconds, traced pass",
}


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("busy_s_mean"):
        return None
    return 100.0 * trace["collective_s_mean"] / trace["busy_s_mean"]
