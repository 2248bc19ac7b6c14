META = {
    "name": "device_idle_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "device",
    "moves": "states_per_s",
    "what": "1 - union of device-operation intervals over the traced pass, "
            "on the busiest device",
}


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s_max"] / trace["window_s"])
