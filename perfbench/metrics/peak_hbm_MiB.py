META = {
    "name": "peak_hbm_MiB", "unit": "MiB", "better": "lower",
    "source": "program_counter", "layer": "device",
    "moves": "states_per_s",
    "what": "memory_stats()['peak_bytes_in_use'] after the window, the "
            "fullest device",
}


def read(ctx):
    if ctx["rehearsal"] or not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 2.0 ** 20
