import roofline

META = {
    "name": "bytes_roofline_share", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels",
    "moves": "states_per_s",
    "what": "least bytes the traced pass's levels must move "
            "(roofline.pass_min_bytes) over the device's peak HBM bandwidth, "
            "over the device's busy time in the traced pass; bound by bytes, "
            "not operations (integer work)",
}


def read(ctx):
    trace, traced, peaks = ctx["trace"], ctx["traced"], ctx["peaks"]
    if not trace or not traced or not peaks:
        return None
    recs = traced["level_records"]
    if not recs or "enabled_candidates" not in recs[0]:
        return None
    floor_s = roofline.pass_min_bytes(
        recs, ctx["lanes"], peaks["hbm_bytes"]) / peaks["hbm_bytes_per_s"]
    # across chips the bytes divide over the chips; busy time is per chip
    return 100.0 * floor_s / ctx["chips"] / trace["busy_s_mean"]
