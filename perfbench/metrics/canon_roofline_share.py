import canonreduce
import canonroof

META = {
    "name": "canon_roofline_share", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels",
    "moves": "states_per_s",
    "what": "least bytes the canonicalisation of the traced pass must move "
            "(canonroof.pass_min_bytes: each enabled candidate's packed row "
            "read, its 8-byte key written) over the device's peak HBM "
            "bandwidth, over the leaf device seconds under kspec.canon; a "
            "floor model, bound by bytes; nothing to read on a program "
            "without the scope",
}


def read(ctx):
    reduced, traced, peaks = canonreduce.for_ctx(ctx), ctx["traced"], ctx["peaks"]
    if not reduced or not reduced["canon_s"] or not traced or not peaks:
        return None
    recs = traced["level_records"]
    if not recs or "enabled_candidates" not in recs[0]:
        return None
    floor_s = (canonroof.pass_min_bytes(recs, ctx["lanes"])
               / peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / reduced["canon_s"]
