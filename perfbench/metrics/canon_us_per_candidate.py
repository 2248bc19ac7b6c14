import canonreduce
from metriclib import has

META = {
    "name": "canon_us_per_candidate", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "kernels",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.canon in the traced pass, on "
            "the plane and in the window of the stage metrics "
            "(canonreduce.for_ctx), x 1e6 over that pass's enabled "
            "candidates: the level records' enabled_candidates and those "
            "of the level a verdict cut (stats `cut_level`, whose chunks "
            "were canonicalised like any other).  Per candidate, not per "
            "stored state: a pass under a symmetry keeps one state of "
            "several candidates, and every candidate is canonicalised, so "
            "this is the number that carries between one block a level "
            "and forty blocks a chunk.  Nothing to read on a program "
            "without the scope, or on a pass whose cut level's record "
            "lacks the count (the seconds would hold work the candidates "
            "do not)",
}


def read(ctx):
    reduced, traced = canonreduce.for_ctx(ctx), ctx.get("traced")
    if not reduced or not traced:
        return None
    recs = traced["level_records"]
    if not has(recs, "enabled_candidates"):
        return None
    enabled = sum(r["enabled_candidates"] for r in recs)
    cut = (traced.get("stats") or {}).get("cut_level")
    if cut:
        if "enabled_candidates" not in cut:
            return None
        enabled += cut["enabled_candidates"]
    return reduced["canon_s"] * 1e6 / enabled if enabled else None
