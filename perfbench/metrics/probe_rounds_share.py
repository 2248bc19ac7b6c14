from metriclib import has, median_over_passes

META = {
    "name": "probe_rounds_share", "unit": "%", "better": "lower",
    "source": "program_counter", "layer": "kernels",
    "moves": "states_per_s",
    "what": "search rounds the sorted-set probes ran over their query lanes "
            "(level records' probe_rounds: a device count, the bit length of "
            "the fullest directory bucket a probe) over the rounds a search "
            "of the whole pinned capacity runs (probe_rounds_plain: "
            "cap.bit_length() a probe), summed over a pass's levels, median "
            "over the passes; 100% is the fixed-count search; nothing to "
            "read on a program whose records lack the fields",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "probe_rounds") or not has(recs, "probe_rounds_plain"):
            return None
        plain = sum(r["probe_rounds_plain"] for r in recs)
        return 100.0 * sum(r["probe_rounds"] for r in recs) / plain \
            if plain else None

    return median_over_passes(ctx, one)
