import partreduce

META = {
    "name": "compact_novel_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.compact/part.novel in the "
            "traced pass, on the plane and in the window of the stage "
            "metrics, x 1e6 over that pass's distinct states: the "
            "compaction of the new states after dedup (a row gather, a row "
            "scatter and the lane scatters beside it); nothing to read on a "
            "program without part scopes",
}


def read(ctx):
    return partreduce.part_us_per_state(ctx, "novel")
