META = {
    "name": "level_programs", "unit": "count", "better": "lower",
    "source": "program_counter", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "the level programs set-up built: the `compile` spans of its "
            "passes (one a (program, bucket, capacity, widths) the engine "
            "compiled or loaded: `fsc`, `fgd`, `step`, `init`, `hinv`, "
            "`dvl`, `dvh`) plus the variants `rewarm` returned.  These are "
            "the programs that cost the minutes of a first set-up and the "
            "MiB of the compile cache; `programs` counts them together "
            "with the helper jits that cost a cache file each.  Nothing to "
            "read on a set-up record without the two fields",
}


def read(ctx):
    setup = ctx.get("setup") or {}
    if "compile_spans" not in setup or "rewarmed_variants" not in setup:
        return None
    return setup["compile_spans"] + setup["rewarmed_variants"]
