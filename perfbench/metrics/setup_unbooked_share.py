from setupparts import seconds

META = {
    "name": "setup_unbooked_share", "unit": "%", "better": "lower",
    "source": "program_span", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "1 - (`setup_start_s` + `setup_model_s` + `setup_passes_s` + "
            "`setup_rewarm_s`) / the harness's `setup_s`: what no mark of "
            "the program holds.  That is the harness's own steps (the "
            "cell's files and golden, `Cell.__init__`, a run directory, "
            "its records and a rendered trace a pass, the 2 s oracle "
            "prefix) and anything the ledger misses: the check that the "
            "parts add up",
}


def read(ctx):
    parts = seconds(ctx)
    setup_s = (ctx.get("setup") or {}).get("setup_s")
    if parts is None or parts["start"] is None or not setup_s:
        return None
    booked = sum(parts[k] for k in ("start", "model", "passes", "rewarm"))
    return 100.0 * (1.0 - booked / setup_s)
