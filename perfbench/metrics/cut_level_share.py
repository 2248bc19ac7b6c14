from metriclib import median_over_passes

META = {
    "name": "cut_level_share", "unit": "%", "better": "lower",
    "source": "program_span", "layer": "level loop on the host",
    "moves": "verdict_s",
    "what": "level_ms of the level the verdict cut (stats `cut_level`: its "
            "begin to the verdict, the dropped chunk's launch closed) over "
            "the pass's `check` span, median over the passes: how much of a "
            "verdict is the level it cut; nothing to read on a pass with no "
            "verdict or a program with no such record",
}


def read(ctx):
    def one(p):
        cut = p["stats"].get("cut_level")
        check_s = sum(s[2] for s in p["spans"]["spans"] if s[0] == "check")
        if not cut or not check_s:
            return None
        return 100.0 * cut["level_ms"] / (1e3 * check_s)

    return median_over_passes(ctx, one)
