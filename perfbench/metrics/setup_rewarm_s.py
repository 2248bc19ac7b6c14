from setupparts import part

META = {
    "name": "setup_rewarm_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "the process ledger's `rewarm.s`: wall seconds inside "
            "`PreparedKernels.rewarm` between the set-up passes (the "
            "ledger is its only record: no caller has a run open around "
            "it, so it has no span); 0 on the sharded engine, which has "
            "none",
}


def read(ctx):
    return part(ctx, "rewarm")
