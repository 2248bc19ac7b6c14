import canonreduce

META = {
    "name": "canon_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "kernels",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.canon over all leaf device "
            "seconds of the traced pass (stagereduce's leaf_s, same plane "
            "and window): how much of a pass under SYMMETRY is the "
            "canonicalisation; nothing to read on a program without the "
            "scope",
}


def read(ctx):
    return canonreduce.share(ctx)
