import canonreduce

META = {
    "name": "stage_canon_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "kernels",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.canon in the traced pass, on "
            "the plane and in the window of the stage metrics, x 1e6 over "
            "that pass's stored states (orbits): forming every image of "
            "every live candidate, the least one and its fingerprint "
            "(ops/canon.py); nothing to read on a program without the scope",
}


def read(ctx):
    return canonreduce.us_per_state(ctx)
