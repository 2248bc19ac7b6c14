import stagereduce

META = {
    "name": "stage_exchange_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "exchange",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.exchange in the traced pass, "
            "busiest device, x 1e6 over that pass's distinct states: "
            "routing by owner, the codec's encode and decode and the "
            "collective itself (the framing digests on either side are "
            "stage_digest); against the dedup stages it is the codec-"
            "against-dedup split of a sharded pass",
}


def read(ctx):
    return stagereduce.stage_us_per_state(ctx, "exchange")
