import exchange

META = {
    "name": "exchange_ici_share", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "exchange",
    "moves": "states_per_s",
    "what": "the exchange's share of its roofline in the traced pass: bytes "
            "that leave one chip (exchange.bytes_leaving_one_chip of the "
            "manifest's exchange_bytes_total) over the chip's published "
            "interconnect peak, over that chip's seconds inside collective "
            "operations.  A floor model: the peak (1,600 Gbit/s) is all of a "
            "chip's links and a 2x2 host uses some of them, and the bytes of "
            "discarded dispatches and of the verdict all_gathers are not "
            "counted, so the true share is higher; it cannot pass 100%",
}


def read(ctx):
    trace, traced, peaks = ctx["trace"], ctx["traced"], ctx["peaks"]
    if not trace or not traced or not peaks:
        return None
    total = (traced["manifest"].get("result") or {}).get(
        "exchange_bytes_total")
    if not total or not trace.get("collective_s_mean"):
        return None
    floor_s = exchange.ici_floor_seconds(
        total, ctx["chips"], peaks["ici_bits_per_s"])
    return 100.0 * floor_s / trace["collective_s_mean"]
