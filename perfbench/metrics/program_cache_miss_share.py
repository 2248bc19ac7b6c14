from setupparts import process

META = {
    "name": "program_cache_miss_share", "unit": "%", "better": "lower",
    "source": "program_counter", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "`cache_misses` / (`cache_hits` + `cache_misses`) over "
            "`programs` + `helpers` of the process ledger, JAX's own "
            "persistent-cache events before the window: 0 where every "
            "program set-up asked for was found, so a `setup_s` says "
            "whether it was read warm.  Exact between two runs on one "
            "state of the cache.  Nothing to read where the cache answered "
            "nothing",
}


def read(ctx):
    p = process(ctx)
    if p is None:
        return None
    hits = p["programs"]["cache_hits"] + p["helpers"]["cache_hits"]
    misses = p["programs"]["cache_misses"] + p["helpers"]["cache_misses"]
    return 100.0 * misses / (hits + misses) if hits + misses else None
