"""`setup_s` by part, from the program's process ledger (PR 49).

The program keeps one ledger a process (`kafka_specification_tpu/obs/
ledger.py`) and every check's result carries a snapshot of it
(`stats["process"]`).  A warm pass builds nothing, models nothing and
rewarms nothing, so the snapshot the FIRST window pass carries holds
set-up's totals, and `checks.s` less that pass's own `checks.last_s` is the
engine wall of the set-up passes.  (`stats` of a pass of several jobs is
its last job's: the traffic files list one.)

The eight readers under `metrics/` (`setup_start_s`, `setup_model_s`,
`setup_trace_s`, `setup_lower_s`, `setup_passes_s`, `setup_rewarm_s`,
`setup_unbooked_share`, `program_cache_miss_share`) go through here.  A
record without `process` (a program from before PR 49) reads nothing.
"""


def process(ctx):
    """The ledger snapshot of the first window pass, or None."""
    passes = ctx.get("passes") or []
    if not passes:
        return None
    return (passes[0].get("stats") or {}).get("process")


def seconds(ctx):
    """Set-up's wall seconds by part -> dict, or None: no ledger, or a
    rehearsal (a CPU run counts and never times).  `start` is None where
    the program never marked its backend."""
    p = process(ctx)
    if p is None or ctx.get("rehearsal"):
        return None
    ready = p.get("backend_ready_unix")
    return {
        "start": None if ready is None else ready - p["start_unix"],
        "model": p["model_s"],
        "trace": p["programs"]["trace_s"] + p["helpers"]["trace_s"],
        "lower": p["programs"]["lower_s"] + p["helpers"]["lower_s"],
        "passes": p["checks"]["s"] - p["checks"]["last_s"],
        "rewarm": p["rewarm"]["s"],
    }


def part(ctx, name):
    parts = seconds(ctx)
    return None if parts is None else parts[name]
