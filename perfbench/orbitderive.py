#!/usr/bin/env python3
"""The orbit sizes of a configuration run under SYMMETRY, level by level.

    python3 perfbench/orbitderive.py <config> <depth> [seconds]

Breadth-first search over the program's oracle twin of the configuration's
cfg, as `selfcheck.py --derive` runs it (the twin hands out one canonical
member an orbit, so a plain set of them counts orbits), and for every new
member its orbit's size, N! over its stabiliser, from the twin's own
`symmetry.canonical`.  The sizes of a level sum to the UNREDUCED job's count
of that level: what ties the reduction to the model.  Host Python only; writes
perfbench/golden/<config>.orbits.json (`levels`: orbits, `orbit_states`: the
sums) and stops after the last level it finished inside `seconds`.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def derive(om, depth, seconds=float("inf"), say=None):
    """-> (levels, orbit_states) of the oracle model `om`, to `depth` or
    the last level finished before `seconds` ran out."""
    size = om.symmetry.canonical
    deadline = time.perf_counter() + seconds
    frontier = list(dict.fromkeys(om.init_states()))
    visited = set(frontier)
    levels = [len(frontier)]
    orbit_states = [sum(size(s)[1] for s in frontier)]
    while frontier and len(levels) <= depth:
        nxt, weight = [], 0
        for i, s in enumerate(frontier):
            if i % 256 == 0 and time.perf_counter() > deadline:
                return levels, orbit_states
            for a in om.actions:
                for t in a.successors(s):
                    if t not in visited:
                        visited.add(t)
                        nxt.append(t)
                        weight += size(t)[1]
        for name, pred in om.invariants:
            if not all(pred(s) for s in nxt):
                raise SystemExit(f"{name} violated at depth {len(levels)}")
        if nxt:
            levels.append(len(nxt))
            orbit_states.append(weight)
            if say:
                say(levels, orbit_states)
        frontier = nxt
    return levels, orbit_states


def main():
    os.environ["JAX_PLATFORMS"] = "cpu"  # the tensor model is built, never run
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import adapter

    name, depth = sys.argv[1], int(sys.argv[2])
    seconds = float(sys.argv[3]) if len(sys.argv) > 3 else float("inf")
    with open(os.path.join(HERE, "configs", name + ".json")) as fh:
        config = json.load(fh)
    om = adapter.Job(config, ROOT).oracle_model()
    if om.symmetry is None:
        raise SystemExit(f"{name}: its cfg has no SYMMETRY stanza")
    path = os.path.join(HERE, "golden", name + ".orbits.json")
    t0 = time.perf_counter()

    def write(levels, orbit_states):
        record = {
            "config": name,
            "command": "python3 perfbench/orbitderive.py "
                       + " ".join(sys.argv[1:]),
            "what": "breadth-first search over the oracle twin under the "
                    "cfg's SYMMETRY stanza: `levels` counts orbits, "
                    "`orbit_states` sums each level's orbit sizes (N! over "
                    "the stabiliser) and equals the unreduced job's count of "
                    "that level; CPU, host Python only",
            "symmetry": {"set": om.symmetry.set_name,
                         "order": om.symmetry.order},
            "derived": time.strftime("%Y-%m-%d", time.gmtime()),
            "oracle_seconds_on_this_cpu": round(time.perf_counter() - t0, 1),
            "levels": levels,
            "total": sum(levels),
            "orbit_states": orbit_states,
        }
        with open(path + ".tmp", "w") as fh:
            json.dump(record, fh, indent=1)
            fh.write("\n")
        os.replace(path + ".tmp", path)
        print(len(levels) - 1, levels[-1], orbit_states[-1],
              round(time.perf_counter() - t0, 1), flush=True)

    levels, orbit_states = derive(om, depth, seconds, write)
    write(levels, orbit_states)
    return 0


if __name__ == "__main__":
    sys.exit(main())
