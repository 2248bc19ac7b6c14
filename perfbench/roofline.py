"""The table of peaks and the least bytes a BFS level must move.

A BFS level is integer work bound by HBM bytes, so the roofline of a level
is bytes over bandwidth.  The model below is a floor that no implementation
can go under, not an estimate of what this one moves:

  read every frontier row once                    4 * lanes * frontier
  write every new row once                        4 * lanes * new
  read each enabled candidate's fingerprint       8 * enabled
  touch the visited set once per candidate        8 * enabled
  insert each new fingerprint                     8 * new

A candidate's packed row is not counted (an ideal kernel fingerprints it in
registers), and the visited set is counted at one 8-byte word per probe
(an ideal hash probe), so the floor does not grow with the set's size:
`visited` is taken only to refuse a level whose set could not be resident.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json; "
            f"add its published peaks with their source")
    return table[device_kind]


def level_min_bytes(frontier, enabled, new, visited, lanes,
                    hbm_bytes=None):
    if min(frontier, enabled, new, visited, lanes) < 0:
        raise ValueError("counts are non-negative")
    if hbm_bytes is not None and 8 * visited > hbm_bytes:
        raise ValueError("visited set larger than the device's memory")
    return (4 * lanes * frontier + 4 * lanes * new
            + 8 * enabled + 8 * enabled + 8 * new)


def pass_min_bytes(level_records, lanes, hbm_bytes=None):
    """Sum over a pass's level records (`frontier`, `enabled_candidates`,
    `new`, `total`)."""
    total = 0
    for rec in level_records:
        total += level_min_bytes(
            rec["frontier"], rec["enabled_candidates"], rec["new"],
            rec["total"], lanes, hbm_bytes)
    return total
