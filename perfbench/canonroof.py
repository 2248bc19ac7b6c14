"""The least bytes the canonicalisation of a pass must move.

Whatever forms the orbit key of a candidate has to read the candidate's
packed row and write its 64-bit key, once a candidate:

  read each enabled candidate's row     4 * lanes * enabled
  write its key                         8 * enabled

The |G| images are counted at nothing (an ideal kernel forms them in
registers), so this is a floor no implementation can go under and the share
it gives of the device's bandwidth cannot pass 100%: the same work whatever
implements it, from the level records' exact `enabled_candidates`.
"""


def level_min_bytes(enabled, lanes):
    if min(enabled, lanes) < 0:
        raise ValueError("counts are non-negative")
    return enabled * (4 * lanes + 8)


def pass_min_bytes(level_records, lanes):
    """Sum over a pass's level records (`enabled_candidates`)."""
    return sum(level_min_bytes(rec["enabled_candidates"], lanes)
               for rec in level_records)
