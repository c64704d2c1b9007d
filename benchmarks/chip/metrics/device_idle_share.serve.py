"""Device: share of the traced serving window in which no operation ran,
in % (1 - union of busy intervals / window, mean over the chips)."""


def read(rec):
    trace = rec.get("trace")
    return None if not trace else 100.0 * trace["idle_share"]
