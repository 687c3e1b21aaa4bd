"""decide_s: the seconds the scheduler spent deciding, probes included:
the sum of the program's autosage_decide_ms histogram, over every op."""


def read(rec):
    return rec.get("program", {}).get("decide_s")
