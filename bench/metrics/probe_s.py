"""probe_s: the seconds the scheduler spent probing candidates on the
chip: the sum of the program's autosage_probe_ms histogram."""


def read(rec):
    return rec.get("program", {}).get("probe_s")
