"""setup_s: from the start of the process to the start of the window
(host clock): imports, data, trace, decide, probe, prepare, compile and
the check's first steps."""


def read(rec):
    return rec.get("setup_s")
