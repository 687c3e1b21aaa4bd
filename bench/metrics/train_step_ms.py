"""train_step_ms: the measured window's length over the number of whole
training steps in it (host clock; each step ends in block_until_ready)."""


def read(rec):
    w = rec.get("window")
    return w["seconds"] / w["steps"] * 1e3 if w and w["steps"] else None
