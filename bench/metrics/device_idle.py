"""device_idle: the share of the traced window in which the device ran no
op (1 - busy union / window), in percent, averaged over the chips."""


def read(rec):
    t = rec.get("trace")
    if not t or not t.get("window_s"):
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
