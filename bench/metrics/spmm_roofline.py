"""spmm_roofline: the least time of the step's SpMMs (per SpMM the larger
of its operations over the peak compute and its compulsory bytes over
the peak bandwidth, bench/work.py), over the device time of the ops the
trace attributes to the scheduled SpMM runners, in percent. The v5e
publishes no float32 peak; its bf16 peak stands for the compute ceiling."""


def read(rec):
    t = rec.get("trace")
    if not t or not t.get("spmm_s"):
        return None
    return rec["work"]["spmm_least_s_per_step"] * t["steps"] / t["spmm_s"] * 100.0
