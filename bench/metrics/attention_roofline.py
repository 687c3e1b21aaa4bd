"""attention_roofline: the least time of the step's attentions (per
layer, forward and backward, the larger of its operations over the peak
compute and its compulsory bytes over the peak bandwidth,
bench/work_attn.py), over the device time of the window's ops under the
program's attention scopes (``autosage.<dir>.attention*``: the forward
pipeline, the backward's five scheduled pieces and its softmax pieces;
bench/scopes.py), in percent. The v5e publishes no float32 peak; its
bf16 peak stands for the compute ceiling."""


def read(rec):
    t = rec.get("trace")
    if not t or not t.get("scopes"):
        return None
    busy = sum(s for key, s in t["scopes"].items()
               if key.split(".", 1)[-1].startswith("attention"))
    if not busy:
        return None
    return rec["work"]["attention_least_s_per_step"] * t["steps"] / busy * 100.0
