"""step_mfu: the model operations of the traced steps (bench/work.py:
dense layers and 2*nnz*F per SpMM, forward and backward, none
recomputed) over the traced window times the chip's peak, in percent.
The v5e publishes no float32 peak; the peak is its bf16 197 TFLOP/s."""


def read(rec):
    t = rec.get("trace")
    if not t or not t.get("window_s"):
        return None
    w = rec["work"]
    return w["model_flops_per_step"] * t["steps"] / (t["window_s"] * w["peak_flops"]) * 100.0
