"""A tiny UniMP cell, added to a copy of the benchmark as files alone,
for the tests that drive a whole run of the attention kind on the CPU."""
import json
import shutil
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
TINY = {
    "name": "tiny-unimp", "source": "https://arxiv.org/abs/2009.03509",
    "num_layers": 3, "hidden": 16, "heads": 4, "in_features": 8, "num_classes": 5,
    "label_rate": 0.625, "num_nodes": 256, "mean_degree": 8, "degree_sigma": 1.1,
    "dtype": "float32", "matmul_precision": "default", "reference": "unimp",
}
WORKLOAD = "tiny-unimp.quick"


def build_tree(root: Path) -> Path:
    """A checkout holding the benchmark and the program, with one more
    configuration, cell and metric added as files and entries only; the
    cell runs the UniMP cell's mix under its limits."""
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-unimp", "source": TINY["source"],
                            "file": "bench/configs/tiny-unimp.json", "reduced": [],
                            "why": "a test size"})
    spec["workloads"].append({"name": WORKLOAD, "config": "tiny-unimp",
                              "traffic": "full-graph-train-attn", "chips": 1,
                              "why": "a test cell"})
    spec["end_to_end"].append({"name": "window_steps", "unit": "steps", "better": "higher",
                               "bound": 0.01, "source": "host_clock",
                               "workloads": [WORKLOAD]})
    for m in spec["per_layer"]:
        if "products-unimp.full" in m.get("workloads", []):
            m["workloads"].append(WORKLOAD)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    b = root / "bench"
    (b / "configs" / "tiny-unimp.json").write_text(json.dumps(TINY))
    (b / "limits" / f"{WORKLOAD}.json").write_text(
        (REPO / "bench" / "limits" / "products-unimp.full.json").read_text())
    (b / "metrics" / "window_steps.py").write_text(
        "def read(rec):\n    return rec['window']['steps']\n")
    return root


def cpu_cell(root: Path, seed: int = 2**31 + 5, seconds: float = 0.3, trace: bool = False):
    """The tiny cell resolved by name, with the look for a chip skipped."""
    import jax

    from bench import harness, peaks

    cell = harness.resolve(WORKLOAD, seed, seconds, trace, time.perf_counter(), root=root)
    cell.device = jax.devices()[0]
    cell.peaks = peaks.for_kind("TPU v5 lite")
    return cell
