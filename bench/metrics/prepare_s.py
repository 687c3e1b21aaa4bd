"""prepare_s: the seconds the program spent preparing kernel layouts on
the whole graph (AutoSage.build_runner), without the probes' subgraph
prepares: the sum of the program's autosage_prepare_ms{phase="prepare"},
read from its registry in the run's process. Layouts are prepared only
in set-up. A program that does not label prepares by phase reports
nothing."""
import sys


def read(rec):
    obs = sys.modules.get("repro.core.obs")
    if obs is None:
        return None
    sums = [h.sum for labels, h in obs.REGISTRY.hist_series("autosage_prepare_ms").items()
            if ("phase", "prepare") in labels]
    return sum(sums) / 1e3 if sums else None
