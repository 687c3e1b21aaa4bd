"""probe_compile_s: the seconds XLA spent on the programs the scheduler's
probes ran, compiled or fetched from the persistent compilation cache:
the sum of the program's autosage_xla_compile_ms{phase="probe"}, read
from its registry in the run's process. Probes run only in set-up. A
program that does not count compiles by phase reports nothing."""
import sys


def read(rec):
    obs = sys.modules.get("repro.core.obs")
    if obs is None:
        return None
    sums = [h.sum for labels, h in obs.REGISTRY.hist_series("autosage_xla_compile_ms").items()
            if ("phase", "probe") in labels]
    return sum(sums) / 1e3 if sums else None
