"""What keeps a chip run honest: the smoke's guard, the hardware table
keyed by device_kind, the on-chip gates on every graph a bucket serves,
and where the compilation cache lives."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from repro import compile_cache
from repro.core import AutoSage, BatchScheduler, ScheduleCache, registry
from repro.core.features import TPU_BY_DEVICE_KIND, HardwareSpec, InputFeatures
from repro.core.obs import MetricsRegistry
from repro.core.scheduler import Decision
from repro.sparse import fixed_degree

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_guard_passes_a_clean_run(smoke):
    reg = MetricsRegistry()
    reg.inc("autosage_decides_total", op="spmm", tier="probe", scheduler="exact")
    reg.inc("autosage_decides_total", op="spmm", tier="cache", scheduler="exact")
    assert smoke.guard(reg) == {}


@pytest.mark.parametrize("name,labels", [
    ("autosage_faults_total", {"site": "probe", "kind": "jaxruntimeerror"}),
    ("autosage_fallback_total", {"from": "ragged_ell_pallas", "to": "baseline"}),
    ("autosage_quarantine_total", {"event": "quarantine"}),
    ("autosage_decides_total", {"op": "spmm", "tier": "fault", "scheduler": "exact"}),
    ("autosage_decides_total", {"op": "spmm", "tier": "transfer", "scheduler": "batch"}),
])
def test_guard_fails_on_hidden_failures(smoke, name, labels):
    reg = MetricsRegistry()
    reg.inc(name, **labels)
    assert list(smoke.guard(reg).values()) == [1.0]


def test_guard_fails_on_every_counter_at_once(smoke):
    reg = MetricsRegistry()
    reg.inc("autosage_faults_total", 3, site="run", kind="oom")
    reg.inc("autosage_fallback_total", 2, **{"from": "x", "to": "baseline"})
    reg.inc("autosage_quarantine_total", event="quarantine")
    bad = smoke.guard(reg)
    assert bad == {"autosage_faults_total": 3.0, "autosage_fallback_total": 2.0,
                   "autosage_quarantine_total": 1.0}


def _device(platform, kind):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_hardware_profile_is_keyed_by_device_kind():
    v5e = HardwareSpec.for_device(_device("tpu", "TPU v5 lite"))
    assert v5e is TPU_BY_DEVICE_KIND["TPU v5 lite"] == HardwareSpec.tpu_v5e()
    assert (v5e.peak_flops, v5e.hbm_bw, v5e.smem_bytes) == (197e12, 819e9, 2**20)
    assert v5e.source
    assert HardwareSpec.for_device(_device("cpu", "cpu")).name == "cpu"


def test_unknown_tpu_kind_is_an_error_not_v5e():
    with pytest.raises(KeyError, match="TPU v7x"):
        HardwareSpec.for_device(_device("tpu", "TPU v7x"))


def test_bucket_serves_baseline_to_a_graph_past_the_pinned_gate(monkeypatch):
    """Two graphs of one bucket on either side of the ragged SpMM's SMEM
    limit (about 130k slots on a v5e): the ragged variant pinned by the
    first must not be served to the second."""
    monkeypatch.setenv("AUTOSAGE_PROBE_PALLAS", "1")
    hw = HardwareSpec.tpu_v5e()
    small, big = (fixed_degree(n, 5, n_cols=1_000_000, seed=1).dedup_edges()
                  for n in (20_000, 26_100))
    ragged = [v for v in registry.candidates(InputFeatures.from_csr(small, 256, "spmm"), hw)
              if v.name == "ragged_ell_pallas"][0]
    assert not ragged.applicable(InputFeatures.from_csr(big, 256, "spmm"), hw)

    bs = BatchScheduler(AutoSage(cache=ScheduleCache(path=None), hw=hw),
                        probe_budget_ms=0.0)
    assert bs.decide(small, 256, "spmm").choice == "baseline"  # provisional
    st = bs._by_bucket[bs.last_bucket]
    st.decision = Decision(
        op="spmm", choice=ragged.full_name(), variant=ragged, guardrail=None,
        from_cache=False, probe_ms={}, probe_overhead_ms=0.0,
        probe_iter_ms=0.0, estimates_ms={},
    )
    st.probed = True

    d = bs.decide(big, 256, "spmm")
    assert bs._by_bucket[bs.last_bucket] is st  # same bucket
    assert (d.choice, d.variant.is_baseline, bs.last_source) == (
        "baseline", True, "gated")
    assert bs.decide(small, 256, "spmm").choice == ragged.full_name()
    assert bs.last_source == "probe"


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defers_to_the_environment(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir is None  # JAX reads the env


def test_compile_cache_defaults_to_the_repo(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(ROOT / ".jax_cache") == jax.config.jax_compilation_cache_dir
