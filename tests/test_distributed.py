"""Multi-device behaviour (8 fake host devices via subprocess, since the
main pytest process must keep a single device): EP MoE vs reference,
compressed cross-pod psum with error feedback, elastic checkpoint restore
onto a different mesh, sharding-rule sanitization."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run(script: str) -> subprocess.CompletedProcess:
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "HOME": "/tmp",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           # never drop the platform pin: without it jax probes for a TPU
           # via the GCE metadata server, ~200 s of retries per subprocess
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=420,
    )


@pytest.mark.slow
def test_moe_ep_matches_reference_multidevice():
    r = _run(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, reduced
from repro.models.moe import init_moe, moe_ffn_ref, moe_ffn_ep
cfg = reduced(get_config("qwen3_moe_235b_a22b"))
params = init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*3)
exp = moe_ffn_ref(params, x, cfg)
got = moe_ffn_ep(params, x, cfg, mesh, capacity_factor=8.0)
np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-3, atol=2e-3)
# gradients flow through the EP path
g = jax.grad(lambda p: moe_ffn_ep(p, x, cfg, mesh, capacity_factor=8.0).sum())(params)
assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
print("OK")
"""
    )
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]


@pytest.mark.slow
def test_compressed_psum_error_feedback():
    r = _run(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compress import compressed_psum, init_ef
mesh = jax.make_mesh((8,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))  # one row per pod

def step(xs, ef):
    return jax.shard_map(lambda a, e: compressed_psum(a, "pod", e),
        mesh=mesh, in_specs=(P("pod", None), P("pod", None)),
        out_specs=(P("pod", None), P("pod", None)), check_vma=False)(xs, ef)

exact = jnp.mean(x, axis=0)
ef = jnp.zeros((8, 64))
out, ef = step(x, ef)
err1 = float(jnp.abs(out[0] - exact).max())
assert err1 < 0.05, err1  # int8 quantization error is small
# error feedback: repeated reduction of the SAME gradient converges
accum = jnp.zeros(64)
for i in range(20):
    out, ef = step(x, ef)
    accum = accum + out[0]
drift = float(jnp.abs(accum / 20 - exact).max())
assert drift < err1 / 2 + 1e-6, (drift, err1)  # EF kills the bias
print("OK", err1, drift)
"""
    )
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]


@pytest.mark.slow
def test_elastic_restore_other_mesh(tmp_path):
    r = _run(
        f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_config, reduced
from repro.checkpoint import ckpt as ckpt_mod
from repro.distributed.sharding import param_specs, to_shardings
from repro.train.step import init_train_state
cfg = reduced(get_config("qwen3_14b"))
state = init_train_state(cfg, jax.random.PRNGKey(0), jnp.float32)
ckpt_mod.save(state.params, r"{tmp_path}", 3)  # params tree (keys match restore template)
# restore onto a (2,2,2)-device mesh with full sharding rules (elastic:
# checkpoint was written from unsharded single-host state)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*3)
template = jax.eval_shape(lambda: state)
pspecs = param_specs(template.params, cfg, mesh)
shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                         is_leaf=lambda x: isinstance(x, P))
restored, _ = ckpt_mod.restore(template.params, r"{tmp_path}", shardings=shardings)
for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
devs = {{d for l in jax.tree.leaves(restored) for d in l.devices()}}
assert len(devs) == 8, devs  # actually distributed
print("OK")
"""
    )
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]


def test_sharding_sanitize_single_device():
    """Rule sanitization drops non-divisible axes (whisper vocab 51865)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import sanitize

    mesh = jax.make_mesh(
        (1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    # with axis size 1 everything divides; emulate 16 via a fake mesh dict
    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}

    s = sanitize(P("data", "model"), (51865, 768), FakeMesh())
    assert s == P(None, "model")
    s2 = sanitize(P("data", "model"), (8192, 1024), FakeMesh())
    assert s2 == P("data", "model")
    # non-divisible tuple axis dropped
    s3 = sanitize(P(("pod", "data"), None), (1, 5), FakeMesh())
    assert s3 == P(None, None)
