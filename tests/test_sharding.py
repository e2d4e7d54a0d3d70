"""Sharding rules, divisibility guards, and multi-device equivalence
(the latter in a subprocess with forced host device count)."""
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.models import build, RunConfig
from repro.models.common import (LONG_RULES, SERVE_RULES, TRAIN_RULES,
                                 logical_to_pspec, param_pspecs)


def test_logical_to_pspec_basic():
    names = ("data", "model")
    ps = logical_to_pspec(("embed", "ffn"), TRAIN_RULES, names)
    assert ps == P("data", "model")
    ps = logical_to_pspec(("vocab", "embed"), TRAIN_RULES, names)
    assert ps == P("model", "data")
    # unknown logical axis → replicated
    assert logical_to_pspec(("nope",), TRAIN_RULES, names) == P(None)


def test_divisibility_guard():
    names = ("data", "model")
    sizes = {"data": 16, "model": 16}
    # 8 kv heads don't divide model=16 → replicated
    ps = logical_to_pspec(("embed", "kv_heads", None), TRAIN_RULES, names,
                          shape=(4096, 8, 128), axis_sizes=sizes)
    assert ps == P("data", None, None)
    ps = logical_to_pspec(("embed", "kv_heads", None), TRAIN_RULES, names,
                          shape=(4096, 16, 128), axis_sizes=sizes)
    assert ps == P("data", "model", None)


def test_no_repeated_mesh_axes():
    names = ("data", "model")
    ps = logical_to_pspec(("vocab", "heads"), TRAIN_RULES, names)
    # both map to 'model' — second occurrence dropped
    assert ps == P("model", None)


def test_param_pspecs_cover_all_leaves():
    m = build("qwen3-32b")
    specs = m.specs()
    pspecs = param_pspecs(specs, TRAIN_RULES, ("data", "model"),
                          {"data": 16, "model": 16})
    n_leaves = len(jax.tree.leaves(specs,
                                   is_leaf=lambda x: hasattr(x, "axes")))
    n_ps = len(jax.tree.leaves(pspecs,
                               is_leaf=lambda x: isinstance(x, P)))
    assert n_leaves == n_ps > 10


def test_serve_rules_replicate_fsdp_axis():
    assert SERVE_RULES["embed"] is None
    assert TRAIN_RULES["embed"] == "data"
    assert LONG_RULES["seq"] == "data"
    assert LONG_RULES["batch"] is None


MULTIDEV_SCRIPT = r"""
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp, numpy as np
from repro.models import build, RunConfig
from repro.models.common import TRAIN_RULES
from repro.train.optim import init_opt_state
from repro.train.train_step import build_train_step, make_train_step

run = RunConfig(remat="none", learning_rate=1e-3)
m = build("qwen2-moe-a2.7b", run, smoke=True)
params = m.init(jax.random.PRNGKey(0))
opt = init_opt_state(params)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32)}
batch["labels"] = batch["tokens"]

# single-device reference
p1, o1, met1 = jax.jit(make_train_step(m))(params, opt, batch)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
fn, *_ = build_train_step(m, mesh, donate=False)
p2, o2, met2 = fn(params, opt, batch)
d = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                       - jnp.asarray(b, jnp.float32)))),
    p1, p2)))
print("MAXDIFF", d, "LOSS", float(met1["loss"]), float(met2["loss"]))
assert d < 5e-2, d
assert abs(float(met1["loss"]) - float(met2["loss"])) < 5e-2
print("MULTIDEV-OK")
"""


def test_sharded_train_step_matches_single_device():
    """8 fake host devices: sharded MoE train step ≈ single-device step."""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           # Force the CPU backend: without it, a TPU-enabled jaxlib probes
           # the GCE metadata server (30 retries per variable ⇒ minutes of
           # hang) before falling back.  Fake host devices are CPU anyway.
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT], env=env,
                       capture_output=True, text=True, cwd=".", timeout=420)
    assert "MULTIDEV-OK" in r.stdout, r.stdout + r.stderr
