"""SPMD-path tests that need >1 device: executed in a subprocess with
forced host devices so the main pytest session keeps 1 device (per the
dry-run isolation rule).
"""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
import jax
from jax import make_mesh, set_mesh, shard_map
"""


def _run(script: str, devices: int = 8, timeout: int = 100):
    """Run ``script`` in a child with ``devices`` host devices; the
    child's ``timeout`` stays under its test's time limit."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", _PRELUDE + script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_secure_aggregate_all_modes():
    out = _run("""
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.fl.spmd import secure_aggregate
mesh = make_mesh((4, 2), ('data', 'model'))
rng = np.random.RandomState(0)
per_party = rng.randn(4, 2000).astype(np.float32)
ref = per_party.mean(0)
for scheme, modes in [('additive', ['psum','reduce_scatter','p2p','plain']),
                      ('shamir', ['psum'])]:
    for mode in modes:
        f = lambda x: secure_aggregate(x[0], scheme=scheme, m=3,
            party_axes=('data',), seed=5, round_index=1, mode=mode,
            block_rows=8)[None]
        g = shard_map(f, mesh=mesh, in_specs=P('data', None),
                      out_specs=P('data', None), axis_names={'data'},
                      check_vma=False)
        with set_mesh(mesh):
            out = np.asarray(jax.jit(g)(jnp.asarray(per_party)))
        assert np.abs(out - ref[None]).max() < 1e-3, (scheme, mode)
        assert np.abs(out - out[0:1]).max() == 0.0, (scheme, mode)
print('ALL MODES OK')
""")
    assert "ALL MODES OK" in out


@pytest.mark.slow
@pytest.mark.time_limit(600)
def test_train_step_protocol_equivalence():
    """All aggregation protocols yield the same parameter update (up to
    fixed-point noise) AND the same update as plain DP — the paper's
    central accuracy claim, verified at the train-step level."""
    out = _run("""
import jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step, place
from repro.optim import adamw_init
from repro.models.registry import get_api
import dataclasses
mesh = make_host_mesh(4, 2)
# float32 compute, so the tensor-parallel and all-manual steps differ
# only by float32 reassociation
cfg = dataclasses.replace(get_config('tinyllama-1.1b', smoke=True),
                          compute_dtype='float32')
api = get_api(cfg)
batch = {'tokens': jnp.ones((8,16), jnp.int32),
         'labels': jnp.ones((8,16), jnp.int32)}
bs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k,v in batch.items()}
results, losses = {}, {}
# 'all_manual' takes the model axis manual too, as a step of compiled
# kernels does; the rest keep it tensor-parallel
for name, proto, tp in [('plain', 'plain', True),
                        ('two_phase', 'two_phase', True),
                        ('p2p', 'p2p', True),
                        ('all_manual', 'two_phase', False)]:
    wrap, _, _ = make_train_step(cfg, mesh, protocol=proto, m=3, seed=0,
                                 donate=False, tensor_parallel=tp)
    step, sh = wrap(bs)
    params = place(api.init(jax.random.PRNGKey(0), cfg), sh['params'])
    opt = place(adamw_init(params), sh['opt'])
    with set_mesh(mesh):
        p2, o2, loss = step(params, opt, jnp.int32(0), batch)
        _, _, loss2 = step(p2, o2, jnp.int32(1), batch)
    results[name] = p2
    losses[name] = (float(loss), float(loss2))
def diffs(a, b):
    return np.concatenate([np.abs(np.asarray(x, np.float64)
                                  - np.asarray(y, np.float64)).ravel()
                           for x, y in zip(jax.tree.leaves(results[a]),
                                           jax.tree.leaves(results[b]))])
for proto in ['two_phase', 'p2p', 'all_manual']:
    d = diffs(proto, 'plain').max()
    assert d < 5e-3, (proto, d)
# the all-manual step is the tensor-parallel one, up to reassociation
d = diffs('all_manual', 'two_phase').max()
assert d < 1e-5, d
for a, b in zip(losses['all_manual'], losses['two_phase']):
    assert abs(a - b) <= 1e-5 * abs(b), losses
print('PROTOCOL EQUIVALENCE OK')
""", timeout=560)
    assert "PROTOCOL EQUIVALENCE OK" in out


@pytest.mark.slow
@pytest.mark.time_limit(600)
def test_mpc_fsdp_matches_replicated():
    out = _run("""
import jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step, place
from repro.optim import adamw_init
from repro.models.registry import get_api
mesh = make_host_mesh(4, 2)
cfg = get_config('qwen3-moe-235b-a22b', smoke=True)
api = get_api(cfg)
batch = {'tokens': jnp.ones((8,16), jnp.int32),
         'labels': jnp.ones((8,16), jnp.int32)}
bs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k,v in batch.items()}
outs = {}
for fsdp in [True, False]:
    wrap, _, _ = make_train_step(cfg, mesh, protocol='two_phase', m=3,
                                 seed=0, fsdp=fsdp, donate=False)
    step, sh = wrap(bs)
    params = place(api.init(jax.random.PRNGKey(0), cfg), sh['params'])
    with set_mesh(mesh):
        p2, _, loss = step(params, place(adamw_init(params), sh['opt']),
                           jnp.int32(0), batch)
    outs[fsdp] = p2
mx = max(jax.tree.leaves(jax.tree.map(
    lambda a,b: float(jnp.abs(a-b).max()), outs[True], outs[False])))
assert mx < 1e-3, mx
print('FSDP EQUIVALENCE OK')
""", timeout=560)
    assert "FSDP EQUIVALENCE OK" in out


def test_committee_election_spmd_agrees():
    out = _run("""
import jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.fl.spmd import elect_committee_spmd
mesh = make_mesh((8,), ('data',))
f = lambda x: elect_committee_spmd(8, 3, 10, seed=4,
                                   party_axes=('data',))[None]
g = shard_map(f, mesh=mesh, in_specs=P('data'), out_specs=P('data'),
              axis_names={'data'}, check_vma=False)
with set_mesh(mesh):
    com = np.asarray(jax.jit(g)(jnp.zeros(8)))
assert (com == com[0:1]).all()
assert len(set(com[0].tolist())) == 3
print('ELECTION OK')
""")
    assert "ELECTION OK" in out
