"""Multi-device behaviour (8 virtual CPU devices via subprocess)."""
import jax
import pytest

from util import run_multidevice


def test_distributed_ozaki_bitwise_reproducible():
    out = run_multidevice("""
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np, jax.numpy as jnp
from repro.core.ozaki import OzakiConfig
from repro.launch.mesh import make_mesh_compat
from repro.parallel.ozaki_shard import distributed_ozaki_matmul
rng = np.random.default_rng(0)
a = jnp.asarray(rng.uniform(-0.5, 0.5, (64, 256))
                * np.exp(rng.standard_normal((64, 256))))
b = jnp.asarray(rng.uniform(-0.5, 0.5, (256, 48)))
cfg = OzakiConfig(num_splits=11)
outs = []
for shape in ((2, 4), (4, 2), (1, 8)):
    mesh = make_mesh_compat(shape, ('data', 'model'))
    outs.append(np.asarray(distributed_ozaki_matmul(a, b, mesh, cfg)))
assert np.array_equal(outs[0], outs[1]), 'mesh 2x4 vs 4x2'
assert np.array_equal(outs[0], outs[2]), 'mesh 2x4 vs 1x8'
ref = np.asarray(a) @ np.asarray(b)
err = np.abs(outs[0] - ref).max() / np.abs(ref).max()
assert err < 1e-14, err
# overlap schedule identical (int32 psum exactness)
o2 = np.asarray(distributed_ozaki_matmul(
    a, b, make_mesh_compat((2, 4), ('data', 'model')),
    cfg, schedule='overlap'))
assert np.array_equal(outs[0], o2)
print('OK')
""")
    assert "OK" in out


def test_distributed_ozaki_m_sharded_and_df32():
    out = run_multidevice("""
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np, jax.numpy as jnp
from repro.core.ozaki import OzakiConfig
from repro.core.xmath import df32_to_f64
from repro.launch.mesh import make_mesh_compat
from repro.parallel.ozaki_shard import distributed_ozaki_matmul
rng = np.random.default_rng(1)
a = jnp.asarray(rng.uniform(-0.5, 0.5, (64, 128)))
b = jnp.asarray(rng.uniform(-0.5, 0.5, (128, 32)))
mesh = make_mesh_compat((2, 4), ('data', 'model'))
c = np.asarray(distributed_ozaki_matmul(a, b, mesh,
               OzakiConfig(num_splits=9), m_axis='data'))
ref = np.asarray(a) @ np.asarray(b)
assert np.abs(c - ref).max() < 1e-13
a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
dw = distributed_ozaki_matmul(a32, b32, mesh,
                              OzakiConfig(num_splits=9, accum='df32'),
                              m_axis='data')
c32 = np.asarray(df32_to_f64(dw))
# oracle must use the SAME f32-rounded inputs (their rounding is ~1e-8;
# the scheme reproduces their exact product to df32 precision)
ref32 = np.asarray(a32, np.float64) @ np.asarray(b32, np.float64)
assert np.abs(c32 - ref32).max() < 1e-11, np.abs(c32 - ref32).max()
print('OK')
""")
    assert "OK" in out


def test_distributed_batched_kshard_pallas_fused_parity():
    """k-sharded batched API: pallas_fused (epilogue) == xla == unsharded.

    int32 slice-product reductions are exact and the accumulation runs on
    the reduced (replicated) products, so the sharded result is bitwise
    equal to the single-device pipeline for every backend.
    """
    out = run_multidevice("""
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np, jax.numpy as jnp
from repro.core.ozaki import OzakiConfig, ozaki_matmul_batched
from repro.launch.mesh import make_mesh_compat
from repro.parallel.ozaki_shard import ozaki_matmul_kshard_auto
rng = np.random.default_rng(3)
a = jnp.asarray(rng.uniform(-0.5, 0.5, (3, 16, 64))
                * np.exp(rng.standard_normal((3, 16, 64))))
w = jnp.asarray(rng.uniform(-0.5, 0.5, (64, 24)))
bb = jnp.asarray(rng.uniform(-0.5, 0.5, (3, 64, 24)))
mesh = make_mesh_compat((1, 8), ('data', 'model'))
ref = np.einsum('bmk,kn->bmn', np.asarray(a), np.asarray(w))
un = np.asarray(ozaki_matmul_batched(a, w, OzakiConfig(num_splits=9)))
un3 = np.asarray(ozaki_matmul_batched(a, bb, OzakiConfig(num_splits=9)))
for backend, epi in (('xla', False), ('pallas_fused', True)):
    cfg = OzakiConfig(num_splits=9, backend=backend, fuse_epilogue=epi)
    sh = np.asarray(ozaki_matmul_kshard_auto(a, w, mesh, cfg, axis='model'))
    assert np.array_equal(sh, un), backend + ' broadcast'
    sh3 = np.asarray(ozaki_matmul_kshard_auto(a, bb, mesh, cfg,
                                              axis='model'))
    assert np.array_equal(sh3, un3), backend + ' stacked'
err = np.abs(un - ref).max() / np.abs(ref).max()
assert err < 1e-14, err
print('OK')
""")
    assert "OK" in out


def test_layers_shard_axis_wiring():
    """ArchConfig.ozaki_shard_axis k-shards the 2-D policy matmul through
    the registered shard mesh without changing a single bit; 3-D model
    projections must pass through untouched (see ``_matmul_ozaki``)."""
    out = run_multidevice("""
import jax, numpy as np, jax.numpy as jnp
from repro.api import MatmulPolicy
from repro.launch.mesh import make_mesh_compat
from repro.models.layers import _matmul_ozaki
from repro.parallel.ozaki_shard import use_shard_mesh
rng = np.random.default_rng(5)
x = jnp.asarray(rng.standard_normal((4, 1, 64)), jnp.float32)
w = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
x2 = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)    # plain 2-D
mesh = make_mesh_compat((1, 8), ('data', 'model'))
pol = MatmulPolicy.parse('ozaki-fp64x9/pallas_fused+epilogue')
shp = MatmulPolicy.parse('ozaki-fp64x9/pallas_fused+epilogue|shard=model')
ref = np.asarray(_matmul_ozaki(x, w, pol))
ref2 = np.asarray(_matmul_ozaki(x2, w, pol))
with use_shard_mesh(mesh):
    # 2-D: constraints applied (eager + jit), bitwise identical
    f2 = jax.jit(lambda x, w: _matmul_ozaki(x, w, shp))
    assert np.array_equal(np.asarray(f2(x2, w)), ref2)
    assert np.array_equal(np.asarray(_matmul_ozaki(x2, w, shp)), ref2)
    # 3-D model projections: shard_axis is a structural no-op
    assert np.array_equal(np.asarray(_matmul_ozaki(x, w, shp)), ref)
# absent mesh: silent no-op
assert np.array_equal(np.asarray(_matmul_ozaki(x2, w, shp)), ref2)
# the public facade applies the same 2-D constraints under the mesh
import repro
fa = jnp.asarray(np.float64(np.asarray(x2)))
fw = jnp.asarray(np.float64(np.asarray(w)))
fref = np.asarray(repro.matmul(fa, fw, precision='ozaki-fp64x9'))
with use_shard_mesh(mesh):
    fsh = np.asarray(repro.matmul(fa, fw,
                                  precision='ozaki-fp64x9|shard=model'))
assert np.array_equal(fsh, fref)
print('OK')
""")
    assert "OK" in out


def test_sharded_train_step_matches_single_device():
    out = run_multidevice("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.parallel.sharding import make_plan
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import init_training, make_train_step, train_step
from repro.train.optimizer import adamw_init
from repro.data.pipeline import make_data

cfg = get_config('llama3.2-3b').reduced()
oc = OptimizerConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
data = make_data(cfg, 32, 8)
batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

# single device reference
from repro.models import init_model
params, axes = init_model(cfg, jax.random.key(0))
p1, o1, m1 = train_step(cfg, oc, params, adamw_init(params), batch)

# 4x2 mesh sharded
mesh = make_local_mesh(data=4, model=2)
plan = make_plan(cfg, axes, mesh, kind='train')
step = make_train_step(cfg, oc, plan)
params2, _, opt2 = init_training(cfg, jax.random.key(0), plan)
p2, o2, m2 = step(params2, opt2, batch)
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=5e-4)
assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-2
print('OK')
""")
    assert "OK" in out


def test_int8_gradient_compression_with_error_feedback():
    out = run_multidevice("""
import jax, numpy as np, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh_compat
from repro.parallel.compression import (compress_psum, init_ef_state)

mesh = make_mesh_compat((8,), ('data',))
rng = np.random.default_rng(0)
g_all = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)

@jax.jit                      # one compile, not one per eager primitive
def one_round(gs, res):
    def local(g, r):
        avg, ef = compress_psum({'g': g[0]}, init_ef_state({'g': g[0]})._replace(residual={'g': r[0]}), 'data')
        return avg['g'][None], ef.residual['g'][None]
    return jax.shard_map(local, mesh=mesh, in_specs=(P('data'), P('data')),
                         out_specs=(P('data'), P('data')))(gs, res)

res = jnp.zeros_like(g_all)
exact = np.asarray(jnp.mean(g_all, axis=0))
# EF: accumulated compressed sum over T rounds of the SAME grad converges
acc = np.zeros(256)
for t in range(20):
    avg, res = one_round(g_all, res)
    acc += np.asarray(avg[0])
err = np.abs(acc / 20 - exact).max() / (np.abs(exact).max() + 1e-9)
assert err < 2e-3, err
print('OK')
""")
    assert "OK" in out


def test_int8_transport_parity_matrix():
    """The tentpole acceptance matrix: every int8-slice collective
    schedule x layout x backend is BITWISE identical to the single-device
    reference, fast-mode and df32 rows included (int32 collectives are
    associative; the mnshard gather ships the exact split the reference
    computes)."""
    out = run_multidevice("""
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np, jax.numpy as jnp
from repro.core.ozaki import OzakiConfig, ozaki_matmul, ozaki_matmul_batched
from repro.core.xmath import df32_to_f64
from repro.launch.mesh import make_mesh_compat
from repro.parallel.ozaki_shard import (distributed_ozaki_matmul,
                                        distributed_ozaki_matmul_batched,
                                        ozaki_matmul_mnshard)
rng = np.random.default_rng(7)
m, k, n = 32, 256, 48
a = jnp.asarray(rng.uniform(-0.5, 0.5, (m, k))
                * np.exp(rng.standard_normal((m, k))))
b = jnp.asarray(rng.uniform(-0.5, 0.5, (k, n)))
mesh = make_mesh_compat((1, 8), ('data', 'model'))
cfg = OzakiConfig(num_splits=6)
ref = np.asarray(ozaki_matmul(a, b, cfg))
# k-shard: all four collective schedules
for sched in ('psum', 'overlap', 'reduce_scatter', 'rs_stream'):
    got = np.asarray(distributed_ozaki_matmul(a, b, mesh, cfg,
                                              schedule=sched))
    assert np.array_equal(got, ref), f'kshard/{sched}'
# fast-mode row: resolve_accuracy_config must match the reference driver
cfg_f = OzakiConfig(num_splits=6, fast_mode=True)
ref_f = np.asarray(ozaki_matmul(a, b, cfg_f))
got_f = np.asarray(distributed_ozaki_matmul(a, b, mesh, cfg_f,
                                            schedule='overlap'))
assert np.array_equal(got_f, ref_f), 'kshard fast-mode'
# m/n-shard: SliceWire gather, both schedules, xla + pallas backends
for backend in ('xla', 'pallas'):
    cfg_b = OzakiConfig(num_splits=6, backend=backend)
    ref_b = np.asarray(ozaki_matmul(a, b, cfg_b))
    for sched in ('allgather', 'overlap'):
        got = np.asarray(ozaki_matmul_mnshard(a, b, mesh, cfg_b,
                                              schedule=sched))
        assert np.array_equal(got, ref_b), f'mnshard/{sched}/{backend}'
# 2-D (k x batch) mesh composition, broadcast weights
mesh2 = make_mesh_compat((2, 4), ('data', 'model'))
ab = jnp.asarray(rng.uniform(-0.5, 0.5, (4, m, k)))
refb = np.asarray(ozaki_matmul_batched(ab, b, cfg))
for sched in ('psum', 'reduce_scatter'):
    got = np.asarray(distributed_ozaki_matmul_batched(
        ab, b, mesh2, cfg, axis='model', batch_axis='data',
        schedule=sched))
    assert np.array_equal(got, refb), f'batched2d/{sched}'
# df32 row (TPU-deployable accumulator)
cfg_d = OzakiConfig(num_splits=4, accum='df32')
a32 = a.astype(jnp.float32).astype(jnp.float64)
b32 = b.astype(jnp.float32).astype(jnp.float64)
ref_d = np.asarray(ozaki_matmul(a32, b32, cfg_d))
got_d = np.asarray(df32_to_f64(distributed_ozaki_matmul(
    a32, b32, mesh, cfg_d, schedule='psum')))
assert np.array_equal(got_d, ref_d), 'kshard df32'
print('OK')
""")
    assert "OK" in out


def test_int8_transport_facade_and_auto_routing():
    """``comm=int8`` end to end: the policy spec routes ``repro.matmul``
    and ``ozaki_matmul_kshard_auto`` onto the explicit int8-slice
    schedules, bitwise-equal to the unsharded facade; schedules that the
    transport cannot serve (df32 auto, streaming mnshard) fall back /
    refuse loudly."""
    out = run_multidevice("""
import dataclasses
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np, jax.numpy as jnp
import repro
from repro.api import MatmulPolicy
from repro.core.ozaki import OzakiConfig, ozaki_matmul, ozaki_matmul_batched
from repro.launch.mesh import make_mesh_compat
from repro.parallel.ozaki_shard import (ozaki_matmul_kshard_auto,
                                        ozaki_matmul_mnshard, use_shard_mesh)
rng = np.random.default_rng(11)
a = jnp.asarray(rng.standard_normal((32, 256)))
b = jnp.asarray(rng.standard_normal((256, 48)))
mesh = make_mesh_compat((1, 8), ('data', 'model'))
pol = MatmulPolicy.parse('ozaki-fp64x6|shard=model|comm=int8')
ref = np.asarray(repro.matmul(a, b, MatmulPolicy.parse('ozaki-fp64x6')))
with use_shard_mesh(mesh):
    got = np.asarray(repro.matmul(a, b, pol))
assert np.array_equal(got, ref), 'facade comm=int8'
# kshard_auto: comm=int8 re-routes 2-D and 3-D-broadcast onto the
# explicit schedules, still bitwise vs the unsharded reference
cfg = OzakiConfig(num_splits=6, comm='int8')
assert np.array_equal(
    np.asarray(ozaki_matmul_kshard_auto(a, b, mesh, cfg, axis='model')),
    np.asarray(ozaki_matmul(a, b, OzakiConfig(num_splits=6))))
ab = jnp.asarray(rng.standard_normal((3, 32, 256)))
assert np.array_equal(
    np.asarray(ozaki_matmul_kshard_auto(ab, b, mesh, cfg, axis='model')),
    np.asarray(ozaki_matmul_batched(ab, b, OzakiConfig(num_splits=6))))
# stacked 3-D weights stay on the GSPMD fallback (still runs, correct)
bb = jnp.asarray(rng.standard_normal((3, 256, 48)))
got3 = np.asarray(ozaki_matmul_kshard_auto(ab, bb, mesh, cfg,
                                           axis='model'))
ref3 = np.asarray(ozaki_matmul_batched(ab, bb, OzakiConfig(num_splits=6)))
assert np.array_equal(got3, ref3), 'stacked GSPMD fallback'
# mnshard refuses schedules it cannot serve losslessly
cfg_s = OzakiConfig(num_splits=6, backend='pallas_fused', streaming=True)
try:
    ozaki_matmul_mnshard(a, b, mesh, cfg_s)
    raise SystemExit('streaming mnshard must refuse')
except ValueError as e:
    assert 'streaming' in str(e)
try:
    ozaki_matmul_mnshard(a, b, mesh, OzakiConfig(num_splits=6,
                                                 accum='df32'))
    raise SystemExit('df32 mnshard must refuse')
except ValueError as e:
    assert 'f64' in str(e)
print('OK')
""")
    assert "OK" in out


def test_scheme2_int8_transport_parity_matrix():
    """Scheme II residue-wire transport: every k-shard schedule x
    backend (fused-CRT epilogue included) and the ResidueWire mnshard
    gather are BITWISE identical to the single-device reference, across
    mesh shapes — and the ``ozaki2-fp64|shard=model|comm=int8`` policy
    spec routes the facade onto the same schedules."""
    out = run_multidevice("""
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np, jax.numpy as jnp
import repro
from repro.api import MatmulPolicy
from repro.core.modular import ModularConfig, ozaki2_matmul
from repro.launch.mesh import make_mesh_compat
from repro.parallel.ozaki_shard import (distributed_ozaki2_matmul,
                                        ozaki2_matmul_mnshard,
                                        use_shard_mesh)
rng = np.random.default_rng(13)
m, k, n = 16, 256, 24
a = jnp.asarray(rng.uniform(-0.5, 0.5, (m, k))
                * np.exp(rng.standard_normal((m, k))))
b = jnp.asarray(rng.uniform(-0.5, 0.5, (k, n)))
mesh = make_mesh_compat((1, 8), ('data', 'model'))
for cfg in (ModularConfig(),
            ModularConfig(backend='pallas_fused', fuse_epilogue=True)):
    ref = np.asarray(ozaki2_matmul(a, b, cfg))
    tag = cfg.backend + ('+epi' if cfg.fuse_epilogue else '')
    for sched in ('psum', 'reduce_scatter'):
        got = np.asarray(distributed_ozaki2_matmul(
            a, b, mesh, cfg, axis='model', schedule=sched))
        assert np.array_equal(got, ref), f'kshard/{sched}/{tag}'
    got = np.asarray(ozaki2_matmul_mnshard(a, b, mesh, cfg, axis='model'))
    assert np.array_equal(got, ref), f'mnshard/{tag}'
# mesh-shape elasticity: 4-way k-shard reproduces the same bits
mesh2 = make_mesh_compat((2, 4), ('data', 'model'))
cfg = ModularConfig()
ref = np.asarray(ozaki2_matmul(a, b, cfg))
got = np.asarray(distributed_ozaki2_matmul(a, b, mesh2, cfg,
                                           axis='model'))
assert np.array_equal(got, ref), 'kshard 4-way'
# facade: the policy spec routes onto the explicit residue schedules
pol = MatmulPolicy.parse('ozaki2-fp64|shard=model|comm=int8')
ref_f = np.asarray(repro.matmul(a, b, MatmulPolicy.parse('ozaki2-fp64')))
with use_shard_mesh(mesh):
    got_f = np.asarray(repro.matmul(a, b, pol))
assert np.array_equal(got_f, ref_f), 'facade ozaki2 comm=int8'
# schedule validation refuses loudly
try:
    distributed_ozaki2_matmul(a, b, mesh, cfg, schedule='overlap')
    raise SystemExit('unknown schedule must refuse')
except ValueError as e:
    assert 'schedule' in str(e)
print('OK')
""")
    assert "OK" in out


@pytest.mark.xfail(jax.__version__ == "0.4.37", strict=True,
                   reason="with_sharding_constraint on Ozaki operands "
                          "inside _scan_decoder produces wrong logits on "
                          "the pinned jax CPU SPMD stack (ROADMAP 'Known "
                          "limitation (PR 2)'); layers.py therefore only "
                          "constrains 2-D projections. Strict: an XPASS "
                          "after a jax upgrade flags that the 3-D model "
                          "paths can be re-enabled.")
def test_scan_decoder_sharding_constraint_pinned_failure():
    """Pinned repro of the in-scan sharding-constraint miscompilation:
    constrain the 3-D in-scan projections (exactly what layers.py
    refuses to do) and compare logits to the unsharded reference —
    observed max diff ~3.2 on reduced-llama, pure-XLA backend."""
    out = run_multidevice("""
import dataclasses
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.models import init_model, forward_train
from repro.launch.mesh import make_mesh_compat
from repro.parallel.ozaki_shard import (constrain_batched_kshard,
                                        use_shard_mesh)
import repro.models.layers as L

cfg = dataclasses.replace(get_config('llama3.2-3b').reduced(),
                          matmul_precision='ozaki_fp64', ozaki_splits=7)
params, _ = init_model(cfg, jax.random.key(0))
rng = np.random.default_rng(0)
batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)))}
ref, _ = forward_train(cfg, params, batch)

orig = L._matmul_ozaki
def patched(x, w, policy):
    if x.ndim == 3:
        x, w = constrain_batched_kshard(x, w, 'model')
    return orig(x, w, policy)
L._matmul_ozaki = patched
mesh = make_mesh_compat((1, 8), ('data', 'model'))
with use_shard_mesh(mesh):
    sh, _ = jax.jit(lambda p, b: forward_train(cfg, p, b))(params, batch)
diff = float(jnp.max(jnp.abs(sh - ref)))
print('max diff:', diff)
assert diff < 1e-3, f'logits diverge under in-scan constraints: {diff}'
print('OK')
""")
    assert "OK" in out
