"""parallel.collectives: ring primitives and exact integer reductions.

The ring all-gather and the int32 reduce-scatter are load-bearing for the
int8-slice transport (``parallel.ozaki_shard``): the gather must restore
GLOBAL source order for any ring stride, and the scatter must be exactly
the associative integer sum (bitwise == the reference all-gather + sum).
"""
import pytest

from util import run_multidevice


def test_ring_all_gather_matches_lax_all_gather():
    """hop=1 and a non-contiguous hop=3 ring both reproduce
    ``jax.lax.all_gather`` exactly (source-order restore is by actual
    per-step source id, not by position)."""
    out = run_multidevice("""
import jax, numpy as np, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh_compat
from repro.parallel.collectives import ring_all_gather

mesh = make_mesh_compat((8,), ('data',))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((16, 5)), jnp.float32)

def run(hop):
    def local(blk):
        return ring_all_gather(blk, 'data', 8, hop=hop)
    return jax.shard_map(local, mesh=mesh, in_specs=P('data'),
                         out_specs=P(), check_vma=False)(x)

def ref():
    def local(blk):
        g = jax.lax.all_gather(blk, 'data')      # (8, chunk, 5)
        return g.reshape(-1, g.shape[-1])
    return jax.shard_map(local, mesh=mesh, in_specs=P('data'),
                         out_specs=P(), check_vma=False)(x)

r = np.asarray(ref())
assert np.array_equal(r, np.asarray(x))          # sanity: gather restores x
for hop in (1, 3, 5, 7, 9):                      # 9 % 8 == 1: wrapped stride
    got = np.asarray(run(hop))
    assert np.array_equal(got, r), f'hop={hop}'
print('OK')
""")
    assert "OK" in out


def test_ring_all_gather_rejects_degenerate_ring():
    """gcd(hop, axis_size) != 1 never visits every device — the helper
    must refuse instead of silently dropping source blocks."""
    import math

    from repro.parallel.collectives import ring_all_gather

    import jax.numpy as jnp

    for hop in (2, 4, 6):
        with pytest.raises(ValueError, match="does not generate"):
            ring_all_gather(jnp.zeros((2, 2)), "data", 8, hop=hop)
    assert math.gcd(3, 8) == 1  # the hops the mesh test exercises are rings


def test_reduce_scatter_sum_int32_exact():
    """psum_scatter of int32 == all-gather + exact sum, sliced — the
    bitwise contract the reduce_scatter/rs_stream Ozaki schedules rely
    on (associative integer adds, any reduction order)."""
    out = run_multidevice("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh_compat
from repro.parallel.collectives import psum_exact_int32, reduce_scatter_sum

mesh = make_mesh_compat((8,), ('data',))
rng = np.random.default_rng(1)
# big enough values that float reduction WOULD round: int32 must not
vals = jnp.asarray(rng.integers(-2**24, 2**24, (8, 4, 16)), jnp.int32)

def local_rs(v):
    return reduce_scatter_sum(v[0], 'data', scatter_dim=1)

def local_psum(v):
    return psum_exact_int32(v[0], 'data')

rs = jax.shard_map(local_rs, mesh=mesh, in_specs=P('data', None, None),
                   out_specs=P(None, 'data'), check_vma=False)(vals)
tot = jax.shard_map(local_psum, mesh=mesh, in_specs=P('data', None, None),
                    out_specs=P(), check_vma=False)(vals)
exact = np.asarray(vals, np.int64).sum(axis=0)
assert np.array_equal(np.asarray(tot, np.int64), exact)
assert np.array_equal(np.asarray(rs, np.int64), exact)
print('OK')
""")
    assert "OK" in out
