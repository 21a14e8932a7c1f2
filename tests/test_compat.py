"""``repro.compat`` coverage (satellite of DESIGN.md §11): meshes built
with Auto axes work with ``jax.shard_map`` / ``jax.set_mesh``, exercised
for real — a 1-device mesh in process, and a fabricated 4-device host
platform in a subprocess (XLA_FLAGS must be set before the first jax
import) — plus the flat cost-analysis dict.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat

pytestmark = pytest.mark.sharding


# ---------------------------------------------------------------------------
# real execution, current runtime, 1 device
# ---------------------------------------------------------------------------

class TestOneDeviceReal:
    def test_make_mesh_shape_and_axes(self):
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        assert mesh.axis_names == ("data", "model")
        assert mesh.devices.size == 1

    def test_set_mesh_is_context_manager(self):
        mesh = compat.make_mesh((1,), ("data",))
        with jax.set_mesh(mesh):
            pass                        # entering/exiting must not raise

    def test_shard_map_psum_identity(self):
        mesh = compat.make_mesh((1,), ("data",))
        f = jax.shard_map(
            lambda x: jax.lax.psum(x, "data"), mesh=mesh,
            in_specs=P("data"), out_specs=P())
        x = jnp.arange(4, dtype=jnp.float32).reshape(1, 4)
        # one shard: psum over a size-1 axis is the identity
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))

    def test_shard_map_axis_names_subset(self):
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        f = jax.shard_map(
            lambda x: x * 2.0, mesh=mesh, in_specs=P("data"),
            out_specs=P("data"), axis_names={"data", "model"})
        x = jnp.ones((2, 3))
        np.testing.assert_array_equal(np.asarray(f(x)), 2.0 * np.ones((2, 3)))


# ---------------------------------------------------------------------------
# real execution, fabricated 4-device host platform (subprocess)
# ---------------------------------------------------------------------------

MULTI_DEVICE_CODE = r"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import compat

assert jax.device_count() == 4
mesh = compat.make_mesh((4,), ("data",))
assert mesh.axis_names == ("data",) and mesh.devices.size == 4

# shard_map psum across the fabricated axis: every shard sees the sum
# (each shard holds a (1, 2) block, so the replicated output keeps it)
f = jax.shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                     in_specs=P("data"), out_specs=P())
x = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
np.testing.assert_array_equal(np.asarray(f(x)),
                              np.asarray(x).sum(axis=0, keepdims=True))

# per-shard identity keeps the sharded layout
g = jax.shard_map(lambda x: x + 1.0, mesh=mesh,
                     in_specs=P("data"), out_specs=P("data"))
y = g(jax.device_put(x, NamedSharding(mesh, P("data"))))
np.testing.assert_array_equal(np.asarray(y), np.asarray(x) + 1.0)

# ambient mesh: jit under set_mesh resolves named shardings
with jax.set_mesh(mesh):
    z = jax.jit(lambda a: a * 2.0)(
        jax.device_put(x, NamedSharding(mesh, P("data"))))
np.testing.assert_array_equal(np.asarray(z), np.asarray(x) * 2.0)

# 2-D mesh over the fabricated devices
mesh2 = compat.make_mesh((2, 2), ("data", "model"))
assert mesh2.shape["data"] == 2 and mesh2.shape["model"] == 2
print("COMPAT_MULTI_OK")
"""


@pytest.mark.slow
def test_multi_device_compat_real():
    res = subprocess.run(
        [sys.executable, "-c", MULTI_DEVICE_CODE], capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        cwd="/root/repo", timeout=600)
    assert "COMPAT_MULTI_OK" in res.stdout, \
        res.stdout[-2000:] + res.stderr[-4000:]


class TestMakeMeshAxisTypes:
    def test_axis_types_attached_when_supported(self, monkeypatch):
        seen = {}
        monkeypatch.setattr(
            compat.jax, "make_mesh",
            lambda shapes, names, **kw: seen.update(kw) or "mesh")
        assert compat.make_mesh((2, 2), ("data", "model"),
                                devices=["d0"]) == "mesh"
        assert seen["axis_types"] == (jax.sharding.AxisType.Auto,) * 2
        assert seen["devices"] == ["d0"]

    def test_real_mesh_axes_are_auto(self):
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2


def test_cost_analysis_is_a_flat_dict():
    compiled = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8))).compile()
    cost = compat.cost_analysis(compiled)
    assert isinstance(cost, dict)
    assert cost["flops"] > 0
