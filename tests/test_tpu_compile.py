"""The Pallas sweep kernel compiles for a TPU v5e at the chunk shapes the
sweep driver feeds it.

Nothing here runs: each test lowers ``_hybrid_sweep_scan_pallas`` with
``interpret=False`` against a *described* ``v5e:2x2`` topology and asks the
TPU compiler (installed with jax, no chip needed) to compile it, which
refuses what interpret mode accepts: misaligned block shapes, layouts
Mosaic cannot infer, too much VMEM. The shapes are the driver's:

  * S=32 configs x 4096 apps x 60 bins — the auto chunk of the 32-config
    grid in ``benchmarks/policy_sweep.py``;
  * S=1 x 131072 apps x 240 bins — ``DEFAULT_APP_CHUNK`` at the default
    4-hour histogram;
  * a ragged 37-app chunk (the widest event buckets hold few apps, and the
    kernel then takes the whole chunk as one tile);
  * the benchmark fleet's widest chunks, 20,160 events an app (14 days at
    one call a minute): S=32 x 3072 apps x 60 bins and S=1 x 3072 apps x
    240 bins, where the kernel walks the time axis in many blocks;
  * the app-sharded variant on a 4-chip mesh.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.experiment import EngineOptions
from repro.core.simulator import (_hybrid_sweep_scan_pallas,
                                  _hybrid_sweep_scan_pallas_sharded)
from repro.distributed.scaleout import APP_AXIS

WIDTH = 64          # events per app in the chunk (the <=64 bucket)
WIDEST = 20160      # events per app in the benchmark fleet's widest bucket
TILE = EngineOptions().tile_apps    # the default app tile of a sweep


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _operands(S, n_apps, sharding, rows_sharding=None, width=WIDTH):
    """(gap columns, int32 config block, float32 config block) shapes."""
    f = lambda shape, dt, sh=sharding: jax.ShapeDtypeStruct(shape, dt,
                                                           sharding=sh)
    column = lambda dt: f((n_apps, width), dt, rows_sharding or sharding)
    gaps = (column(jnp.float32), column(jnp.float32), column(jnp.int32))
    return gaps, f((S, 4), jnp.int32), f((S, 7), jnp.float32)


def _assert_kernel_compiled(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("S,n_apps,n_bins", [
    (32, 4096, 60),        # 32-config grid, auto chunk
    (1, 131072, 240),      # one config, DEFAULT_APP_CHUNK, 4-hour range
    (32, 37, 60),          # ragged chunk below one tile
])
def test_sweep_kernel_compiles_for_one_chip(topo, S, n_apps, n_bins):
    one_chip = SingleDeviceSharding(topo.devices[0])
    _assert_kernel_compiled(_hybrid_sweep_scan_pallas.lower(
        *_operands(S, n_apps, one_chip), n_bins, False, TILE))


@pytest.mark.parametrize("S,n_apps,n_bins,tile", [
    (32, 3072, 60, TILE),      # the grid's widest chunk
    (1, 3072, 240, TILE),      # one config's widest chunk
    (32, 3072, 60, 512),       # lane-only tiles of 512 apps
])
def test_sweep_kernel_compiles_at_the_widest_chunks(topo, S, n_apps, n_bins,
                                                    tile):
    one_chip = SingleDeviceSharding(topo.devices[0])
    _assert_kernel_compiled(_hybrid_sweep_scan_pallas.lower(
        *_operands(S, n_apps, one_chip, width=WIDEST), n_bins, False, tile))


def test_sharded_sweep_kernel_compiles_for_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]), (APP_AXIS,))
    rows = NamedSharding(mesh, P(APP_AXIS, None))
    replicated = NamedSharding(mesh, P())
    _assert_kernel_compiled(_hybrid_sweep_scan_pallas_sharded.lower(
        *_operands(32, 4 * 4096, replicated, rows_sharding=rows),
        60, False, TILE, mesh))
