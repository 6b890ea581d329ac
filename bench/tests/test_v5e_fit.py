"""Every program the two cells warm compiles for a TPU v5e and fits it.

Compiled for one chip of a described (not attached) ``v5e:2x2`` topology,
at the published widths of both configurations and their cells' engine
sizes: the benchmark's weight maker, the engine's decode windows (one step
and the widest), its batched prefill at the widest batch pad and the
largest prompt bucket the cell's traffic draws (past the 512-token chunk,
so the chunked path), the swap pair, and the plain reference with its
float8 control at the full cache length.  Each must compile, and its
arguments plus temporaries must stay under the 15.75 GiB the compiler lets
a program use.  Nothing runs: no result or time is checked here.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler's library.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import run

V5E_HBM = 15.75 * 2**30
CELLS = {"granite-3-2b": "agents-burst", "h2o-danube-1.8b": "agents-backlog"}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def largest_bucket(name: str) -> int:
    agents_traffic = run.load_module(run.BENCH / "traffic", "agents")
    mix = json.loads((run.BENCH / "traffic" / f"{CELLS[name]}.json")
                     .read_text())
    n = agents_traffic.pool_size(mix, 51)
    p = max(p for _, lengths in agents_traffic.pool(mix, n)
            for stage in lengths for p, _ in stage)
    return -(-p // 64) * 64


def programs(name: str, one_chip):
    from repro.engine import engine as E
    from repro.models import Model

    cfg = run.load_config(name)
    family = run.load_family(cfg)
    dims = family.Dims.from_config(cfg)
    eng = cfg["engine"]
    b, t = eng["max_batch"], eng["cache_len"]
    model = Model(run.model_config(cfg, family, dims))

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    params = sds(jax.eval_shape(family.make_params, dims, jax.random.key(0)))
    cache = sds(jax.eval_shape(lambda: model.init_cache(None, b, t)))
    row = sds(jax.eval_shape(lambda c: E._gather_slot_jit(c, 0), cache))
    state, k, chunk = i32(3, b), eng["max_window"], eng["prefill_chunk"]
    pad, bucket = 1 << (b - 1).bit_length(), largest_bucket(name)
    return {
        "weights": lambda: family.make_params.lower(dims, key),
        "decode_window_1": lambda: E._decode_window_jit.lower(
            model, 1, params, cache, state),
        "decode_window_max": lambda: E._decode_window_jit.lower(
            model, k, params, cache, state),
        "prefill_write_max": lambda: E._prefill_write_jit.lower(
            model, t, chunk, params, cache, i32(pad, bucket), i32(pad),
            i32(pad)),
        "gather_slot": lambda: E._gather_slot_jit.lower(cache, i32()),
        "scatter_slot": lambda: E._scatter_slot_jit.lower(cache, row, i32()),
        "reference": lambda: family.token_gaps.lower(
            dims, "f32", params, i32(t), i32(t)),
        "reference_fp8": lambda: family.token_gaps.lower(
            dims, "fp8", params, i32(t), i32(t)),
    }


@pytest.mark.parametrize("program", [
    "weights", "decode_window_1", "decode_window_max", "prefill_write_max",
    "gather_slot", "scatter_slot", "reference", "reference_fp8",
])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_program_fits_one_v5e(one_chip, name, program):
    compiled = programs(name, one_chip)[program]().compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{name} {program}: args {mem.argument_size_in_bytes / 2**30:.3f}"
          f" GiB temps {mem.temp_size_in_bytes / 2**30:.3f} GiB")
    assert used < V5E_HBM, (
        f"{name} {program}: {used / 2**30:.2f} GiB of arguments + "
        f"temporaries exceeds the v5e's {V5E_HBM / 2**30} GiB"
    )
