"""The serving engine's programs compile for a TPU v5e at published widths.

Compiles ``repro.engine.engine``'s jitted hot path for one chip of a
described (not attached) ``v5e:2x2`` topology, at granite-3-2b's published
widths (40 layers, d_model 2048, vocab 49155, bf16) and the one-chip sizes
``chip_smoke.py`` serves at (``V5E_ENGINE_KW``), and at granite-4.0-h-micro's
(36 Mamba-2 and 4 attention layers, vocab 100352) with its benchmark's 16
slots.  Each program must compile, and its argument bytes plus temporaries
must stay under the 15.75 GiB of HBM the compiler allows on a v5e.  The
decode windows, at granite's, h2o-danube-1.8b's and granite-4.0-h-micro's
widths, must also update the one donated cache in place: temporaries under
a quarter of the cache and no copy of the whole stacked K, V or Mamba-2
state; and their attention must read the cache through the Pallas decode
kernel (``repro.kernels.decode_attention``), a Mosaic custom call.  The
kernel engages where ``jax.default_backend()`` is a TPU, which it is not
here, so the selector is patched for these compiles.  Nothing runs, so no
result or time is checked here; parameters and cache are
``ShapeDtypeStruct``s from ``jax.eval_shape``.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler's library.
"""

from __future__ import annotations

import inspect
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api.workload import V5E_ENGINE_KW
from repro.configs import get_config
from repro.engine import ServeEngine
from repro.engine import engine as E
from repro.kernels import decode_attention as decode_kernels
from repro.models import Model
from repro.models import ssm
from repro.models import transformer

#: the HBM a v5e's compiler lets one program use
V5E_HBM = 15.75 * 2**30
#: the largest prompt bucket the one-shot (unchunked) prefill path serves
PROMPT_BUCKET = 512
ENGINE_DEFAULTS = inspect.signature(ServeEngine).parameters
MAX_WINDOW = ENGINE_DEFAULTS["max_window"].default
PREFILL_CHUNK = ENGINE_DEFAULTS["prefill_chunk"].default
#: the hybrid's one-chip engine (``bench/configs/granite-4.0-h-micro.json``)
#: and the largest prompt bucket its traffic draws, prefilled in two slices
HYBRID = "granite-4.0-h-micro"
HYBRID_KW = {"max_batch": 16, "cache_len": 2048}
HYBRID_BUCKET = 768


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def tpu_decode():
    """Choose the decode kernel as a TPU process would."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "decode_kernel",
                   lambda width: decode_kernels.decode_attention)
        yield


@pytest.fixture(scope="module")
def shapes_of(one_chip, tpu_decode):
    """``shapes_of(arch)``: the model and the argument shapes of its
    engine programs, made once per architecture."""

    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            tree,
        )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    made = {}

    def make(arch):
        kw = HYBRID_KW if arch == HYBRID else V5E_ENGINE_KW
        b, t = kw["max_batch"], kw["cache_len"]
        if arch not in made:
            model = Model(get_config(arch))
            params = sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
            cache = sds(jax.eval_shape(lambda: model.init_cache(None, b, t)))
            row = sds(jax.eval_shape(lambda c: E._gather_slot_jit(c, 0),
                                     cache))
            made[arch] = (model, params, cache, row, i32)
        return made[arch]

    return make


@pytest.fixture(scope="module")
def compiled_of(shapes_of):
    """``compiled_of(arch, program)``: the engine program compiled once
    per architecture, at the largest prompt bucket its traffic draws."""
    done = {}

    def compile_(arch, program):
        if (arch, program) not in done:
            bucket = HYBRID_BUCKET if arch == HYBRID else PROMPT_BUCKET
            done[arch, program] = lowerings(
                *shapes_of(arch), bucket=bucket)[program]().compile()
        return done[arch, program]

    return compile_


def lowerings(model, params, cache, row, i32, bucket=PROMPT_BUCKET):
    b, t = cache["kv_pos"].shape[1:]
    k, chunk = MAX_WINDOW, PREFILL_CHUNK
    state = i32(3, b)
    # _prefill_batch pads a pass to the power-of-two ceiling of max_batch
    pad = 1 << (b - 1).bit_length()
    return {
        "decode_window_1": lambda: E._decode_window_jit.lower(
            model, 1, params, cache, state),
        "decode_window_max": lambda: E._decode_window_jit.lower(
            model, k, params, cache, state),
        "fused_window_max": lambda: E._fused_window_jit.lower(
            model, k, chunk, params, cache, state, i32(k, chunk), i32(3)),
        "prefill_write": lambda: E._prefill_write_jit.lower(
            model, t, chunk, params, cache,
            i32(pad, bucket), i32(pad), i32(pad)),
        "gather_slot": lambda: E._gather_slot_jit.lower(cache, i32()),
        "scatter_slot": lambda: E._scatter_slot_jit.lower(cache, row, i32()),
    }


def test_granite_widths_are_published():
    cfg = get_config("granite-3-2b")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab, cfg.dtype) == (
        40, 2048, 49155, "bfloat16"
    )
    assert PROMPT_BUCKET <= PREFILL_CHUNK   # one-shot prefill path


@pytest.mark.parametrize("program", [
    "decode_window_1", "decode_window_max", "fused_window_max",
    "prefill_write", "gather_slot", "scatter_slot",
])
def test_engine_program_fits_one_v5e(compiled_of, program):
    compiled = compiled_of("granite-3-2b", program)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM, (
        f"{program}: {used / 2**30:.2f} GiB of arguments + temporaries "
        f"exceeds the v5e's {V5E_HBM / 2**30} GiB"
    )


@pytest.mark.parametrize("program", [
    "decode_window_1", "decode_window_max", "fused_window_max",
])
@pytest.mark.parametrize("arch", ["granite-3-2b", "h2o-danube-1.8b"])
def test_decode_window_updates_one_cache_in_place(shapes_of, compiled_of,
                                                  arch, program):
    cache = shapes_of(arch)[2]
    compiled = compiled_of(arch, program)
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 4, (
        f"{arch} {program}: {temp / 2**30:.2f} GiB of temporaries against "
        f"a {cache_bytes / 2**30:.2f} GiB cache"
    )
    # an instruction whose result (or async pair) is the stacked K or V
    dims = ",".join(map(str, cache["k"].shape))
    copies = re.findall(rf"= \(?\w+\[{dims}\][^=]* copy(?:-start)?\(",
                        compiled.as_text())
    assert not copies, f"{arch} {program}: whole-cache copies {copies}"


def test_hybrid_widths_are_published():
    cfg = get_config(HYBRID)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab, cfg.dtype) == (
        40, 2048, 100352, "bfloat16"
    )
    assert cfg.layer_types.count("attention") == 4
    assert ssm.mamba2_dims(cfg.d_model, cfg.ssm_state) == (4096, 64, 64, 128)


@pytest.mark.parametrize("program", [
    "decode_window_1", "decode_window_max", "prefill_write", "gather_slot",
    "scatter_slot",
])
def test_hybrid_program_fits_one_v5e(compiled_of, program):
    compiled = compiled_of(HYBRID, program)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM, (
        f"{program}: {used / 2**30:.2f} GiB of arguments + temporaries "
        f"exceeds the v5e's {V5E_HBM / 2**30} GiB"
    )


@pytest.mark.parametrize("program", ["decode_window_1", "decode_window_max"])
def test_hybrid_decode_window_updates_its_state_in_place(shapes_of,
                                                         compiled_of,
                                                         program):
    """The window carries K/V and the Mamba-2 state through its scans: no
    temporary near the size of the cache plus state, and no copy of the
    whole ``mamba_h``, ``k`` or ``v`` stack."""
    cache = shapes_of(HYBRID)[2]
    compiled = compiled_of(HYBRID, program)
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 4, (
        f"{program}: {temp / 2**30:.2f} GiB of temporaries against "
        f"{cache_bytes / 2**30:.2f} GiB of cache and state"
    )
    text = compiled.as_text()
    for leaf in ("mamba_h", "k", "v"):
        dims = ",".join(map(str, cache[leaf].shape))
        copies = re.findall(rf"= \(?\w+\[{dims}\][^=]* copy(?:-start)?\(",
                            text)
        assert not copies, f"{program}: whole-{leaf} copies {copies}"


@pytest.mark.parametrize("arch,program", [
    (arch, program)
    for arch in ("granite-3-2b", "h2o-danube-1.8b")
    for program in ("decode_window_1", "decode_window_max", "fused_window_max")
] + [(HYBRID, "decode_window_1"), (HYBRID, "decode_window_max")])
def test_decode_window_reads_the_cache_through_the_kernel(compiled_of, arch,
                                                          program):
    """The layer scan's attention is the Pallas kernel's Mosaic custom
    call, at 512-wide rows (granite, the hybrid) and 640-wide (danube)."""
    calls = re.findall(r"%decode_attention[.\d]* = [^\n]*"
                       r'custom_call_target="tpu_custom_call"',
                       compiled_of(arch, program).as_text())
    assert calls, f"{arch} {program}: no decode attention kernel"
