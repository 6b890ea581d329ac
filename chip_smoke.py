#!/usr/bin/env python3
"""Chip smoke: serve granite-3-2b at its published widths on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four replicas, one per chip

One process drives every phase, through the same entry points a user
calls (``service_for_backend`` -> ``AgentService`` -> ``EngineBackend`` ->
``ServeEngine``).  Weights are random, made from ``--seed``.

Default (one chip):

1. granite-3-2b unreduced (40 layers, d_model 2048, vocab 49155, bf16)
   warms up for the workload's prompt buckets, then serves six small-class
   agents at real token lengths until drained.  Every agent must complete,
   the engine must count exactly the decode tokens the agents asked for,
   and every sampled token must lie in ``[0, vocab)``.
2. The reduced config under KV-pool pressure (the repeated swap-out /
   swap-in regime): ``ServeEngine`` must give the same completions, clock
   and token/prefill/swap/decode-step counts as the frozen
   ``ReferenceServeEngine``.  This catches donation and staging-buffer
   faults that only a real device shows.

``--four-chips`` runs only the replicated path: four unreduced replicas
behind ``round_robin``, first advanced concurrently (``fleet_workers=4``)
and then by the sequential loop.  The completions must be identical, and
while the first fleet is alive every device must hold one model's weights.

The script exits non-zero, and prints no result line, if JAX finds no TPU,
if the repository's ``src/`` is not next to it, or if any check fails.  Its
last line on success is ``{"ok": true, "device": {...}}``.  The lines
before it are diagnostics, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

ARCH = "granite-3-2b"
#: small classes whose prompts stay within one 512-token prefill chunk
#: (CC's run past it, which would take the chunked prefill path)
CLASSES = ("EV", "FV", "KBQAV")
MAX_PROMPT = 512


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def workload(seed: int, n_agents: int):
    from repro.api import specs_from_classes

    specs = specs_from_classes(
        np.random.default_rng(seed), n_agents, 10.0, classes=CLASSES
    )
    infs = [s for spec in specs for stage in spec.stages for s in stage]
    check(
        max(s.prefill for s in infs) <= MAX_PROMPT,
        f"a prompt exceeds {MAX_PROMPT} tokens (seed {seed})",
    )
    return specs, sum(s.decode for s in infs)


def build(seed: int, **kw):
    from repro.api import service_for_backend
    from repro.api.workload import V5E_ENGINE_KW

    return service_for_backend(
        "engine", "justitia", arch=ARCH, reduced=False, token_scale=1,
        seed=seed, **V5E_ENGINE_KW, **kw,
    )


def engines(service) -> list:
    """The ``ServeEngine`` of every replica behind ``service``."""
    backend = service.backend
    return [c.engine for c in getattr(backend, "children", [backend])]


def serve(service, specs, demand: int, vocab: int) -> dict:
    """Serve ``specs`` to completion and check the drained result."""
    t0 = time.perf_counter()
    service.submit_many(specs)
    res = service.drain()
    wall = time.perf_counter() - t0
    handles = list(service.handles.values())
    check(
        all(h.done for h in handles) and len(res.finish) == len(specs),
        f"{sum(h.done for h in handles)}/{len(specs)} agents completed",
    )
    metrics = {
        k: sum(e.metrics[k] for e in engines(service))
        for k in ("tokens", "windows", "swaps")
    }
    check(
        metrics["tokens"] == demand,
        f"engines counted {metrics['tokens']} tokens, agents asked for "
        f"{demand}",
    )
    toks = np.concatenate([np.asarray(h.tokens, np.int64) for h in handles])
    check(len(toks) == demand, f"{len(toks)} tokens streamed, want {demand}")
    check(
        bool(((toks >= 0) & (toks < vocab)).all()),
        f"sampled tokens outside [0, {vocab})",
    )
    log(f"serve: wall_s={wall:.3f} tokens={metrics['tokens']} "
        f"demand={demand} completions={len(res.finish)}/{len(specs)} "
        f"windows={metrics['windows']} swaps={metrics['swaps']}")
    return {
        "finish": res.finish,
        "jct": res.jct,
        "event_counts": res.event_counts,
        "tokens": {h.agent_id: list(h.tokens) for h in handles},
    }


def nbytes(tree) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree))


def bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def phase_published(seed: int, dev) -> None:
    import jax

    from repro.api import warmup_engines

    specs, demand = workload(seed, n_agents=6)
    t0 = time.perf_counter()
    service = build(seed)
    (eng,) = engines(service)
    jax.block_until_ready((eng.params, eng.cache))
    setup = time.perf_counter() - t0
    log(f"setup_s={setup:.3f} (params + cache on {eng.device})")
    cfg = eng.model.cfg
    # weight matrices are bf16; norm scales stay float32 by design
    dtypes = {
        str(x.dtype) for x in jax.tree.leaves(eng.params)
        if x.size >= cfg.d_model ** 2
    }
    log(f"model: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
        f"n_heads={cfg.n_heads} n_kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} dtype={cfg.dtype} matrix_dtypes={sorted(dtypes)} "
        f"param_bytes={nbytes(eng.params)} cache_bytes={nbytes(eng.cache)}")
    check(
        (cfg.n_layers, cfg.d_model, cfg.vocab, cfg.dtype)
        == (40, 2048, 49155, "bfloat16") and dtypes == {"bfloat16"},
        "the engine is not serving granite-3-2b at its published widths",
    )
    log(f"after_setup bytes_in_use={bytes_in_use(dev)}")
    t0 = time.perf_counter()
    warmup_engines(service, specs)
    log(f"compile_s={time.perf_counter() - t0:.3f} (warmup)")
    serve(service, specs, demand, cfg.vocab)
    log(f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}")


def phase_reduced_vs_reference(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.core import InferenceSpec, agent_cost, make_scheduler
    from repro.engine import EngineAgent, ReferenceServeEngine, ServeEngine
    from repro.models import Model

    vocab, pool = 256, 320
    model = Model(get_config(ARCH).reduced(vocab=vocab))
    params = model.init(jax.random.PRNGKey(seed))

    def agents():
        # concurrent KV demand ~3x the pool: repeated swap cycles
        rng = np.random.default_rng(seed)
        return [
            EngineAgent(
                i, 2 * i,
                [[(rng.integers(0, vocab, size=40), 48) for _ in range(2)]],
                agent_cost([InferenceSpec(40, 48)] * 2),
            )
            for i in range(4)
        ]

    runs = {}
    for cls in (ServeEngine, ReferenceServeEngine):
        eng = cls(model, params, make_scheduler("justitia", float(pool)),
                  pool_tokens=pool, max_batch=4, cache_len=128)
        for a in agents():
            eng.submit_agent(a)
        done = eng.run_until_idle(max_iters=100_000)
        eng.alloc.check_invariants()
        runs[cls.__name__] = (
            done, eng.now,
            {k: eng.metrics[k]
             for k in ("tokens", "prefills", "swaps", "decode_steps")},
        )
    new, ref = runs["ServeEngine"], runs["ReferenceServeEngine"]
    log(f"reduced: ServeEngine now={new[1]} {new[2]} | "
        f"ReferenceServeEngine now={ref[1]} {ref[2]}")
    check(len(new[0]) == 4, "reduced engine did not complete every agent")
    check(new[2]["swaps"] > 0, "the pressure workload did not swap")
    check(new == ref, "ServeEngine differs from ReferenceServeEngine")


def serve_fleet(seed: int, specs, demand: int, label: str,
                workers) -> tuple[dict, int]:
    """Build four replicas behind ``round_robin``, serve ``specs``, check
    that each device holds one replica; returns what the fleet served and
    one replica's weight bytes.  The fleet is garbage once this returns."""
    import jax

    devices = jax.devices()
    t0 = time.perf_counter()
    service = build(seed, replicas=4, router="round_robin",
                    fleet_workers=workers)
    fleet = engines(service)
    check(
        [e.device for e in fleet] == devices,
        "replica k is not on jax.devices()[k]",
    )
    log(f"{label}: setup_s={time.perf_counter() - t0:.3f}")
    served = serve(service, specs, demand, fleet[0].model.cfg.vocab)
    # one replica = its weights and its cache; a second copy of the
    # weights on any device would mean replicas share a chip
    weights, cache = nbytes(fleet[0].params), nbytes(fleet[0].cache)
    for d in devices:
        used = bytes_in_use(d)
        log(f"{label}: device {d.id} bytes_in_use={used} "
            f"param_bytes={weights} cache_bytes={cache}")
        check(
            weights + cache <= used < 2 * weights + cache,
            f"device {d.id} does not hold exactly one replica",
        )
    service.backend.close()
    return served, weights


def phase_four_chips(seed: int) -> None:
    import jax

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    specs, demand = workload(seed, n_agents=8)
    results = {}
    for label, workers in (("concurrent", 4), ("sequential", None)):
        results[label], weights = serve_fleet(
            seed, specs, demand, label, workers
        )
        gc.collect()
        left = [bytes_in_use(d) for d in devices]
        log(f"{label}: freed, bytes_in_use={left}")
        check(max(left) < weights, f"the {label} fleet was not freed")
    check(
        results["concurrent"] == results["sequential"],
        "concurrent and sequential fleets differ",
    )
    log("four_chips: concurrent == sequential (finish, jct, events, tokens)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica path (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform})",
              file=sys.stderr)
        return 1
    log(f"jax={jax.__version__} platform={dev.platform} "
        f"kind={dev.device_kind} count={len(jax.devices())} "
        f"compile_cache={cache_dir}")
    try:
        if args.four_chips:
            phase_four_chips(args.seed)
        else:
            phase_published(args.seed, dev)
            phase_reduced_vs_reference(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
