#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``) and
a traffic mix (``bench/traffic/``); the configuration names its family,
whose module (``bench/reference/<family>.py``) sizes, makes, counts and
checks the model.  Each is found by name.  One run:

1. ``setup``: makes the traffic and the weights from ``--seed`` and builds
   the service through the program's own entry (``AgentService.engine``
   over ``EngineBackend`` / ``ServeEngine``), then warms up every prompt
   bucket the traffic uses (``EngineBackend.warmup``); this is ``setup_s``;
2. ``measure``: serves for ``--seconds`` seconds of host time
   (``loadgen.py``), then drains what arrived; with ``--trace 1`` the
   window's last part runs under the profiler; reads the device's peak
   memory;
3. ``finish``: frees the service, decides ``correct`` (``check.py``)
   against the plain reference, and reads the metrics;
4. prints, last on stderr, each number compared beside its limit, and last
   on stdout one JSON line: with ``--trace 0`` the cell's end-to-end
   metrics, with ``--trace 1`` its per-layer metrics.

It exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not beside it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoDevice(Exception):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix) of a workload name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, load_config(cell["config"]), mix


def load_peaks() -> dict:
    """Device peaks keyed by ``device_kind`` (``bench/peaks.json``)."""
    return load_json(BENCH / "peaks.json")


def require_tpu(chips: int):
    """The devices to serve on; raises NoDevice without enough TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoDevice(f"need {chips} TPU chip(s), JAX found {len(devs)} "
                       f"{devs[0].platform} device(s)")
    return devs


def load_module(directory: Path, name: str):
    """``directory/name.py`` as a module (a configuration's family, the
    generator of a traffic mix, the reader of a metric), found by name and
    loaded once."""
    if str(directory) not in sys.path:
        sys.path.insert(0, str(directory))
    modname = f"bench_{directory.name}_{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(
        modname, directory / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_family(cfg: dict):
    """The module of the configuration's model family."""
    return load_module(BENCH / "reference", cfg["family"])


def metrics_for(bench: dict, cell: dict, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def enable_cache() -> None:
    """JAX's persistent compilation cache, at a fixed place in the checkout
    (the path is part of the cache's key), for every program however fast
    it compiles, with no size limit; and no libtpu log files.

    With a size limit (``JAX_COMPILATION_CACHE_MAX_SIZE``) JAX keeps an
    access-time file beside each entry and, before every write, reads the
    one of every entry: a single entry without one, as a cache filled with
    no limit leaves them, then fails every write.  Without the limit no
    such file is read or written."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class Tracer:
    """The JAX profiler into a temporary directory: the device, and the
    benchmark's own host spans, without per-call Python tracing."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()


def compile_counter():
    """A callable counting programs compiled or loaded since now."""
    import jax
    from jax._src import dispatch

    n = [0]

    def listener(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return lambda: n[0]


def seed_key(seed: int, stream: int = 0):
    """A JAX key from any whole-number seed (also past 32 bits)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def model_config(cfg: dict, family, dims):
    """The program's ``ModelConfig`` at exactly the file's sizes."""
    from repro.configs import get_config

    return dataclasses.replace(get_config(cfg["arch"]),
                               **family.program_fields(dims))


def check_layout(model, params, key) -> None:
    """The weights the benchmark made have the program's tree, shapes and
    dtypes."""
    import jax

    want = jax.eval_shape(model.init, key)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))
    ):
        raise RuntimeError("the benchmark's weights do not match the "
                           "program's parameter layout")


def specs_of(traffic):
    from repro.api import AgentSpec
    from repro.core.cost import InferenceSpec

    return [
        AgentSpec(
            stages=[[InferenceSpec(len(p), d) for p, d in st]
                    for st in a.stages],
            arrival=0.0,
            prompts=[[p for p, _ in st] for st in a.stages],
            name=a.cls,
        )
        for a in traffic.agents
    ]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Setup:
    """What set-up made: the service warmed for every mix's traffic."""

    cfg: dict
    mixes: list               # traffic mixes, each with its traffic and specs
    traffics: list
    specs: list
    family: object
    dims: object
    devs: list
    peak: dict
    params: dict
    service: object
    compiles: object          # callable: programs compiled so far
    setup_s: float


def setup(cell: dict, cfg: dict, mixes: list, seed: int,
          seconds: float) -> Setup:
    """Set-up (``setup_s``): the traffic of each mix, the weights, the
    service, and the warm-up of every prompt bucket the traffic draws."""
    enable_cache()
    import jax

    from repro.api import AgentService
    from repro.models import Model

    devs = require_tpu(cell["chips"])
    peaks = load_peaks()
    if devs[0].device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {devs[0].device_kind!r} "
                         f"in bench/peaks.json")
    compiles = compile_counter()
    family = load_family(cfg)
    dims = family.Dims.from_config(cfg)
    eng_kw = cfg["engine"]
    traffics = [load_module(BENCH / "traffic", m["generator"]).build(
        m, seed, seconds, dims.vocab) for m in mixes]
    longest = max(len(p) + d + 1 for t in traffics for a in t.agents
                  for p, d in a.requests)
    if longest > eng_kw["cache_len"]:
        raise SystemExit(f"the traffic holds a request of {longest} "
                         f"positions; the cache holds {eng_kw['cache_len']}")
    specs = [specs_of(t) for t in traffics]
    model = Model(model_config(cfg, family, dims))
    key = seed_key(seed)
    params = family.make_params(dims, key)
    check_layout(model, params, key)
    service = AgentService.engine(
        model, params, cfg["scheduler"], record_events=False, seed=seed,
        token_scale=1, time_scale=1.0, **eng_kw,
    )
    service.backend.warmup([s for sp in specs for s in sp])
    jax.block_until_ready(service.backend.engine.cache)
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.3f} agents={sum(len(sp) for sp in specs)} "
        f"compiles_in_setup={compiles()}")
    return Setup(cfg=cfg, mixes=mixes, traffics=traffics, specs=specs,
                 family=family, dims=dims, devs=devs,
                 peak=peaks[devs[0].device_kind], params=params,
                 service=service, compiles=compiles, setup_s=setup_s)


def measure(s: Setup, k: int, seconds: float, trace: bool):
    """Serve mix ``k``'s traffic for one window and drain it; a
    ``stats.Run``."""
    import devtrace
    import loadgen
    import stats

    mix = s.mixes[k]
    tracer = Tracer() if trace else None
    served = loadgen.serve(
        s.service, s.traffics[k], s.specs[k], seconds=seconds,
        drain_cap_s=mix["drain_cap_s"],
        max_window=s.cfg["engine"]["max_window"], trace_s=mix["trace_s"],
        tracer=tracer, compile_count=s.compiles,
    )
    mem = s.devs[0].memory_stats() or {}
    summary = None
    if trace:
        ex = devtrace.extract(devtrace.find_xplane(tracer.dir))
        shutil.rmtree(tracer.dir, ignore_errors=True)
        log(f"trace lines: {json.dumps(ex['lines'])}")
        summary = devtrace.summarize(ex, served.trace_window,
                                     served.inflight_intervals())
    return stats.Run(served=served, traffic=s.traffics[k], family=s.family,
                     dims=s.dims, engine=s.cfg["engine"], peak=s.peak,
                     setup_s=s.setup_s, memory=mem, trace=summary)


def finish(bench: dict, cell: dict, s: Setup, run, seed: int, trace: bool,
           control: bool = False) -> dict:
    """Free the service, decide ``correct`` and read the metrics: the
    result line.  ``control`` adds the control's readings (``control.py``)."""
    import check
    import loadgen
    import stats

    served = run.served
    faults = check.bookkeeping(run)
    late = loadgen.lateness(served)

    # ---- the program's state goes; the reference runs on what is left
    s.service = None
    gc.collect()
    chk = s.cfg["check"]
    seqs = check.sequences(run, check.sample(run, chk["sample_requests"],
                                             seed),
                           s.cfg["engine"]["cache_len"])
    step = check.model_step(s.family, s.dims, s.params, seqs, control)
    widest, limit = step["logit_gap"], chk["logit_gap_limit"]
    checks = {"logit_gap": {"value": widest, "limit": limit}}
    checks.update({k: {"value": v, "limit": 0} for k, v in faults.items()})
    correct = (all(v == 0 for v in faults.values()) and None not in
               (widest, limit) and widest <= limit)

    # ---- metrics
    out = {}
    for m in metrics_for(bench, cell, trace):
        v = load_module(BENCH / "metrics", m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev, mem = s.devs[0], run.memory
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(s.devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result = {"correct": bool(correct),
              "attempted": len(run.window_agents()),
              "failed": int(faults["unfinished_agents"]),
              "metrics": out, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        top = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:10]]
        result["breakdown"] = {"device_ops": top(run.trace["programs"]),
                               "idle_gaps": top(run.trace["idle_by_span"])}
    result["diagnostics"] = {
        "compiles_in_window": served.compiles_in_window,
        "submit_late_p90_s": stats.percentile(late, 90),
        "submit_late_max_s": float(late.max()) if len(late) else 0.0,
        "drain_s": served.t_end - served.t_close,
        "tracer_pause_s": sum(b - a for a, b in served.paused),
        "requests_in_window": len(run.window_requests()),
        "tokens_in_window": run.tokens_in(run.t0, run.t1),
        "checked_tokens": step["checked_tokens"],
        "reference_s": time.perf_counter() - served.t_end,
    }
    if control:
        result["control"] = {k: step[k] for k in
                             ("control_gap", "control_mismatch_share")}
    result["checks"] = checks
    return result


def run_cell(bench, cell, cfg, mix, seed: int, seconds: float,
             trace: bool, control: bool = False) -> dict:
    """One run of a cell: set-up, the window, the check; the result."""
    s = setup(cell, cfg, [mix], seed, seconds)
    run = measure(s, 0, seconds, trace)
    return finish(bench, cell, s, run, seed, trace, control)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    bench, cell, cfg, mix = load_cell(args.workload)
    try:
        result = run_cell(bench, cell, cfg, mix, args.seed, args.seconds,
                          bool(args.trace))
    except NoDevice as e:
        log(f"bench/run.py: {e}")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
