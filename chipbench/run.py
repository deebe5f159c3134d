"""Run one benchmark cell once, on the chips of this machine.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: ``BENCHMARK.json`` lists the
cells and metrics, ``chipbench/workloads/<cell>.json`` names the cell's
configuration (``chipbench/configs/<config>.json``), its driver
(``chipbench/drivers/<driver>.py``) and its traffic, and each per-layer
metric is read by ``chipbench/metrics/<metric>.py``.

A run:

1. refuses, with a non-zero exit and no result, a machine where JAX finds
   no TPU or fewer chips than the cell asks for;
2. turns on the persistent compilation cache and lets the driver warm up
   every program the window will run (``setup_s``: process start to the
   first measured call);
3. measures: the driver's calls run back to back from the window's start,
   and the window closes when the first call ends after ``--seconds``.
   The rate is all the work of those calls over that span.  Every compile
   (or compile-cache load) inside the window is counted;
4. with ``--trace 1``, records a profiler trace of the window's first
   call and reports the per-layer metrics, read from it, instead of the
   end-to-end ones;
5. reads the peak device memory (``memory_peak``), then lets the driver
   compare what the window produced with the plain reference.  Each
   number compared is printed beside its limit, on standard error and in
   the result line.

The last line on standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced, ``breakdown``,
then ``checks``.
"""
from __future__ import annotations

import time

_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# JAX's own duration events (as chip_smoke.py reads them): XLA compile or
# compile-cache load, and tracing plus lowering.
_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Compiles:
    """Counts the compiles, cache hits and their seconds while listening."""

    def __init__(self):
        self.count = self.cache_hits = 0
        self.compile_s = self.trace_s = 0.0

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == _COMPILE:
            self.count += 1
            self.compile_s += secs
        elif event in _TRACE:
            self.trace_s += secs

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    @contextlib.contextmanager
    def listening(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self._duration)
            jax.monitoring.unregister_event_listener(self._event)

    def as_dict(self, prefix: str) -> dict:
        return {f"{prefix}compiles": self.count,
                f"{prefix}cache_hits": self.cache_hits,
                f"{prefix}compile_s": self.compile_s,
                f"{prefix}trace_s": self.trace_s}


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (a fixed directory of the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), holding every program,
    small ones too, so that only a checkout's first run of a cell
    compiles."""
    import jax

    from repro.compile_cache import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The metrics of ``kind`` (``end_to_end`` / ``per_layer``) this cell
    reports: those that list it, or that list no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def devices_or_none(chips: int):
    """The chips this run uses, or ``None`` (with the reason on stderr)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"chipbench: the cell needs {chips} chips, JAX sees "
              f"{len(devices)}; nothing was run", file=sys.stderr)
        return None
    return devices[:chips]


class Tracer:
    """The profiler around the window's first call (``--trace 1``).

    One call is one whole grid: seconds of device work, and millions of
    device events per chip, which is what a trace can hold.  The first
    chip alone is traced.  The host span ``chipbench.window`` marks the
    traced part.
    """

    def __init__(self):
        self.log_dir = None
        self.span = None

    def start(self) -> None:
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation

        from chipbench.trace import WINDOW

        self.log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        # One chip only: the chips of a sharded grid run equal shares of
        # the same program, and collecting four chips' events takes the
        # profiler 272 s against 110 s for one (TPU v5 lite).
        opts.advanced_configuration = {"tpu_num_chips_to_profile_per_task": 1}
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.span = TraceAnnotation(WINDOW)
        self.span.__enter__()

    def stop(self) -> None:
        import jax

        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
            t = time.perf_counter()
            jax.profiler.stop_trace()
            print(json.dumps({"trace_stop_s": time.perf_counter() - t}),
                  flush=True)

    def reduce(self) -> dict:
        from chipbench import trace as trace_lib

        try:
            return trace_lib.reduce(trace_lib.find_xplane(self.log_dir))
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


def memory_peak(devices, program_bytes: int) -> tuple[int, dict]:
    """The peak device memory of the fullest chip, and what it was read
    from.

    A TPU's allocator counts apart the buffers that JAX holds (operands,
    results: ``peak_bytes_in_use``) and the memory it reserves for a
    loaded program's temporaries (``peak_bytes_reserved``); the peak is
    their sum.  ``program_bytes``, the window's program as compiled for
    one chip, is printed beside it as a check.
    """
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    return peak, {"program_bytes": program_bytes, "memory_stats": stats[0]}


def measure(driver, seconds: float, tracer=None) -> dict:
    """Drive calls back to back until the first one that ends after
    ``seconds``; return the window's length, work and call count.  A
    ``tracer`` records the first call."""
    work, calls = 0, 0
    t0 = time.perf_counter()
    if tracer is not None:
        tracer.start()
    while True:
        work += driver.call()
        calls += 1
        if tracer is not None:
            tracer.stop()
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            return {"window_s": t1 - t0, "work": work, "calls": calls}


def run(args, devices, root: pathlib.Path = ROOT) -> dict:
    """One run of ``args.workload`` on ``devices``; the result line's
    fields.  ``root`` is the checkout whose ``BENCHMARK.json`` and
    ``chipbench/`` files define the cell."""
    files = root / "chipbench"
    bench = load_json(root / "BENCHMARK.json")
    wl = load_json(files / "workloads" / f"{args.workload}.json")
    cfg = load_json(files / "configs" / f"{wl['config']}.json")
    drv_mod = load_module(files / "drivers" / f"{wl['driver']}.py")

    cache_dir = enable_compile_cache()

    driver = drv_mod.Driver(cfg, wl["traffic"], seed=args.seed,
                            devices=devices, variant=args.variant)
    setup = Compiles()
    with setup.listening():
        driver.setup()
    setup_s = time.perf_counter() - _START

    tracer = Tracer() if args.trace else None
    window = Compiles()
    try:
        with window.listening():
            win = measure(driver, args.seconds, tracer)
    finally:
        if tracer is not None:
            tracer.stop()

    peak, memory = memory_peak(devices, driver.program_bytes())
    info = {"setup_s": setup_s, **setup.as_dict("setup_"),
            **window.as_dict("window_"), **win, "compile_cache": cache_dir,
            **memory}
    print(json.dumps({"chipbench": args.workload, **info}), flush=True)

    result = {"metrics": {}}
    if args.trace:
        t = time.perf_counter()
        red = tracer.reduce()
        print(json.dumps({"trace_reduce_s": time.perf_counter() - t,
                          "ops_covered_s": red["ops_covered_s"],
                          "ops_complete": red["ops_complete"],
                          "loop_s": red["loop_s"],
                          "busy_by_device": red["busy_by_device"],
                          "traced_window_s": red["window_s"]}), flush=True)
        ctx = {"trace": red, "layers": driver.layer_counts(1)}
        for m in cell_metrics(bench, args.workload, "per_layer"):
            value = load_module(files / "metrics" / f"{m['name']}.py").read(
                ctx
            )
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
    else:
        values = {"setup_s": setup_s,
                  drv_mod.RATE: win["work"] / win["window_s"]}
        for m in cell_metrics(bench, args.workload, "end_to_end"):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        extra = {}

    t = time.perf_counter()
    checks, attempted, failed = driver.check()
    print(json.dumps({"check_s": time.perf_counter() - t}), flush=True)
    dev = devices[0]
    out = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": result["metrics"],
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak,
                   **extra},
    }
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # The control of the correctness check (PERF.md): the program run with
    # a guarantee of the configuration broken.  Never used by a measured run.
    ap.add_argument("--variant", choices=("control",), default=None)
    args = ap.parse_args(argv)

    wl_path = BENCH / "workloads" / f"{args.workload}.json"
    if not wl_path.is_file():
        print(f"chipbench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    devices = devices_or_none(load_json(wl_path)["chips"])
    if devices is None:
        return 2
    out = run(args, devices)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
