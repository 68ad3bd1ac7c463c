"""Runs one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Stands up the fleet the cell's configuration file describes, with
weights drawn on the device from the seed; warms up every program shape
the cell's traffic can reach; serves the open-loop schedule of the cell's
traffic mix for ``--seconds``; checks the served tokens against the
float32 reference; prints the result as the last line of stdout. With
``--trace 1`` the profiler records the window's last seconds and the
result carries the per-layer metrics and the trace's breakdown;
otherwise it carries the end-to-end metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result. The persistent compilation cache lives in
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")

import spec  # noqa: E402


def trace_seconds(mix: dict) -> float:
    """The traced run records the window's last seconds: at least 5, and
    long enough that about 10 requests arrive in it, so that it holds
    prefills as well as decode steps."""
    return max(5.0, 10.0 / mix["rate_rps"])


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def chips(n: int):
    """The first ``n`` TPU chips, or exit 1."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform}; nothing is measured")
        raise SystemExit(1)
    if len(devices) < n:
        log(f"the cell needs {n} chips, JAX found {len(devices)}")
        raise SystemExit(1)
    return devices


def peak_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no row in bench/peaks.json")
    return table[kind]


def _compile_counter():
    """Counts backend compiles from now on."""
    import jax
    seen = {"n": 0}

    def on_event(name, *args, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


def use_compile_cache() -> None:
    """Every program goes to the persistent cache in the checkout, however
    fast it compiled, so that only a cell's first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: an evicting cache needs an access-time file beside
    # every entry, and one written without it breaks every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    enable_compile_cache()


class Stand:
    """The system under test, stood up for one cell and seed."""

    def __init__(self, bench: dict, cell_name: str, seed: int, devices,
                 root: pathlib.Path = spec.ROOT, peak: dict = None):
        import jax
        import numpy as np

        import deploy
        import trace_reduce
        import warmup
        import weights
        from repro.models import get_model

        self.cell = spec.cell(bench, cell_name)
        self.conf = spec.config(bench, self.cell, root)
        self.mix = spec.traffic(self.cell, root)
        self.arch = spec.arch(self.conf, root)
        self.dims = self.arch.sizes(self.cell["config"], self.conf["model"])
        self.dep = self.conf["deployment"]
        self.peak = peak or peak_of(devices[0].device_kind)
        lay = self.arch.layout(self.dims)
        cfg = self.arch.program(self.dims)
        weights.check_layout(lay, jax.eval_shape(get_model(cfg).init,
                                                 jax.random.PRNGKey(0)))
        params = weights.make_params(lay, seed, devices[0])
        self.hv, self.fleet, self.tenants = deploy.build(cfg, self.dep,
                                                         params)
        del params
        self.warm = warmup.run(self.fleet, self.tenants, self.mix, self.dep,
                               log)
        eng = self.fleet.engine_for(self.tenants[0])
        bucket = warmup.shapes(self.mix, self.dep)["prefill"][0]
        self.modules = {
            "decode": trace_reduce.module_of(eng._decode),
            "prefill": trace_reduce.module_of(
                eng._prefill, eng.params,
                eng._put(np.ones((1, bucket), np.int32)))}
        self.chips = deploy.chips_of(self.fleet, self.tenants)

    def close(self) -> None:
        """Free the program's state on the device."""
        self.fleet.close()
        del self.fleet, self.hv
        gc.collect()


def execute(bench: dict, cell_name: str, seed: int, seconds: float,
            trace: bool, devices, root: pathlib.Path = spec.ROOT,
            control: bool = False, peak: dict = None) -> dict:
    """One run of a cell on ``devices``; returns the result object.
    ``control`` also judges the control under the same limits
    (``control_check``); ``peak`` stands
    in for the device's row of the peaks table."""
    import jax

    import check
    import loop
    import trace_reduce
    import traffic
    from measure import Run

    st = Stand(bench, cell_name, seed, devices, root, peak)
    arrivals = traffic.schedule(st.mix, seed, seconds, len(st.tenants),
                                st.dims.vocab)
    compiles = _compile_counter()
    setup_s = time.perf_counter() - T_START
    log(f"{cell_name}: set-up {setup_s:.1f} s; {len(arrivals)} requests due "
        f"in {seconds} s on {len(st.chips)} x {devices[0].device_kind}")

    tmp = None
    start_trace = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        start_trace = lambda: jax.profiler.start_trace(tmp)   # noqa: E731
    rec = loop.run(st.fleet, st.tenants, arrivals, seconds,
                   trace_from=max(0.0, seconds - trace_seconds(st.mix)),
                   start_trace=start_trace)
    in_window = compiles["n"]
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        reduced = trace_reduce.load(path)
        shutil.rmtree(tmp, ignore_errors=True)
    memory_peak = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in st.chips]

    run = Run(rec=rec, arch=st.arch, dims=st.dims, deployment=st.dep,
              chips=st.cell["chips"], peak=st.peak, setup_s=setup_s,
              memory_peak=memory_peak,
              device_of={t: st.fleet.device_of(t) for t in st.tenants},
              modules=st.modules, trace=reduced)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics(bench, cell_name, kind):
        v = spec.reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    due = run.due_in_window()

    chosen = check.sample(rec, seed, st.conf["check"]["sample_tokens"])
    seqs = check.served(chosen)
    tenants_seen = len({s.tenant for s in chosen})
    del run, chosen
    st.close()
    numbers, readings, ctl_numbers = check.judge(
        st.arch, st.dims, seed, seqs, st.conf["check"], len(st.tenants),
        tenants_seen, control=control)

    result = {
        "correct": check.passes(numbers),
        "attempted": len(due),
        "failed": sum(s.failed is not None for s in due),
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": max(memory_peak)},
        "compiles_in_window": in_window,
        "warmup": st.warm["counts"],
        "readings": readings,
    }
    if reduced is not None:
        t0, t1 = reduced.window
        result["device"]["busy_s"] = trace_reduce.busy_s(reduced)
        result["device"]["window_s"] = t1 - t0
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(reduced),
                               "idle_gaps": trace_reduce.idle_gaps(reduced)}
    if control:
        result["control_check"] = ctl_numbers
    result["check"] = numbers
    log(f"readings {json.dumps(readings)}")
    for name, n in numbers.items():
        log(f"check {name} {n['value']} limit {n['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    devices = chips(spec.cell(bench, args.workload)["chips"])
    use_compile_cache()
    result = execute(bench, args.workload, args.seed, args.seconds,
                     bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
