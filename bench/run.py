"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic mix are found by name in
``BENCHMARK.json`` at the checkout's root; the per-layer metrics are read by
``bench/metrics/<name>.py``.  A run does set-up (counted in ``setup_s``),
measures for ``--seconds``, then checks what the timed path produced against
the plain reference.  With ``--trace 1`` the middle of the window is traced
with the JAX profiler and the result holds the per-layer metrics instead of
the end-to-end ones.

The run exits with code 2, and prints no result, when JAX finds no TPU, or
fewer chips than the cell asks for, or a chip the peaks table lacks.
JAX's persistent compilation cache lives in ``.bench_cache/jax`` in the
checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE = ROOT / ".bench_cache"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def select(bench: dict, name: str):
    """(cell, configuration entry, end-to-end metrics, per-layer metrics)."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, name) and m["moves"] in reported]
    return cell, config, e2e, layer


def reader(root: pathlib.Path, metric: str):
    from bench import common
    return common.load_file(root, f"bench/metrics/{metric}.py").read


def _jax_setup():
    """Compilation cache at a fixed path in the checkout, for every
    program this process compiles; the TPU runtime's own logs, which go to
    a fixed path under /tmp by default, off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def load_json(rel: str) -> dict:
    with open(ROOT / rel) as f:
        return json.load(f)


def main(argv=None, *, require_chip: bool = True, load=load_json) -> int:
    """``load`` reads a JSON file by its path in the checkout (tests pass
    their own, to run a cell at a small size on the CPU)."""
    args = parse(argv)
    bench = load("BENCHMARK.json")
    cell, centry, e2e, layer = select(bench, args.workload)
    config = load(centry["file"])
    mix = load(f"bench/traffic/{cell['traffic']}.json")
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    jax = _jax_setup()
    from bench import common, peaks, trace_reduce
    devices = jax.devices()
    chips = cell["chips"]
    if require_chip:
        if devices[0].platform != "tpu" or len(devices) < chips:
            common.eprint(f"bench: {args.workload} needs {chips} TPU "
                          f"chip(s); JAX found {len(devices)} "
                          f"{devices[0].platform} device(s) "
                          f"({devices[0].device_kind})")
            return 2
        try:
            peaks.for_kind(devices[0].device_kind)
        except KeyError as e:
            common.eprint(f"bench: {e}")
            return 2
    devices = devices[:chips]
    trace_dir = CACHE / "trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    run = common.Run(root=ROOT, cell=cell, config=config, mix=mix,
                     seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices,
                     clock=common.CompileClock(), t_start=T_START,
                     trace_dir=trace_dir)
    common.log("device", **common.device_info(devices), jax=jax.__version__,
               workload=args.workload, seed=args.seed, seconds=args.seconds,
               trace=args.trace)
    if mix["kind"] == "tune":
        from bench import tune as cells
    else:
        from bench import serve as cells
    out = cells.drive(run)
    device = dict(common.device_info(devices),
                  memory_peak_bytes=out.memory_peak_bytes)
    result = {"correct": all(c.ok for c in out.checks),
              "attempted": out.attempted, "failed": out.failed}
    metrics = {}
    if args.trace:
        red = trace_reduce.reduce(trace_reduce.find_xplane(str(trace_dir)))
        out.window.traced = red
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        for m in layer:
            v = reader(ROOT, m["name"])(out.window)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result.update(metrics=metrics, device=device,
                      breakdown=red.breakdown())
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
        result.update(metrics=metrics, device=device)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    for c in out.checks:
        common.eprint(f"check {c.name} {c.value!r} limit {c.limit!r} "
                      f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
