"""Read the numbers that decide ``correct`` for the program and for its
float8 control, on many seeds, in one process.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

Each seed is one whole run of the cell (set-up, window, check).  The
control is the plain reference computed in float8 over the same sample
(serving: the token the float8 forward puts first at each served position;
tuning: float8 attention on the same inputs).  One JSON line per seed, then
the largest program reading and the smallest control reading.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import CACHE, ROOT, _jax_setup, load_json, select  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell, centry, _, _ = select(load_json("BENCHMARK.json"), args.workload)
    config = load_json(centry["file"])
    mix = load_json(f"bench/traffic/{cell['traffic']}.json")
    jax = _jax_setup()
    from bench import common
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        common.eprint(f"control: needs {cell['chips']} TPU chip(s)")
        return 2
    if mix["kind"] == "tune":
        from bench import tune as cells
    else:
        from bench import serve as cells
    prog, ctrl = [], []
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        run = common.Run(root=ROOT, cell=cell, config=config, mix=mix,
                         seed=seed, seconds=args.seconds, trace=False,
                         devices=devices[:cell["chips"]],
                         clock=common.CompileClock(), t_start=t,
                         trace_dir=CACHE / "trace" / "control",
                         control=True)
        out = cells.drive(run)
        p, c = out.checks
        prog.append(p.value)
        ctrl.append(c.value)
        print(json.dumps({"seed": seed, "program": p.value,
                          "control": c.value, "limit": c.limit,
                          "attempted": out.attempted}), flush=True)
        t = time.perf_counter()
    print(json.dumps({"workload": args.workload,
                      "program_max": max(prog),
                      "control_min": min(ctrl)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
