"""Serving throughput: continuous batching vs the static-batch baseline.

Mixed-length traffic (uniform prompt lengths and decode budgets) is where
continuous batching earns its keep: the static engine pads every prompt in a
batch to the longest and decodes the whole batch to the largest token budget,
so short requests burn slots as padding; the continuous engine refills each
slot the moment its request finishes.  Both engines run the SAME request
stream at the SAME slot capacity, timed after a warmup pass so jit compiles
are excluded (steady-state serving, the regime the ROADMAP north-star cares
about).

Also re-verifies the engine's correctness contract per run: greedy outputs
must be token-identical to single-request ``Engine.generate`` for every
request across 3 arrival orderings (submit order, reversed, shuffled) — for
the contiguous engine AND the paged one (prefix sharing + chunked prefill
on), which is the differential gate the paged KV cache lands behind.

Two paged-specific sections:

* ``capacity_at_equal_memory`` — the page pool gets exactly the contiguous
  allocation's token memory but twice the slots; page-granular reservations
  (a request holds ceil((plen+new)/page) pages, not a max_len segment) must
  sustain strictly more concurrent requests on the same bytes.
* ``ttft_mixed`` — two long prompts ahead of a burst of short ones; chunked
  prefill must keep the shorts' TTFT p99 no worse than the contiguous
  engine, whose monolithic long prefills stall the admission step.

A ``mesh`` axis reports tensor-parallel serving throughput (contiguous and
paged) at each of ``MESH_SHAPES`` device counts that this process has
devices for (on a CPU host, start it with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

``python benchmarks/serve_throughput.py`` writes ``BENCH_serve.json``;
``--smoke`` shrinks the model and stream for CI.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

CAPACITY = 8


def _model(full: bool):
    import jax
    from repro.models import model as M
    from repro.models import modules as nn
    from repro.models.config import ModelConfig
    cfg = ModelConfig(
        name="serve-bench", family="dense", vocab=1024, dtype="float32",
        **(dict(n_layers=4, d_model=256, n_heads=8, n_kv_heads=4, d_ff=512)
           if full else
           dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128)),
    ).validate()
    params = nn.unwrap(M.init_lm(jax.random.PRNGKey(0), cfg))
    return params, cfg


def _traffic(full: bool, rng: np.random.Generator, vocab: int):
    n = 32 if full else 10
    # bucketed prompt lengths: realistic mixed traffic, bounded prefill
    # retraces for both engines; decode budgets spread wide — the straggler
    # effect static batching pays for
    lens = (8, 16, 24, 32) if full else (4, 8, 12)
    new_lo, new_hi = (8, 48) if full else (3, 8)
    prompts = [rng.integers(0, vocab, int(rng.choice(lens))).astype(np.int32)
               for _ in range(n)]
    budgets = [int(rng.integers(new_lo, new_hi + 1)) for _ in range(n)]
    return prompts, budgets


REPS = 3        # timed repetitions; best-of-N suppresses machine noise


def _run_continuous(params, cfg, scfg, prompts, budgets, mesh=None):
    from repro.serve.engine import ContinuousEngine
    eng = ContinuousEngine(params, cfg, scfg, mesh=mesh)
    wall = float("inf")
    for rep in range(1 + REPS):             # pass 0 warms jit caches
        for p, n in zip(prompts, budgets):
            eng.submit(p, n)
        t0 = time.perf_counter()
        eng.run(max_steps=100_000)
        if rep == 0:
            eng.reset_stats()   # metrics describe the timed (warm) passes
        else:
            wall = min(wall, time.perf_counter() - t0)
    toks = sum(budgets)
    m = eng.metrics()
    return {"wall_s": round(wall, 3), "useful_tokens": toks,
            "tokens_per_s": round(toks / wall, 1),
            "mean_occupancy": round(m["mean_occupancy"], 2),
            "prefill_frac": round(m["prefill_frac"], 3),
            "prefill_compiles": eng.stats["prefill_compiles"]}


def _run_static(params, cfg, scfg, prompts, budgets):
    from repro.serve.engine import Engine, static_batches
    eng = Engine(params, cfg, scfg)
    wall = float("inf")
    for rep in range(1 + REPS):             # pass 0 warms jit caches
        t0 = time.perf_counter()
        decoded = 0
        for padded, new, idxs in static_batches(prompts, budgets,
                                                scfg.capacity):
            decoded += new * len(idxs)
            eng.generate(padded, new)
        if rep > 0:
            wall = min(wall, time.perf_counter() - t0)
    toks = sum(budgets)
    return {"wall_s": round(wall, 3), "useful_tokens": toks,
            "decoded_tokens": decoded,
            "tokens_per_s": round(toks / wall, 1),
            "decode_waste": round(1 - toks / decoded, 3)}


PAGE = 16


def _paged_scfg(scfg, capacity=None, num_pages=None):
    import dataclasses
    from repro.serve.engine import ServeConfig  # noqa: F401 (doc anchor)
    return dataclasses.replace(
        scfg, paged=True, page_size=PAGE, prefill_chunk=PAGE,
        capacity=capacity if capacity is not None else scfg.capacity,
        num_pages=num_pages)


def _run_paged(params, cfg, scfg, prompts, budgets, mesh=None):
    from repro.serve.engine import ContinuousEngine
    eng = ContinuousEngine(params, cfg, _paged_scfg(scfg), mesh=mesh)
    wall = float("inf")
    for rep in range(1 + REPS):             # pass 0 warms jit caches
        for p, n in zip(prompts, budgets):
            eng.submit(p, n)
        t0 = time.perf_counter()
        eng.run(max_steps=100_000)
        if rep == 0:
            eng.reset_stats()   # metrics describe the timed (warm) passes
        else:
            wall = min(wall, time.perf_counter() - t0)
    toks = sum(budgets)
    m = eng.metrics()
    return {"wall_s": round(wall, 3), "useful_tokens": toks,
            "tokens_per_s": round(toks / wall, 1),
            "mean_occupancy": round(m["mean_occupancy"], 2),
            "prefill_compiles": eng.stats["prefill_compiles"],
            "prefix_hits": int(m["prefix_hits"]),
            "prefix_tokens_saved": int(m["prefix_tokens_saved"]),
            "chunk_steps": int(m["chunk_steps"]),
            "page_size": PAGE}


def _drive_peak(eng, prompts, budgets):
    """Submit everything, step to drain; returns (wall_s, peak and mean
    concurrent requests) — the steady-state capacity measure."""
    for p, n in zip(prompts, budgets):
        eng.submit(p, n)
    peak, occ_sum, steps = 0, 0, 0
    t0 = time.perf_counter()
    while not eng.pool.idle:
        eng.step()
        peak = max(peak, eng.pool.occupancy)
        occ_sum += eng.pool.occupancy
        steps += 1
    return time.perf_counter() - t0, peak, occ_sum / max(steps, 1)


def _capacity_at_equal_memory(params, cfg, scfg, prompts, budgets) -> dict:
    """Same KV bytes, page-granular bookkeeping: the paged pool holds
    exactly the contiguous engine's capacity*max_len token memory (plus the
    one trash page) but twice the slots — page-rounded per-request
    reservations are what let extra requests fit."""
    from repro.serve.engine import ContinuousEngine
    token_mem = scfg.capacity * (-(-scfg.max_len // PAGE)) * PAGE
    pscfg = _paged_scfg(scfg, capacity=2 * scfg.capacity,
                        num_pages=token_mem // PAGE + 1)
    out = {}
    for name, sc in (("contiguous", scfg), ("paged", pscfg)):
        eng = ContinuousEngine(params, cfg, sc)
        _drive_peak(eng, prompts, budgets)          # warm the jit caches
        wall, peak, mean = float("inf"), 0, 0.0
        for _ in range(REPS):
            w, p, m = _drive_peak(eng, prompts, budgets)
            if w < wall:
                wall, peak, mean = w, p, m
        out[name] = {"wall_s": round(wall, 3), "slots": sc.capacity,
                     "kv_token_memory": token_mem,
                     "peak_concurrency": peak,
                     "mean_concurrency": round(mean, 2),
                     "tokens_per_s": round(sum(budgets) / wall, 1)}
    out["paged_higher_capacity"] = (
        out["paged"]["peak_concurrency"]
        > out["contiguous"]["peak_concurrency"])
    return out


def _ttft_mixed(params, cfg, scfg, full: bool) -> dict:
    """Two long prompts submitted ahead of a short burst: the shorts' TTFT
    p99 gates the chunked-prefill claim (no worse than contiguous, whose
    long prefills run monolithically inside the admission step)."""
    from repro.serve.engine import ContinuousEngine
    rng = np.random.default_rng(11)
    long_len = scfg.max_len - (16 if full else 8)
    n_short = 24 if full else 8
    longs = [(rng.integers(0, cfg.vocab, long_len).astype(np.int32), 8)
             for _ in range(2)]
    shorts = [(rng.integers(0, cfg.vocab, 8).astype(np.int32), 4)
              for _ in range(n_short)]
    out = {}
    for name, sc in (("contiguous", scfg), ("paged", _paged_scfg(scfg))):
        best = None
        for rep in range(1 + REPS):         # pass 0 warms jit caches
            eng = ContinuousEngine(params, cfg, sc)
            hl = [eng.submit(p, n) for p, n in longs]
            hs = [eng.submit(p, n) for p, n in shorts]
            eng.run(max_steps=100_000)
            if rep == 0:
                continue
            ttft = sorted(r.admitted_at - r.submitted_at for r in hs)
            p99 = float(np.percentile(ttft, 99))
            overall = float(np.percentile(
                [r.admitted_at - r.submitted_at for r in hl + hs], 99))
            if best is None or p99 < best["short_ttft_p99_ms"] / 1e3:
                best = {"short_ttft_p99_ms": round(p99 * 1e3, 1),
                        "all_ttft_p99_ms": round(overall * 1e3, 1)}
        out[name] = best
    # 10% head-room absorbs scheduler noise on a shared machine
    out["paged_no_worse"] = (out["paged"]["short_ttft_p99_ms"]
                             <= 1.10 * out["contiguous"]["short_ttft_p99_ms"])
    return out


MESH_SHAPES = (2, 4)   # tensor-parallel widths benchmarked per run


def _mesh_args(full: bool):
    from repro.serve.engine import ServeConfig
    params, cfg = _model(full)
    rng = np.random.default_rng(0)
    prompts, budgets = _traffic(full, rng, cfg.vocab)
    scfg = ServeConfig(max_len=max(len(p) for p in prompts) + max(budgets),
                       capacity=CAPACITY if full else 4)
    return params, cfg, scfg, prompts, budgets


def _bench_mesh(full: bool) -> dict:
    """The bench stream served tensor-parallel over each ("model",) mesh
    width this process's devices allow — contiguous and paged, all in this
    one process (a chip belongs to the process that first touches it)."""
    import jax
    from repro.dist import tp
    from repro.launch.mesh import mesh_for
    params, cfg, scfg, prompts, budgets = _mesh_args(full)
    out = {}
    for n in MESH_SHAPES:
        if n > len(jax.devices()):
            out[f"mesh{n}"] = {"skipped": f"{len(jax.devices())} devices"}
            continue
        mesh = mesh_for((n,), ("model",))
        ok, reason = tp.tp_eligible(cfg, n)
        out[f"mesh{n}"] = {
            "devices": n, "tp_path": "shard_map" if ok else "gspmd",
            "tp_reason": reason,
            "continuous": _run_continuous(params, cfg, scfg, prompts,
                                          budgets, mesh=mesh),
            "paged": _run_paged(params, cfg, scfg, prompts, budgets,
                                mesh=mesh)}
    return out


def _differential(params, cfg, scfg, prompts, budgets) -> dict:
    """Greedy token-identity vs single-request generate, 3 arrival orders."""
    from repro.serve.engine import ContinuousEngine, Engine
    ref = Engine(params, cfg, scfg)
    want = [ref.generate(p[None], n)[0] for p, n in zip(prompts, budgets)]
    rng = np.random.default_rng(7)
    orders = [list(range(len(prompts))),
              list(range(len(prompts)))[::-1],
              list(rng.permutation(len(prompts)))]
    identical = 0
    for order in orders:
        eng = ContinuousEngine(params, cfg, scfg)
        handles = {j: eng.submit(prompts[j], budgets[j]) for j in order}
        out = eng.run(max_steps=100_000)
        if all(np.array_equal(out[handles[j].uid], want[j])
               for j in range(len(prompts))):
            identical += 1
    return {"orderings": len(orders), "identical": identical,
            "token_identical": identical == len(orders)}


def bench(full: bool = True) -> dict:
    from repro.serve.engine import ServeConfig
    params, cfg = _model(full)
    rng = np.random.default_rng(0)
    prompts, budgets = _traffic(full, rng, cfg.vocab)
    scfg = ServeConfig(max_len=max(len(p) for p in prompts) + max(budgets),
                       capacity=CAPACITY if full else 4)
    # differential first (small subset in full mode keeps the reference pass
    # cheap without weakening the orderings check)
    k = 12 if full else len(prompts)
    diff = _differential(params, cfg, scfg, prompts[:k], budgets[:k])
    paged_diff = _differential(params, cfg, _paged_scfg(scfg),
                               prompts[:k], budgets[:k])
    cont = _run_continuous(params, cfg, scfg, prompts, budgets)
    stat = _run_static(params, cfg, scfg, prompts, budgets)
    paged = _run_paged(params, cfg, scfg, prompts, budgets)
    cap = _capacity_at_equal_memory(params, cfg, scfg, prompts, budgets)
    ttft = _ttft_mixed(params, cfg, scfg, full)
    mesh = _bench_mesh(full)
    return {
        "config": {"mode": "full" if full else "smoke",
                   "capacity": scfg.capacity, "requests": len(prompts),
                   "model": cfg.name, "max_len": scfg.max_len},
        "continuous": cont, "static": stat, "differential": diff,
        "paged": paged, "paged_differential": paged_diff,
        "capacity_at_equal_memory": cap, "ttft_mixed": ttft, "mesh": mesh,
        "speedup_tokens_per_s": round(cont["tokens_per_s"]
                                      / stat["tokens_per_s"], 2),
    }


def run(full: bool = True):
    """benchmarks.run harness entry — CSV rows."""
    res = bench(full)
    for key in ("differential", "paged_differential"):
        if not res[key]["token_identical"]:
            raise AssertionError(
                f"{key}: engine diverged from single-request generation "
                f"({res[key]['identical']}/{res[key]['orderings']} "
                f"orderings identical)")
    cap = res["capacity_at_equal_memory"]
    return [("serve/continuous_vs_static_speedup",
             res["speedup_tokens_per_s"],
             f"cont={res['continuous']['tokens_per_s']}tok/s "
             f"static={res['static']['tokens_per_s']}tok/s "
             f"occupancy={res['continuous']['mean_occupancy']} "
             f"decode_waste={res['static']['decode_waste']:.0%} "
             f"diff_identical={res['differential']['token_identical']}"),
            ("serve/paged_peak_concurrency_at_equal_memory",
             cap["paged"]["peak_concurrency"],
             f"contiguous={cap['contiguous']['peak_concurrency']} "
             f"paged={cap['paged']['peak_concurrency']} on "
             f"{cap['paged']['kv_token_memory']} cached tokens; "
             f"short_ttft_p99 paged="
             f"{res['ttft_mixed']['paged']['short_ttft_p99_ms']}ms vs "
             f"contiguous="
             f"{res['ttft_mixed']['contiguous']['short_ttft_p99_ms']}ms")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + short stream (CI)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    res = bench(full=not args.smoke)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1, sort_keys=True)
        f.write("\n")
    cap = res["capacity_at_equal_memory"]
    print(f"continuous {res['continuous']['tokens_per_s']} tok/s vs "
          f"static {res['static']['tokens_per_s']} tok/s "
          f"({res['speedup_tokens_per_s']}x), differential "
          f"{res['differential']['identical']}/"
          f"{res['differential']['orderings']} orderings identical, paged "
          f"{res['paged_differential']['identical']}/"
          f"{res['paged_differential']['orderings']}")
    print(f"equal-memory concurrency: paged "
          f"{cap['paged']['peak_concurrency']} vs contiguous "
          f"{cap['contiguous']['peak_concurrency']} "
          f"(higher={cap['paged_higher_capacity']}); mixed-trace short "
          f"TTFT p99 paged {res['ttft_mixed']['paged']['short_ttft_p99_ms']}"
          f"ms vs contiguous "
          f"{res['ttft_mixed']['contiguous']['short_ttft_p99_ms']}ms "
          f"(no_worse={res['ttft_mixed']['paged_no_worse']})")
    for key, m in sorted(res["mesh"].items()):
        if "skipped" in m:
            print(f"{key}: skipped ({m['skipped']})")
        else:
            print(f"{key} ({m['tp_path']}): continuous "
                  f"{m['continuous']['tokens_per_s']} tok/s, paged "
                  f"{m['paged']['tokens_per_s']} tok/s")
    print(f"wrote {args.out}")
    for key in ("differential", "paged_differential"):
        if not res[key]["token_identical"]:
            raise SystemExit(f"{key} correctness check FAILED")
    if not cap["paged_higher_capacity"]:
        raise SystemExit("equal-memory capacity check FAILED")
    if not res["ttft_mixed"]["paged_no_worse"]:
        raise SystemExit("mixed-trace TTFT p99 check FAILED")


if __name__ == "__main__":
    main()
