"""Smoke run of the serving path on a TPU, at a supported model's full width.

    python chip_smoke.py               # qwen3-1.7b on one chip
    python chip_smoke.py --four-chips  # internlm2-20b, tensor-parallel over 4

One chip: one wall-clock SIP tuning round of the causal flash-attention
kernel at a shape the served prefill dispatches, then eight requests served
by ``ContinuousEngine`` (paged KV cache, chunked prefill, prefix cache,
compiled Pallas kernels) inside that schedule store.  It checks that every
request gets its full token budget, that the lowered serve steps hold
compiled kernels (``tpu_custom_call``), and that the first-step logits of
the Pallas path agree with the plain ``jax.numpy`` path.

Four chips (and nothing else): internlm2-20b with bf16 parameters made in
their tensor-parallel shards, serving a few requests at TP=4; beforehand the
same configuration cut to 4 layers is served on chip 0 alone and at TP=4,
and the two are compared.

Weights are random from a fixed seed.  Each phase prints one line; the last
line is a JSON object naming the device.  The script exits non-zero, and
prints no such line, when a phase fails or JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
NEW_TOKENS = 32
# one odd length; 320+ are prefilled in chunks of PREFILL_CHUNK
PROMPT_LENS = (64, 97, 128, 200, 256, 320, 448)
PREFILL_CHUNK = 256
# the SIP round tunes the prefill that the 256-token prompt dispatches
TUNE_LEN = 256
# the eighth request shares the TUNE_LEN prompt's full pages (a prefix-cache
# hit) and is LATE_LEN tokens long, so its tail is prefilled in chunks
LATE_LEN = 512
PAGE_SIZE = 16
# prompts whose first-step logits are compared across attention paths
REF_LENS = (97, TUNE_LEN)
TP_COMPARE_LENS = (97, 256)
TP_SERVE_LENS = (64, 97, 200, 256)
# Both attention paths compute in bf16 (8 significant bits, a relative step
# of 2**-8) but round in different orders; over 28 layers that moved the
# last-position logits by 2.0% of their largest magnitude in a 28-layer
# bf16 probe, the same as bf16 against f32 there.  A wrong kernel moves
# them by O(1).
LOGIT_RTOL = 5e-2
# tensor-parallel vs one chip: bf16 parameters and partial sums reduced
# across chips in bf16, so again a few bf16 steps per layer
TP_LOGIT_RTOL = 5e-2


class PhaseFailed(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def log(phase: str, **fields) -> None:
    print(f"[chip_smoke] {phase} {json.dumps(fields, default=str)}",
          flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return round(s, 2)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def prompts_for(vocab: int, lens, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def serve(engine, prompts, new_tokens: int, late=()):
    """Submit ``prompts``, take one step, submit ``late`` (so they can hit
    the prefix cache the first step filled), and run to completion."""
    reqs = [engine.submit(p, new_tokens) for p in prompts]
    engine.step()
    reqs += [engine.submit(p, new_tokens) for p in late]
    engine.run(max_steps=10_000)
    short = [(len(r.prompt), len(r.tokens)) for r in reqs
             if len(r.tokens) != new_tokens]
    check(not short, f"requests short of their {new_tokens}-token budget "
                     f"(prompt_len, tokens): {short}")
    return reqs


def first_logits(engine, prompt):
    """Last-position logits of ``prompt`` from the engine's own prefill
    dispatch (the one it serves whole prompts with)."""
    import jax.numpy as jnp
    ps = engine.pages.page_size
    fn = engine._prefill_fn(-(-len(prompt) // ps) * ps)
    logits, _ = fn(engine.params, {"tokens": jnp.asarray(prompt)[None]})
    return logits[0]


def scfg_for(max_len: int, capacity: int):
    from repro.serve.engine import ServeConfig
    return ServeConfig(max_len=max_len, capacity=capacity, paged=True,
                       page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK,
                       prefix_cache=True, seed=SEED)


# ------------------------------------------------------------------ one chip
def phase_tune(cfg, store):
    """One wall-clock SIP round on the causal flash-attention kernel at the
    shape the served prefill of a TUNE_LEN-token prompt dispatches."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.jit import TuneConfig
    from repro.core.registry import registry
    from repro.kernels.flash_attention import ops as fa_ops

    rng = np.random.default_rng(SEED)
    dt = jnp.dtype(cfg.dtype)
    q = jnp.asarray(rng.standard_normal((1, cfg.n_heads, TUNE_LEN, cfg.hd)),
                    dt)
    kv = [jnp.asarray(rng.standard_normal(
        (1, cfg.n_kv_heads, TUNE_LEN, cfg.hd)), dt) for _ in range(2)]
    kern = registry.get(fa_ops.ensure_registered(causal=True), cache=store)
    res, = kern.tune([q, *kv], TuneConfig(energy="wallclock", rounds=1,
                                          cooling=1.3, final_samples=16,
                                          seed=SEED))
    sig = kern.sig_str(kern.static_of(q, *kv))
    entry, = store.entries(kern.name, sig)
    check(np.isfinite(res.initial_raw),
          f"default schedule energy {res.initial_raw}")
    check(entry.tests_passed, "tuned winner failed its final test")
    return {"kernel": kern.name, "signature": json.loads(sig),
            "default_s": res.initial_raw, "winner_s": res.best_raw,
            "improvement": res.improvement, "evals": res.evals,
            "winner": json.loads(res.best.to_json()),
            "final_test": f"PASS({entry.test_samples})"}


def phase_serve(engine, cfg, clock):
    import numpy as np
    prompts = prompts_for(cfg.vocab, PROMPT_LENS, SEED)
    base = prompts[PROMPT_LENS.index(TUNE_LEN)]
    shared = base[:(len(base) - 1) // PAGE_SIZE * PAGE_SIZE]
    tail = prompts_for(cfg.vocab, (LATE_LEN - len(shared),), SEED + 1)[0]
    late = [np.concatenate([shared, tail])]
    t0 = time.perf_counter()
    reqs = serve(engine, prompts, NEW_TOKENS, late=late)
    wall = time.perf_counter() - t0
    m = engine.metrics()
    check(m["prefix_hits"] >= 1, "the shared-prefix request missed the "
                                 "prefix cache")
    toks = sum(len(r.tokens) for r in reqs)
    return {"requests": len(reqs),
            "prompt_lens": [len(r.prompt) for r in reqs],
            "new_tokens": NEW_TOKENS, "tokens": toks,
            "wall_s": round(wall, 3), "compile_s": clock.lap(),
            "tokens_per_s_incl_compile": round(toks / wall, 1),
            "prefix_hits": m["prefix_hits"], "chunk_steps": m["chunk_steps"],
            "prefill_compiles": engine.stats["prefill_compiles"]}


def phase_compiled(engine):
    """The lowered serve steps call compiled Pallas kernels, not the
    interpreter: their text holds Mosaic custom calls."""
    import jax
    import jax.numpy as jnp
    cap = engine.capacity
    decode = engine._decode.lower(
        engine.params, engine.caches, jnp.zeros((cap,), jnp.int32),
        jnp.asarray(engine._pt), jnp.ones((cap,), bool),
        key=jax.random.PRNGKey(0)).as_text()
    prefill = engine._prefill_fn(TUNE_LEN).lower(
        engine.params,
        {"tokens": jnp.zeros((1, TUNE_LEN), jnp.int32)}).as_text()
    out = {"decode_tpu_custom_calls": decode.count("tpu_custom_call"),
           "prefill_tpu_custom_calls": prefill.count("tpu_custom_call")}
    check(all(out.values()), f"serve step without compiled kernels: {out}")
    return out


def phase_reference(engine, cfg):
    """First-step logits: Pallas path against the plain jax.numpy path."""
    from repro.serve.engine import ContinuousEngine
    plain = ContinuousEngine(engine.params,
                             dataclasses.replace(cfg, use_pallas=False),
                             engine.scfg)
    out = {}
    for n in REF_LENS:
        prompt = prompts_for(cfg.vocab, (n,), SEED + 2)[0]
        got, want = first_logits(engine, prompt), first_logits(plain, prompt)
        out[f"len{n}"] = {"rel_err": rel_err(got, want),
                          "argmax_equal": bool(got.argmax() == want.argmax())}
    bad = {k: v for k, v in out.items() if v["rel_err"] > LOGIT_RTOL}
    check(not bad, f"logits beyond rtol {LOGIT_RTOL}: {bad}")
    out["rtol"] = LOGIT_RTOL
    return out


def one_chip(cfg, clock, phases: dict) -> None:
    import jax
    from repro.core.cache import ScheduleCache
    from repro.core.registry import schedule_cache
    from repro.serve.engine import ContinuousEngine, init_params

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    params = init_params(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    run(phases, "init", lambda: {
        "config": cfg.name, "param_bytes": sum(
            x.nbytes for x in jax.tree.leaves(params)),
        "wall_s": round(time.perf_counter() - t0, 3),
        "compile_s": clock.lap()})
    store = ScheduleCache()
    run(phases, "tune", lambda: dict(phase_tune(cfg, store),
                                     compile_s=clock.lap()))
    with schedule_cache(store):
        engine = ContinuousEngine(
            params, cfg, scfg_for(max(PROMPT_LENS + (LATE_LEN,))
                                  + NEW_TOKENS, capacity=8))
        run(phases, "serve", lambda: phase_serve(engine, cfg, clock))
        run(phases, "compiled", lambda: phase_compiled(engine))
        run(phases, "reference", lambda: phase_reference(engine, cfg))
    log("memory", peak_bytes_in_use=peak_bytes(dev))


# --------------------------------------------------------------- four chips
def phase_tp_compare(cfg, mesh, clock):
    """The configuration cut to 4 layers, served on chip 0 alone and at
    TP=4 from the same parameters: first-step logits and greedy tokens."""
    import jax
    from repro.serve.engine import ContinuousEngine, init_params
    small = dataclasses.replace(cfg, n_layers=4)
    params = init_params(jax.random.PRNGKey(SEED), small)
    lens = TP_COMPARE_LENS
    prompts = prompts_for(small.vocab, lens, SEED)
    scfg = scfg_for(max(lens) + NEW_TOKENS, capacity=4)
    one = ContinuousEngine(params, small, scfg)
    four = ContinuousEngine(params, small, scfg, mesh=mesh)
    check(four.tp_path == "shard_map", f"TP path {four.tp_path}: "
                                       f"{four.tp_reason}")
    out = {"n_layers": small.n_layers, "tp_path": four.tp_path}
    errs = [rel_err(first_logits(four, p), first_logits(one, p))
            for p in prompts]
    toks_one = [r.tokens for r in serve(one, prompts, NEW_TOKENS)]
    toks_four = [r.tokens for r in serve(four, prompts, NEW_TOKENS)]
    agree = sum(a == b for x, y in zip(toks_one, toks_four)
                for a, b in zip(x, y))
    out.update(logit_rel_err=errs, rtol=TP_LOGIT_RTOL,
               token_agreement=f"{agree}/{len(prompts) * NEW_TOKENS}",
               compile_s=clock.lap())
    check(max(errs) <= TP_LOGIT_RTOL,
          f"TP=4 logits beyond rtol {TP_LOGIT_RTOL}: {errs}")
    return out


def phase_tp_serve(cfg, mesh, clock):
    """The full-depth model, parameters made in their TP=4 shards."""
    import jax
    from repro.serve.engine import ContinuousEngine, init_params
    t0 = time.perf_counter()
    params = init_params(jax.random.PRNGKey(SEED), cfg, mesh)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    per_dev = {}
    for x in jax.tree.leaves(params):
        for s in x.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    lens = TP_SERVE_LENS
    engine = ContinuousEngine(params, cfg, scfg_for(max(lens) + NEW_TOKENS,
                                                    capacity=4), mesh=mesh)
    t0 = time.perf_counter()
    reqs = serve(engine, prompts_for(cfg.vocab, lens, SEED), NEW_TOKENS)
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in reqs)
    return {"config": cfg.name, "n_layers": cfg.n_layers,
            "param_dtype": cfg.param_dtype, "tp_path": engine.tp_path,
            "param_bytes_per_device": per_dev, "init_s": round(init_s, 3),
            "requests": len(reqs), "prompt_lens": list(lens),
            "tokens": toks, "wall_s": round(wall, 3),
            "tokens_per_s_incl_compile": round(toks / wall, 1),
            "compile_s": clock.lap()}


def four_chips(cfg, clock, phases: dict) -> None:
    import jax
    from repro.launch.mesh import mesh_for
    mesh = mesh_for((4,), ("model",))
    run(phases, "tp_compare", lambda: phase_tp_compare(cfg, mesh, clock))
    gc.collect()     # the 4-layer models leave the chips first
    run(phases, "tp_serve", lambda: phase_tp_serve(cfg, mesh, clock))
    log("memory", peak_bytes_in_use={d.id: peak_bytes(d)
                                     for d in jax.devices()})


# ---------------------------------------------------------------------- main
def run(phases: dict, name: str, fn) -> None:
    t0 = time.perf_counter()
    try:
        out = fn()
    except Exception as e:
        phases[name] = False
        log(name, ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        traceback.print_exc()
        return
    phases[name] = True
    log(name, ok=True, phase_s=round(time.perf_counter() - t0, 3), **out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel phase on four chips")
    args = ap.parse_args()
    need = 4 if args.four_chips else 1

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2

    from repro import configs
    from repro.launch.jax_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    log("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__, compile_cache=cache_dir)
    phases: dict[str, bool] = {}
    t0 = time.perf_counter()
    if args.four_chips:
        cfg = dataclasses.replace(configs.get("internlm2-20b"),
                                  param_dtype="bfloat16", use_pallas=True)
        four_chips(cfg, clock, phases)
    else:
        cfg = dataclasses.replace(configs.get("qwen3-1.7b"), use_pallas=True)
        one_chip(cfg, clock, phases)
    log("done", phases=phases, wall_s=round(time.perf_counter() - t0, 3))
    if not all(phases.values()):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
