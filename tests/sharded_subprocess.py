"""Subprocess body for multi-device sharding tests (8 host devices).

Run as:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         python sharded_subprocess.py <mode>
Prints a single JSON line with the result."""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


def train_parity():
    """Sharded train step on a (4, 2) mesh == single-device step."""
    from repro.dist import partition
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps
    from repro.models import model as M
    from repro.models import modules as nn
    from repro.models.config import ModelConfig
    from repro.optim import adamw

    cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                      n_experts=2, top_k=1, capacity_factor=2.0,
                      dtype="float32")
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 128, (8, 16)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 128, (8, 16)), jnp.int32)}
    ptree = M.init_lm(jax.random.PRNGKey(0), cfg)
    params = nn.unwrap(ptree)
    opt = adamw.init_opt_state(params)
    ocfg = adamw.OptConfig()

    p_ref, _, m_ref = steps.train_step(params, opt, batch, cfg=cfg,
                                       opt_cfg=ocfg)

    mesh = mesh_lib.mesh_for((4, 2), ("data", "model"))
    with partition.mesh_rules(mesh):
        pshard = steps.param_shardings(ptree, mesh)
        oshard = steps.opt_shardings(pshard, mesh)
        bshard = steps.batch_shardings(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         batch), mesh)
        params_s = jax.device_put(params, pshard)
        opt_s = jax.device_put(opt, oshard)
        batch_s = jax.device_put(batch, bshard)
        jfn = jax.jit(lambda p, o, b: steps.train_step(p, o, b, cfg=cfg,
                                                       opt_cfg=ocfg),
                      in_shardings=(pshard, oshard, bshard),
                      out_shardings=(pshard, oshard, None))
        p_sh, _, m_sh = jfn(params_s, opt_s, batch_s)

    errs = [float(np.max(np.abs(np.asarray(a, np.float64) -
                                np.asarray(b, np.float64))) /
                  (np.max(np.abs(np.asarray(a, np.float64))) + 1e-9))
            for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh))]
    print(json.dumps({"max_rel_err": max(errs),
                      "loss_ref": float(m_ref["loss"]),
                      "loss_sh": float(m_sh["loss"])}))


def compressed_psum_test():
    from jax.sharding import PartitionSpec as P
    from repro.dist import collectives
    from repro.dist.compat import shard_map
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.mesh_for((8,), ("pod",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 64, 32)), jnp.float32)

    exact = shard_map(
        lambda v: jax.lax.psum(v[0], "pod"), mesh=mesh,
        in_specs=P("pod", None, None), out_specs=P(None, None))(x)
    # check_vma=False: the compressed reduction is value-replicated (sum of
    # all-gathered blocks) but shard_map cannot prove it
    comp = shard_map(
        lambda v: collectives.compressed_psum(v[0], "pod"), mesh=mesh,
        in_specs=P("pod", None, None), out_specs=P(None, None),
        check_vma=False)(x)
    want = np.sum(np.asarray(x), axis=0)
    rel = float(np.max(np.abs(np.asarray(comp) - want)) /
                np.max(np.abs(want)))
    exact_err = float(np.max(np.abs(np.asarray(exact) - want)))
    print(json.dumps({"rel_err": rel, "exact_is_exact": exact_err}))


def tp_parity():
    """Manual shard_map TP (dist.tp): prefill + greedy decode over the
    model fns must produce the single-device tokens at every eligible mesh
    width, and the compressed seams must stay within int8 tolerance."""
    import functools

    from jax.sharding import PartitionSpec as P
    from repro.dist import tp
    from repro.dist.compat import shard_map
    from repro.launch import mesh as mesh_lib
    from repro.models import model as M
    from repro.models import modules as nn
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=8, n_kv_heads=4, d_ff=256, vocab=128,
                      dtype="float32")
    params = nn.unwrap(M.init_lm(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    inputs = {"tokens": jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32)}
    max_len = 24

    def greedy(prefill_fn, decode_fn, p):
        logits, caches = prefill_fn(p, inputs)
        toks = [np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))]
        for _ in range(4):
            logits, caches = decode_fn(p, caches, jnp.asarray(toks[-1]))
            toks.append(np.asarray(jnp.argmax(logits, -1).astype(jnp.int32)))
        return np.stack(toks, 1), np.asarray(logits)

    ref_toks, ref_logits = greedy(
        jax.jit(functools.partial(M.prefill, cfg=cfg, max_len=max_len)),
        jax.jit(functools.partial(M.decode_step, cfg=cfg)), params)

    paxes = M.param_logical_axes(cfg)
    pspecs = tp.tp_specs(paxes)
    cspecs = tp.tp_specs(M.cache_logical_axes(cfg))
    out = {}
    for n in (2, 4):
        ok, why = tp.tp_eligible(cfg, n)
        assert ok, why
        mesh = mesh_lib.mesh_for((n,), ("model",))
        params_s = jax.device_put(params, tp.tp_shardings(paxes, mesh))

        def rep(tree):
            return jax.tree.map(lambda x: P(*[None] * jnp.ndim(x)), tree)

        def sm_prefill(p, i, *, compressed=False):
            def body(pp, ii):
                with tp.tp_context("model", compressed=compressed):
                    return M.prefill(pp, ii, cfg, max_len=max_len)
            return shard_map(body, mesh=mesh, in_specs=(pspecs, rep(i)),
                             out_specs=(P(), cspecs),
                             check_vma=False)(p, i)

        def sm_decode(p, c, t, *, compressed=False):
            def body(pp, cc, tt):
                with tp.tp_context("model", compressed=compressed):
                    return M.decode_step(pp, cc, tt, cfg)
            return shard_map(body, mesh=mesh, in_specs=(pspecs, cspecs,
                                                        rep(t)),
                             out_specs=(P(), cspecs),
                             check_vma=False)(p, c, t)

        tp_toks, tp_logits = greedy(jax.jit(sm_prefill), jax.jit(sm_decode),
                                    params_s)
        # compressed seams: bounded error vs the exact-psum prefill logits,
        # not bit parity
        logits_x, _ = jax.jit(sm_prefill)(params_s, inputs)
        logits_c, _ = jax.jit(
            functools.partial(sm_prefill, compressed=True))(params_s, inputs)
        out[f"mesh{n}_tokens_equal"] = bool(np.array_equal(ref_toks, tp_toks))
        out[f"mesh{n}_logit_err"] = float(np.max(np.abs(tp_logits -
                                                        ref_logits)))
        out[f"mesh{n}_compressed_rel"] = float(
            np.max(np.abs(np.asarray(logits_c) - np.asarray(logits_x))) /
            (np.max(np.abs(np.asarray(logits_x))) + 1e-9))
    print(json.dumps(out))


def serve_sharded():
    """Tensor-parallel ContinuousEngine == 1-device ContinuousEngine, token
    for token: contiguous + paged layouts, shard_map + GSPMD paths, two mesh
    shapes, two arrival orderings; compressed seams must at least serve."""
    from repro.launch import mesh as mesh_lib
    from repro.models import model as M
    from repro.models import modules as nn
    from repro.models.config import ModelConfig
    from repro.serve.engine import ContinuousEngine, ServeConfig

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=8, n_kv_heads=4, d_ff=256, vocab=128,
                      dtype="float32")
    params = nn.unwrap(M.init_lm(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(7)
    # few distinct lengths -> few prefill compiles; > capacity requests so
    # ordering changes the batching/splicing pattern
    reqs = [(rng.integers(1, 128, n).astype(np.int32), b)
            for n, b in ((6, 5), (12, 4), (6, 6), (18, 3), (12, 5))]

    def run(mesh=None, paged=False, reverse=False, **kw):
        scfg = ServeConfig(max_len=48, capacity=3, paged=paged, page_size=8,
                           prefill_chunk=8 if paged else None, **kw)
        eng = ContinuousEngine(params, cfg, scfg, mesh=mesh)
        order = reqs[::-1] if reverse else reqs
        for p, b in order:
            eng.submit(p, b)
        done = eng.run(max_steps=2000)
        return {tuple(p.tolist()): done[uid].tolist()
                for uid, (p, _) in enumerate(order)}

    ref = run()
    out = {"ref_paged_equal": run(paged=True) == ref}
    for n in (2, 4):
        mesh = mesh_lib.mesh_for((n,), ("model",))
        for paged in (False, True):
            for reverse in (False, True):
                got = run(mesh=mesh, paged=paged, reverse=reverse)
                key = (f"mesh{n}_{'paged' if paged else 'contig'}"
                       f"_{'rev' if reverse else 'fwd'}")
                out[key] = got == ref
        out[f"mesh{n}_gspmd"] = run(mesh=mesh, tp_mode="gspmd") == ref
        comp = run(mesh=mesh, compressed_collectives=True)
        out[f"mesh{n}_compressed_served"] = sorted(
            len(v) for v in comp.values()) == sorted(b for _, b in reqs)
    print(json.dumps(out))


def elastic():
    """Save params sharded on (4,2), restore onto (2,4) and (8,1) —
    values must be identical (mesh-independent checkpoints)."""
    import tempfile

    from repro.checkpoint.ckpt import CheckpointManager
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps
    from repro.models import model as M
    from repro.models import modules as nn
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                      dtype="float32")
    ptree = M.init_lm(jax.random.PRNGKey(3), cfg)
    params = nn.unwrap(ptree)

    mesh_a = mesh_lib.mesh_for((4, 2), ("data", "model"))
    shard_a = steps.param_shardings(ptree, mesh_a)
    params_a = jax.device_put(params, shard_a)

    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        cm.save(1, params_a)
        ok = True
        for shape in ((2, 4), (8, 1), (1, 8)):
            mesh_b = mesh_lib.mesh_for(shape, ("data", "model"))
            shard_b = steps.param_shardings(ptree, mesh_b)
            restored = cm.restore(1, params, shard_b)
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    ok = False
            # restored arrays actually carry the new shardings
            leaf = jax.tree.leaves(restored)[0]
            if leaf.sharding.mesh.shape != mesh_b.shape:
                ok = False
        print(json.dumps({"identical": ok}))


def elastic_supervised():
    """Supervised train on a (4,2) mesh; two workers die permanently mid-run
    → FTManager orders ELASTIC_RESHAPE onto the (2,2) ladder rung; the
    supervisor rebuilds the mesh from the surviving devices and the restore
    reshards every leaf.  Final loss must match the uninterrupted (4,2)
    baseline (restarted arithmetic on a different mesh: tolerance, not
    bit-equality)."""
    import functools
    import tempfile

    from repro.data.pipeline import DataConfig
    from repro.ft import (ChaosEngine, FaultPlan, FTConfig, FTManager,
                          Supervisor)
    from repro.launch import mesh as mesh_lib
    from repro.models.config import ModelConfig
    from repro.optim import adamw
    from repro.train.loop import TrainConfig, train

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                      dtype="float32")
    dcfg = DataConfig(global_batch=8, seq_len=16, vocab=128)
    ocfg = adamw.OptConfig(peak_lr=1e-3, warmup_steps=2, decay_steps=12)
    axes = ("data", "model")
    ladder = (((4, 2), axes), ((2, 2), axes), ((1, 2), axes))

    with tempfile.TemporaryDirectory() as d_base, \
            tempfile.TemporaryDirectory() as d_chaos:
        tcfg_b = TrainConfig(total_steps=12, ckpt_every=4, ckpt_dir=d_base,
                             log_every=1000)
        base = train(cfg, dcfg, tcfg_b, ocfg,
                     mesh=mesh_lib.mesh_for((4, 2), axes))

        # 4 logical workers x 2 chips; clock ticks per heartbeat so the
        # suppressed workers time out deterministically fast
        t = [0.0]
        ft = FTManager(n_workers=4,
                       cfg=FTConfig(heartbeat_timeout_s=1.0,
                                    chips_per_worker=2, mesh_ladder=ladder),
                       clock=lambda: t[0])
        orig_hb = ft.heartbeat

        def ticking_hb(w, lat):
            t[0] += 0.1
            orig_hb(w, lat)

        ft.heartbeat = ticking_hb
        chaos = ChaosEngine(FaultPlan.parse("kill@4:w2:perm,kill@4:w3:perm",
                                            n_workers=4))
        tcfg = TrainConfig(total_steps=12, ckpt_every=4, ckpt_dir=d_chaos,
                           log_every=1000)
        sup = Supervisor(
            functools.partial(train, cfg, dcfg, tcfg, ocfg, ft=ft,
                              chaos=chaos),
            ft=ft, chaos=chaos, mesh=mesh_lib.mesh_for((4, 2), axes),
            mesh_factory=lambda target: mesh_lib.mesh_for(*target),
            sleep=lambda s: None)
        res = sup.run()
        s = res["supervisor"]
        print(json.dumps({
            "step": res["step"],
            "final_loss": res["final_loss"],
            "base_loss": base["final_loss"],
            "events": [e["kind"] for e in s["events"]],
            "final_mesh": list(s["final_mesh"][0]) if s["final_mesh"] else None,
        }))


if __name__ == "__main__":
    mode = sys.argv[1]
    assert len(jax.devices()) == 8, jax.devices()
    {"train_parity": train_parity,
     "compressed_psum": compressed_psum_test,
     "tp_parity": tp_parity,
     "serve_sharded": serve_sharded,
     "elastic": elastic,
     "elastic_supervised": elastic_supervised}[mode]()
